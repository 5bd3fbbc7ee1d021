"""``xla_shared_memory`` — the TPU-native device-side data path.

API-parity port target: reference ``tritonclient/utils/cuda_shared_memory``
(`__init__.py:107-429`, `_utils.py:49-121`) — same function names and call
shapes, so the reference's ``simple_*_cudashm_*`` examples run with an import
swap (a ``cuda_shared_memory`` alias module is provided for exactly that).

TPU translation of the cudaIPC design (BASELINE.json north star; SURVEY.md
§3.5/§7 hard parts (a)).  A chip belongs to ONE process, so which half of
the design a region uses is fixed when it is created, by whether a server
runs in this process (``broker().server_present``):

* **co-located** (``ServerHarness`` in this process — the zero-copy
  topology): cudaMalloc → a **region slot** in the process-local broker
  holding the current immutable ``jax.Array`` (PjRt buffer; "writing" a
  region rebinds the slot); cudaMemcpyAsync → ``jax.device_put`` / DLPack
  zero-copy ingest.  The server shares the slot, tensors stay in HBM.
* **out of process** (a client of a server that owns the chip — the
  README quickstart, ``tpu-perf-analyzer --shared-memory=xla``, the
  cudashm examples): the region IS its POSIX host-shm staging region plus
  an 8-byte generation counter; create/set/get never import JAX, let
  alone open a backend — exactly what the C++ client does
  (``native/client/xla_shm_utils.h``).  PjRt has no cudaIpcOpenMemHandle
  equivalent, so the server pays one host↔device DMA per changed region.
* cudaIpcGetMemHandle → ``get_raw_handle``: a JSON descriptor carrying the
  slot uuid (resolves only in the server's own process) and the staging
  keys (resolve from any process).
* cudaIpc leak assertions → ``allocated_shared_memory_regions()``.
"""

from __future__ import annotations

import threading
import uuid as _uuid
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..._xla_broker import broker
from .. import np_to_triton_dtype, serialize_byte_tensor, triton_to_np_dtype
from .. import shared_memory as _sysshm

__all__ = [
    "XlaSharedMemoryException",
    "CudaSharedMemoryException",
    "XlaSharedMemoryRegion",
    "create_shared_memory_region",
    "get_raw_handle",
    "set_shared_memory_region",
    "set_shared_memory_region_from_dlpack",
    "get_contents_as_numpy",
    "as_shared_memory_tensor",
    "allocated_shared_memory_regions",
    "destroy_shared_memory_region",
]


class XlaSharedMemoryException(Exception):
    """Mirrors reference ``CudaSharedMemoryException`` (_utils.py:49-64)."""

    def __init__(self, msg):
        self._msg = str(msg)
        super().__init__(self._msg)

    def __str__(self):
        return self._msg


# drop-in alias for reference-written except clauses
CudaSharedMemoryException = XlaSharedMemoryException

_allocated: Dict[str, "XlaSharedMemoryRegion"] = {}
_alloc_lock = threading.Lock()


def _device(device_id: int):
    import jax

    devices = jax.devices()
    if device_id < 0 or device_id >= len(devices):
        raise XlaSharedMemoryException(
            f"unable to create shared memory region on device {device_id}: "
            f"only {len(devices)} XLA device(s) visible"
        )
    return devices[device_id]


class XlaSharedMemoryRegion:
    """Handle for one region (reference ``CudaSharedMemoryRegion``,
    _utils.py:67-100 — RAII free in ``__del__``)."""

    def __init__(self, triton_shm_name: str, byte_size: int, device_id: int):
        self._triton_shm_name = triton_shm_name
        self._byte_size = byte_size
        self._device_id = device_id
        self._uuid = _uuid.uuid4().hex
        # cleanup state FIRST: if any allocation below raises (/dev/shm
        # full), __del__ -> _close() must still release what was created
        self._closed = False
        self._staging = None
        self._seq = None
        # topology is fixed here (module docstring): a device slot only
        # when a server in THIS process can share it
        self._slot = (broker().create(self._uuid, byte_size, device_id)
                      if broker().server_present else None)
        # Host-shm staging region: the whole region for an out-of-process
        # client; created for a co-located one too (mmap is cheap, and the
        # raw handle always names it) but never written there.
        self._staging_key = f"/xlashm_{self._uuid[:16]}"
        self._staging = _sysshm.create_shared_memory_region(
            self._triton_shm_name, self._staging_key, byte_size
        )
        # 8-byte generation counter beside the staging bytes: every write
        # bumps it, so a cross-process server can CACHE its device import
        # and skip the host copy + DMA when the region hasn't changed
        # (the closest TPU analog of cudaIPC's map-once semantics)
        self._seq_key = self._staging_key + "_seq"
        try:
            self._seq = _sysshm.create_shared_memory_region(
                self._triton_shm_name + "_seq", self._seq_key, 8
            )
        except _sysshm.SharedMemoryException:
            self._close()
            raise

    # -- introspection ----------------------------------------------------
    @property
    def triton_shm_name(self) -> str:
        return self._triton_shm_name

    @property
    def byte_size(self) -> int:
        return self._byte_size

    @property
    def device_id(self) -> int:
        return self._device_id

    @property
    def array(self):
        """Current device contents (jax.Array); None when nothing is bound
        or the region is staging-only (out-of-process client)."""
        return self._slot.get()[0] if self._slot is not None else None

    # -- lifecycle ---------------------------------------------------------
    def _close(self):
        if self._closed:
            return
        self._closed = True
        broker().drop(self._uuid)
        for h in (self._staging, self._seq):
            if h is None:
                continue
            try:
                _sysshm.destroy_shared_memory_region(h)
            except _sysshm.SharedMemoryException:
                pass

    def __del__(self):
        try:
            self._close()
        except Exception:
            pass


def create_shared_memory_region(
    triton_shm_name: str, byte_size: int, device_id: int
) -> XlaSharedMemoryRegion:
    """Allocate a device-backed region (reference __init__.py:107-150:
    cudaSetDevice + cudaMalloc + cudaIpcGetMemHandle)."""
    if byte_size <= 0:
        raise XlaSharedMemoryException("byte_size must be positive")
    if device_id < 0:
        raise XlaSharedMemoryException(
            f"unable to create shared memory region on device {device_id}")
    if broker().server_present:
        # co-located: the device is this process's own — validate it.  An
        # out-of-process client must not open a backend to find out; the
        # id travels in the handle to the server that owns the chip.
        _device(device_id)
    region = XlaSharedMemoryRegion(triton_shm_name, byte_size, device_id)
    with _alloc_lock:
        _allocated[region._uuid] = region
    return region


def get_raw_handle(xla_shm_handle: XlaSharedMemoryRegion) -> bytes:
    """Serialized import descriptor (reference __init__.py:152-170 returns
    base64(cudaIpcMemHandle.reserved); the transport re-encodes, so the raw
    payload here is a JSON descriptor both registries understand)."""
    import json

    return json.dumps(
        {
            "uuid": xla_shm_handle._uuid,
            "staging_key": xla_shm_handle._staging_key,
            "seq_key": xla_shm_handle._seq_key,
            "byte_size": xla_shm_handle._byte_size,
            "device_id": xla_shm_handle._device_id,
        }
    ).encode("utf-8")


def _bind(handle: XlaSharedMemoryRegion, array, datatype: str, shape) -> None:
    handle._slot.bind(array, datatype, tuple(shape))


def _write_staging(handle: XlaSharedMemoryRegion, payloads, offset: int = 0):
    _sysshm.set_shared_memory_region(handle._staging, payloads, offset=offset)
    seq = _sysshm.get_contents_as_numpy(handle._seq, np.uint64, [1])
    _sysshm.set_shared_memory_region(
        handle._seq, [np.array([int(seq[0]) + 1], np.uint64)]
    )


def set_shared_memory_region(
    xla_shm_handle: XlaSharedMemoryRegion,
    input_values: Sequence[np.ndarray],
    offset: int = 0,
) -> None:
    """Write numpy arrays into the region (reference __init__.py:173-239:
    cudaMemcpyAsync per value + stream sync).

    Co-located: one H2D ``jax.device_put`` binds the device slot the
    server shares.  Out of process: the bytes go to the host staging
    region (generation counter bumped) and no backend is touched."""
    if not isinstance(input_values, (list, tuple)):
        raise XlaSharedMemoryException("input_values must be a list of numpy arrays")
    payloads = []
    for v in input_values:
        v = np.asarray(v)
        if v.dtype == np.object_ or v.dtype.kind in ("S", "U"):
            payloads.append(serialize_byte_tensor(v))
        else:
            payloads.append(np.ascontiguousarray(v))
    total = sum(p.nbytes for p in payloads)
    if offset + total > xla_shm_handle._byte_size:
        raise XlaSharedMemoryException(
            "unable to set shared memory region: byte_size "
            f"{xla_shm_handle._byte_size} is too small for {offset + total} bytes"
        )
    if xla_shm_handle._slot is None:
        _write_staging(xla_shm_handle, payloads, offset=offset)
    else:
        _write_device(xla_shm_handle, payloads, offset)
    from ..._telemetry import telemetry

    telemetry().record_shm_transfer("xla", "write", total)


def _write_device(handle: XlaSharedMemoryRegion, payloads, offset: int) -> None:
    """Co-located write: one H2D ``device_put`` rebinds the slot."""
    import jax

    dev = _device(handle._device_id)
    if len(payloads) == 1 and offset == 0:
        host = payloads[0]
        datatype = np_to_triton_dtype(host.dtype) or "UINT8"
        arr = jax.device_put(host, dev)
        _bind(handle, arr, datatype, host.shape)
    else:
        # multiple values / offset: region becomes a flat byte buffer
        flat = np.concatenate(
            [p.reshape(-1).view(np.uint8) for p in payloads]
        ) if payloads else np.zeros((0,), np.uint8)
        cur, _, _ = handle._slot.get()
        size = handle._byte_size
        buf = np.zeros((size,), np.uint8)
        if cur is not None:
            # Preserve whatever the region already holds (reference cudashm
            # offset writes leave the rest of the allocation intact) — the
            # current slot may be a typed array from a prior single-value
            # write, not just a full-size uint8 buffer.
            cur_bytes = np.ascontiguousarray(np.asarray(cur)).reshape(-1).view(np.uint8)
            buf[: min(cur_bytes.size, size)] = cur_bytes[: min(cur_bytes.size, size)]
        buf[offset : offset + flat.size] = flat
        arr = jax.device_put(buf, dev)
        _bind(handle, arr, "UINT8", (size,))


def set_shared_memory_region_from_dlpack(
    xla_shm_handle: XlaSharedMemoryRegion, input_values: Sequence
) -> None:
    """Zero-copy ingest of DLPack-capable tensors (reference
    __init__.py:328-388 — device-pointer based, the model for this module).

    Co-located: jax arrays bind directly (no copy); other producers (torch
    CPU, numpy) come in through ``jax.dlpack``/``device_put`` with one
    transfer.  Out of process: each tensor is copied to host staging."""
    if not isinstance(input_values, (list, tuple)):
        input_values = [input_values]
    if xla_shm_handle._slot is None:
        hosts = []
        for v in input_values:
            if not hasattr(v, "__dlpack__"):
                raise XlaSharedMemoryException(
                    f"tensor of type {type(v).__name__} does not support "
                    "DLPack")
            if not _contiguous_ok(v):
                raise XlaSharedMemoryException(
                    "the tensor must be contiguous in memory")
            # __array__ first: a device-resident producer (a jax.Array the
            # caller already holds) copies out through it, which
            # np.from_dlpack cannot do across devices
            hosts.append(np.ascontiguousarray(
                np.asarray(v) if hasattr(v, "__array__")
                else np.from_dlpack(v)))
        set_shared_memory_region(xla_shm_handle, hosts)
        return
    import jax

    dev = _device(xla_shm_handle._device_id)
    arrays = []
    total = 0
    for v in input_values:
        if isinstance(v, jax.Array):
            arr = v
        elif hasattr(v, "__dlpack__"):
            try:
                arr = jax.dlpack.from_dlpack(v)
            except Exception:
                arr = jax.device_put(np.from_dlpack(v), dev)
        else:
            raise XlaSharedMemoryException(
                f"tensor of type {type(v).__name__} does not support DLPack"
            )
        if not _contiguous_ok(v):
            raise XlaSharedMemoryException(
                "the tensor must be contiguous in memory"
            )
        arrays.append(arr)
        total += arr.size * arr.dtype.itemsize
    if total > xla_shm_handle._byte_size:
        raise XlaSharedMemoryException(
            "unable to set shared memory region: byte_size "
            f"{xla_shm_handle._byte_size} is too small for {total} bytes"
        )
    if len(arrays) == 1:
        arr = arrays[0]
        datatype = np_to_triton_dtype(np.dtype(str(arr.dtype))) or "UINT8"
        _bind(xla_shm_handle, arr, datatype, arr.shape)
    else:
        hosts = [np.ascontiguousarray(np.asarray(a)) for a in arrays]
        set_shared_memory_region(xla_shm_handle, hosts)


def _contiguous_ok(v) -> bool:
    if isinstance(v, np.ndarray):
        return v.flags["C_CONTIGUOUS"]
    if hasattr(v, "is_contiguous"):
        try:
            return bool(v.is_contiguous())
        except Exception:
            return True
    return True


def get_contents_as_numpy(
    xla_shm_handle: XlaSharedMemoryRegion,
    datatype,
    shape: Sequence[int],
    offset: int = 0,
) -> np.ndarray:
    """Device → host read-back (reference __init__.py:242-325: D2H
    cudaMemcpy then numpy reinterpret; BYTES deserialized host-side)."""
    arr = xla_shm_handle.array
    if arr is None:
        # staging-only region (the server is another process and wrote its
        # outputs here), or a slot nothing was bound to yet
        return _sysshm.get_contents_as_numpy(
            xla_shm_handle._staging, datatype, list(shape), offset=offset
        )
    host = np.asarray(arr)  # single D2H transfer
    flat = host.reshape(-1).view(np.uint8)
    if offset:
        flat = flat[offset:]
    dt = np.dtype(datatype)
    if dt == np.object_:
        from .. import deserialize_bytes_tensor

        out = deserialize_bytes_tensor(flat.tobytes())
        return out.reshape(tuple(shape))
    count = int(np.prod(shape)) if len(shape) else 1
    nbytes = count * dt.itemsize
    if nbytes > flat.size:
        raise XlaSharedMemoryException(
            f"unable to read {nbytes} bytes at offset {offset} from region "
            f"'{xla_shm_handle._triton_shm_name}'"
        )
    return flat[:nbytes].view(dt).reshape(tuple(shape))


def as_shared_memory_tensor(
    xla_shm_handle: XlaSharedMemoryRegion, datatype: str, shape: Sequence[int]
):
    """DLPack-view export (reference __init__.py:391-399).

    For a device-bound region the live ``jax.Array`` is itself the DLPack
    producer — frameworks consume TPU HBM with no host hop.  A
    staging-only region exports a numpy view of the host staging bytes
    (numpy arrays are DLPack producers too)."""
    dt = triton_to_np_dtype(datatype)
    if dt is None:
        raise XlaSharedMemoryException(f"unsupported datatype {datatype}")
    if xla_shm_handle._slot is None and dt is not np.object_:
        return _sysshm.get_contents_as_numpy(
            xla_shm_handle._staging, dt, list(shape))
    arr = xla_shm_handle.array
    if arr is None:
        raise XlaSharedMemoryException(
            f"shared memory region '{xla_shm_handle._triton_shm_name}' has no "
            "contents to export"
        )
    import jax.numpy as jnp

    host_dt = jnp.dtype(dt) if dt is not np.object_ else None
    if host_dt is not None and (
        arr.dtype != host_dt or tuple(arr.shape) != tuple(shape)
    ):
        flat = arr.reshape(-1)
        if arr.dtype != host_dt:
            import jax.lax as lax

            if arr.dtype == jnp.uint8:
                itemsize = np.dtype(dt).itemsize
                flat = flat[: int(np.prod(shape)) * itemsize]
                flat = (
                    lax.bitcast_convert_type(flat.reshape(-1, itemsize), host_dt)
                    if itemsize > 1
                    else lax.bitcast_convert_type(flat, host_dt)
                )
            else:
                raise XlaSharedMemoryException(
                    f"region holds {arr.dtype}, cannot view as {datatype}"
                )
        arr = flat.reshape(tuple(shape))
    return arr  # jax.Array implements __dlpack__ / __dlpack_device__


def allocated_shared_memory_regions() -> List[str]:
    """Names of live regions (reference __init__.py:402-411 — the leak
    assertion hook used by the cudashm examples)."""
    with _alloc_lock:
        return [r._triton_shm_name for r in _allocated.values()]


def destroy_shared_memory_region(xla_shm_handle: XlaSharedMemoryRegion) -> None:
    """Free the region (reference __init__.py:414-429; cudaFree happens in
    the handle's __del__ there — here the slot drop + staging unlink run
    eagerly)."""
    with _alloc_lock:
        _allocated.pop(xla_shm_handle._uuid, None)
    xla_shm_handle._close()
