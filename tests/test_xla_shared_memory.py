"""xla_shared_memory tests.

Mirrors the reference's test strategy for the device data path
(src/python/library/tests/test_cuda_shared_memory.py, SURVEY.md §4 tier 2):
DLPack round-trips with a framework as the interop oracle, numpy set/get,
serialized BYTES — plus the full cudashm-client end-to-end flow of
simple_grpc_cudashm_client.py (SURVEY.md §3.5) against the in-process
harness, where tensors stay device-resident (zero host copy on the infer
path), and the out-of-process contract: a client of a server that owns the
chip works through the staging region alone and never imports JAX.
"""

import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import triton_client_tpu.utils.xla_shared_memory as xlashm
from triton_client_tpu._xla_broker import broker
from triton_client_tpu.utils import serialize_byte_tensor


@pytest.fixture(autouse=True)
def _leak_check():
    yield
    assert xlashm.allocated_shared_memory_regions() == []


class TestDLPack:
    def test_jax_roundtrip(self):
        import jax
        import jax.numpy as jnp

        src = jnp.arange(16, dtype=jnp.float32).reshape(4, 4)
        h = xlashm.create_shared_memory_region("dlpack_jax", src.nbytes, 0)
        try:
            xlashm.set_shared_memory_region_from_dlpack(h, [src])
            t = xlashm.as_shared_memory_tensor(h, "FP32", [4, 4])
            back = jnp.from_dlpack(t)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(src))
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_numpy_to_torch(self):
        import torch

        src = np.arange(12, dtype=np.int32).reshape(3, 4)
        h = xlashm.create_shared_memory_region("dlpack_np", src.nbytes, 0)
        try:
            xlashm.set_shared_memory_region_from_dlpack(h, [src])
            got = xlashm.get_contents_as_numpy(h, np.int32, [3, 4])
            t = torch.from_numpy(np.ascontiguousarray(got))
            np.testing.assert_array_equal(t.numpy(), src)
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_noncontiguous_rejected(self):
        src = np.arange(16, dtype=np.float32).reshape(4, 4).T
        h = xlashm.create_shared_memory_region("dlpack_nc", 64, 0)
        try:
            with pytest.raises(xlashm.XlaSharedMemoryException):
                xlashm.set_shared_memory_region_from_dlpack(h, [src])
        finally:
            xlashm.destroy_shared_memory_region(h)


class TestNumpy:
    def test_set_get(self):
        src = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
        h = xlashm.create_shared_memory_region("np_region", src.nbytes, 0)
        try:
            xlashm.set_shared_memory_region(h, [src])
            got = xlashm.get_contents_as_numpy(h, np.float32, [2, 8])
            np.testing.assert_array_equal(got, src)
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_too_small_raises(self):
        src = np.zeros((100,), np.float64)
        h = xlashm.create_shared_memory_region("small", 8, 0)
        try:
            with pytest.raises(xlashm.XlaSharedMemoryException):
                xlashm.set_shared_memory_region(h, [src])
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_bytes_tensor(self):
        strings = np.array([b"hello", b"", b"tpu-shm"], dtype=np.object_)
        payload = serialize_byte_tensor(strings)
        h = xlashm.create_shared_memory_region("bytes_r", payload.nbytes, 0)
        try:
            xlashm.set_shared_memory_region(h, [strings])
            got = xlashm.get_contents_as_numpy(h, np.object_, [3])
            assert list(got) == [b"hello", b"", b"tpu-shm"]
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_invalid_device(self):
        with pytest.raises(xlashm.XlaSharedMemoryException):
            xlashm.create_shared_memory_region("bad_dev", 64, -1)

    def test_staging_only_region_binds_no_device_slot(self):
        # no server in this process: the region IS its staging region —
        # nothing device-side exists, the broker never hears of it
        src = np.arange(4, dtype=np.int32)
        assert not broker().server_present
        h = xlashm.create_shared_memory_region("no_slot", src.nbytes, 0)
        try:
            xlashm.set_shared_memory_region(h, [src])
            assert h.array is None
            assert broker().lookup(h._uuid) is None
            np.testing.assert_array_equal(
                xlashm.get_contents_as_numpy(h, np.int32, [4]), src)
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_offset_write_preserves_prior_contents(self):
        # Regression: an offset write after a typed single-value write must
        # not wipe the earlier bytes (reference cudashm leaves the rest of
        # the allocation intact on offset writes).
        first = np.arange(8, dtype=np.int32)          # 32 bytes at offset 0
        second = np.arange(100, 104, dtype=np.int32)  # 16 bytes at offset 32
        h = xlashm.create_shared_memory_region("off_region", 64, 0)
        try:
            xlashm.set_shared_memory_region(h, [first])
            xlashm.set_shared_memory_region(h, [second], offset=first.nbytes)
            got_first = xlashm.get_contents_as_numpy(h, np.int32, [8])
            got_second = xlashm.get_contents_as_numpy(
                h, np.int32, [4], offset=first.nbytes)
            np.testing.assert_array_equal(got_first, first)
            np.testing.assert_array_equal(got_second, second)
        finally:
            xlashm.destroy_shared_memory_region(h)


class TestStagingImport:
    """Cross-process import path: a region created with no server in the
    process has no broker slot, so the server-side registry imports it
    through the host staging region."""

    def test_registry_staging_read(self):
        from triton_client_tpu.server.shm import XlaShmRegistry
        from triton_client_tpu.server.types import ShmRef

        src = np.arange(8, dtype=np.float32)
        h = xlashm.create_shared_memory_region("staging_r", src.nbytes, 0)
        try:
            assert not broker().server_present
            xlashm.set_shared_memory_region(h, [src])  # writes staging
            raw = xlashm.get_raw_handle(h)
            reg = XlaShmRegistry()
            reg.register("staging_r", raw, 0, src.nbytes)
            arr = reg.read(ShmRef("staging_r", src.nbytes, 0), "FP32", (8,))
            np.testing.assert_array_equal(np.asarray(arr), src)
            reg.unregister("staging_r")
        finally:
            xlashm.destroy_shared_memory_region(h)

    def test_unchanged_region_served_from_import_cache(self):
        """Generation-stamped cache: a second read of an unchanged region
        must not re-import (no host copy, no DMA); a client rewrite bumps
        the generation and forces exactly one re-import."""
        from triton_client_tpu.server.shm import XlaShmRegistry
        from triton_client_tpu.server.types import ShmRef

        src = np.arange(8, dtype=np.float32)
        h = xlashm.create_shared_memory_region("cache_r", src.nbytes, 0)
        try:
            xlashm.set_shared_memory_region(h, [src])
            raw = xlashm.get_raw_handle(h)
            reg = XlaShmRegistry()
            reg.register("cache_r", raw, 0, src.nbytes)
            ref = ShmRef("cache_r", src.nbytes, 0)
            a1 = reg.read(ref, "FP32", (8,))
            assert reg.stats["staging_imports"] == 1
            a2 = reg.read(ref, "FP32", (8,))
            assert reg.stats["cache_hits"] == 1
            assert a2 is a1  # the very same device array
            # rewrite -> generation bump -> one re-import with new contents
            src2 = src + 100
            xlashm.set_shared_memory_region(h, [src2])
            a3 = reg.read(ref, "FP32", (8,))
            assert reg.stats["staging_imports"] == 2
            np.testing.assert_array_equal(np.asarray(a3), src2)
            # different shape/dtype view of same generation: re-imports
            reg.read(ref, "FP32", (2, 4))
            assert reg.stats["staging_imports"] == 3
            reg.unregister("cache_r")
        finally:
            xlashm.destroy_shared_memory_region(h)


class TestEndToEnd:
    """simple_grpc_cudashm_client.py flow (SURVEY.md §3.5) over the live
    harness: register → shm inputs → infer → shm outputs → unregister."""

    @pytest.fixture()
    def harness(self):
        from triton_client_tpu.models import zoo
        from triton_client_tpu.server.registry import ModelRegistry
        from triton_client_tpu.server.testing import ServerHarness

        registry = ModelRegistry()
        zoo.register_all(registry)
        h = ServerHarness(registry)
        h.start()
        yield h
        h.stop()

    @pytest.mark.parametrize("proto", ["grpc", "http"])
    def test_cudashm_flow(self, harness, proto):
        if proto == "grpc":
            from triton_client_tpu.grpc import (
                InferenceServerClient, InferInput, InferRequestedOutput)

            client = InferenceServerClient(f"127.0.0.1:{harness.grpc_port}")
        else:
            from triton_client_tpu.http import (
                InferenceServerClient, InferInput, InferRequestedOutput)

            client = InferenceServerClient(f"127.0.0.1:{harness.http_port}")

        a = np.arange(16, dtype=np.int32).reshape(1, 16)
        b = np.full((1, 16), 3, dtype=np.int32)
        nbytes = a.nbytes

        handles = {}
        try:
            client.unregister_cuda_shared_memory()
            for name in ("input0_data", "input1_data", "output0_data", "output1_data"):
                handles[name] = xlashm.create_shared_memory_region(name, nbytes, 0)
                client.register_cuda_shared_memory(
                    name, xlashm.get_raw_handle(handles[name]), 0, nbytes)
            xlashm.set_shared_memory_region(handles["input0_data"], [a])
            xlashm.set_shared_memory_region(handles["input1_data"], [b])

            i0 = InferInput("INPUT0", [1, 16], "INT32")
            i0.set_shared_memory("input0_data", nbytes)
            i1 = InferInput("INPUT1", [1, 16], "INT32")
            i1.set_shared_memory("input1_data", nbytes)
            o0 = InferRequestedOutput("OUTPUT0")
            o0.set_shared_memory("output0_data", nbytes)
            o1 = InferRequestedOutput("OUTPUT1")
            o1.set_shared_memory("output1_data", nbytes)

            result = client.infer("simple", [i0, i1], outputs=[o0, o1])
            assert result.get_output("OUTPUT0") is not None

            sum_out = xlashm.get_contents_as_numpy(
                handles["output0_data"], np.int32, [1, 16])
            diff_out = xlashm.get_contents_as_numpy(
                handles["output1_data"], np.int32, [1, 16])
            np.testing.assert_array_equal(sum_out, a + b)
            np.testing.assert_array_equal(diff_out, a - b)

            status = client.get_cuda_shared_memory_status()
            names = _status_names(status)
            assert "input0_data" in names

            client.unregister_cuda_shared_memory()
            status = client.get_cuda_shared_memory_status()
            assert not _status_names(status)
        finally:
            for h in handles.values():
                xlashm.destroy_shared_memory_region(h)
            client.close()

    def test_zero_copy_in_process(self, harness):
        """Co-located topology: a jax.Array input stays device-resident —
        the server consumes the exact buffer the client bound."""
        import jax.numpy as jnp

        from triton_client_tpu.grpc import (
            InferenceServerClient, InferInput, InferRequestedOutput)

        client = InferenceServerClient(f"127.0.0.1:{harness.grpc_port}")
        src = jnp.arange(16, dtype=jnp.int32).reshape(1, 16)
        ones = jnp.ones((1, 16), jnp.int32)
        nbytes = 16 * 4
        h0 = xlashm.create_shared_memory_region("zc_in0", nbytes, 0)
        h1 = xlashm.create_shared_memory_region("zc_in1", nbytes, 0)
        try:
            assert broker().server_present  # harness marks co-located mode
            with pytest.raises(xlashm.XlaSharedMemoryException):
                # co-located, the device is this process's own: validated
                xlashm.create_shared_memory_region("bad_dev", 64, 99)
            xlashm.set_shared_memory_region_from_dlpack(h0, [src])
            xlashm.set_shared_memory_region_from_dlpack(h1, [ones])
            # same PjRt buffer, not a copy
            assert h0.array is src
            client.register_cuda_shared_memory(
                "zc_in0", xlashm.get_raw_handle(h0), 0, nbytes)
            client.register_cuda_shared_memory(
                "zc_in1", xlashm.get_raw_handle(h1), 0, nbytes)
            i0 = InferInput("INPUT0", [1, 16], "INT32")
            i0.set_shared_memory("zc_in0", nbytes)
            i1 = InferInput("INPUT1", [1, 16], "INT32")
            i1.set_shared_memory("zc_in1", nbytes)
            result = client.infer("simple", [i0, i1])
            np.testing.assert_array_equal(
                result.as_numpy("OUTPUT0"), np.asarray(src) + 1)
            client.unregister_cuda_shared_memory()
        finally:
            xlashm.destroy_shared_memory_region(h0)
            xlashm.destroy_shared_memory_region(h1)
            client.close()


_OUT_OF_PROCESS_CLIENT = r"""
import sys
import numpy as np
import triton_client_tpu.grpc as grpcclient
import triton_client_tpu.utils.cuda_shared_memory as cudashm
from triton_client_tpu.perf_analyzer import (
    _build_inputs, _make_data, _resolve_model, run_level)

grpc_url = sys.argv[1]
# examples/simple_grpc_cudashm_client.py, as its own process
client = grpcclient.InferenceServerClient(grpc_url)
client.unregister_cuda_shared_memory()
a = np.arange(16, dtype=np.int32).reshape(1, 16)
b = np.ones((1, 16), dtype=np.int32)
handles = {}
for name in ("input0_data", "input1_data", "output0_data", "output1_data"):
    handles[name] = cudashm.create_shared_memory_region(name, a.nbytes, 0)
    client.register_cuda_shared_memory(
        name, cudashm.get_raw_handle(handles[name]), 0, a.nbytes)
cudashm.set_shared_memory_region(handles["input0_data"], [a])
cudashm.set_shared_memory_region(handles["input1_data"], [b])
inputs = [grpcclient.InferInput("INPUT0", [1, 16], "INT32"),
          grpcclient.InferInput("INPUT1", [1, 16], "INT32")]
inputs[0].set_shared_memory("input0_data", a.nbytes)
inputs[1].set_shared_memory("input1_data", a.nbytes)
outputs = [grpcclient.InferRequestedOutput("OUTPUT0"),
           grpcclient.InferRequestedOutput("OUTPUT1")]
outputs[0].set_shared_memory("output0_data", a.nbytes)
outputs[1].set_shared_memory("output1_data", a.nbytes)
client.infer("simple", inputs, outputs=outputs)
assert np.array_equal(cudashm.get_contents_as_numpy(
    handles["output0_data"], np.int32, [1, 16]), a + b)
assert np.array_equal(cudashm.get_contents_as_numpy(
    handles["output1_data"], np.int32, [1, 16]), a - b)
client.unregister_cuda_shared_memory()
for h in handles.values():
    cudashm.destroy_shared_memory_region(h)
assert not cudashm.allocated_shared_memory_regions()
# tpu-perf-analyzer -m dense_tpu --shared-memory=xla, the README quickstart
pa_inputs, pa_outputs, max_batch = _resolve_model(client, "grpc", "dense_tpu", "")
arrays = _make_data(pa_inputs, {}, 1, max_batch, np.random.default_rng(0))
# the shape's first request compiles in the server: over the wire, before
# the level, whose 1.5 s a busy machine can spend compiling (throughput 0)
client.infer("dense_tpu", _build_inputs(grpcclient, arrays, "none"))
client.close()
res = run_level("grpc", grpc_url, "dense_tpu", "", 2, arrays, pa_outputs,
                "xla", 1 << 16, 1.0, warmup_s=0.5)
assert res["errors"] == 0 and res["throughput"] > 0, res
assert "jax" not in sys.modules, "the xla-shm client imported jax"
print("OUT-OF-PROCESS-OK")
"""


class TestOutOfProcessClient:
    """The README quickstart topology: a CLI server owns the device, the
    xla-shm client is another process.  The client must complete the
    cudashm example flow and a perf_analyzer xla-shm level without ever
    importing JAX — on a chip, a second process opening the backend fails
    (the libtpu lock), so "never imports" is the contract that keeps the
    quickstart working."""

    def test_subprocess_client_never_imports_jax(self):
        from triton_client_tpu.server.testing import free_port

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        http_port, grpc_port = free_port(), free_port()
        server = subprocess.Popen(
            [sys.executable, "-m", "triton_client_tpu.server", "--zoo",
             "--host", "127.0.0.1", "--http-port", str(http_port),
             "--grpc-port", str(grpc_port), "--metrics-port", "0"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            deadline = time.monotonic() + 120
            while True:
                assert server.poll() is None, server.stdout.read()[-2000:]
                assert time.monotonic() < deadline, "server never ready"
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{http_port}/v2/health/ready",
                            timeout=2) as r:
                        if r.status == 200:
                            break
                except OSError:
                    time.sleep(0.2)
            client = subprocess.run(
                [sys.executable, "-c", _OUT_OF_PROCESS_CLIENT,
                 f"127.0.0.1:{grpc_port}"],
                env=env, capture_output=True, text=True, timeout=120)
            assert client.returncode == 0, client.stderr[-3000:]
            assert "OUT-OF-PROCESS-OK" in client.stdout
        finally:
            server.send_signal(signal.SIGTERM)
            try:
                server.wait(timeout=30)
            except subprocess.TimeoutExpired:
                server.kill()
                server.wait(timeout=10)


def _status_names(status):
    if isinstance(status, dict):  # http json
        return {r["name"] for r in status.get("regions", [])} if "regions" in status \
            else {r.get("name") for r in status.values()} if status else set()
    if isinstance(status, list):
        return {r["name"] for r in status}
    # grpc pb CudaSharedMemoryStatusResponse
    try:
        return set(status.regions.keys())
    except AttributeError:
        return {r.name for r in status.regions}
