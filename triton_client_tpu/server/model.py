"""Model abstraction for the serving harness.

The reference's server is out of repo (SURVEY.md "critical absences"); this
harness exists so the framework is testable hermetically (SURVEY.md §7.2) and
so TPU serving has a first-class home.  Design is TPU-first rather than a
Triton-backend port:

* A model's compute is a **pure function** over arrays; ``JaxModel`` wraps it
  in ``jax.jit`` once and relies on XLA caching per input-shape signature.
* Batching pads to configured bucket sizes so XLA re-traces a bounded set of
  shapes (static shapes — no dynamic-shape recompiles in steady state).
* Outputs may be returned as live ``jax.Array``s; they stay on device until a
  frontend (or an xla-shm region write) actually needs host bytes.
"""

from __future__ import annotations

import abc
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..protocol import inference_pb2 as pb
from .types import InferError

# Triton dtype string <-> pb.DataType enum.
_DT_TO_PB = {
    "BOOL": pb.TYPE_BOOL,
    "UINT8": pb.TYPE_UINT8,
    "UINT16": pb.TYPE_UINT16,
    "UINT32": pb.TYPE_UINT32,
    "UINT64": pb.TYPE_UINT64,
    "INT8": pb.TYPE_INT8,
    "INT16": pb.TYPE_INT16,
    "INT32": pb.TYPE_INT32,
    "INT64": pb.TYPE_INT64,
    "FP16": pb.TYPE_FP16,
    "FP32": pb.TYPE_FP32,
    "FP64": pb.TYPE_FP64,
    "BYTES": pb.TYPE_STRING,
    "BF16": pb.TYPE_BF16,
}
_PB_TO_DT = {v: k for k, v in _DT_TO_PB.items()}


def datatype_to_pb(dt: str) -> int:
    return _DT_TO_PB[dt]


def pb_to_datatype(v: int) -> str:
    return _PB_TO_DT[v]


def make_config(
    name: str,
    inputs: Sequence[Tuple[str, str, Sequence[int]]],
    outputs: Sequence[Tuple[str, str, Sequence[int]]],
    max_batch_size: int = 0,
    platform: str = "jax",
    backend: str = "jax",
    decoupled: bool = False,
    preferred_batch_sizes: Optional[Sequence[int]] = None,
    max_queue_delay_us: int = 0,
    sequence_batching: bool = False,
    labels: Optional[Dict[str, List[str]]] = None,
    instance_kind: Optional[str] = None,
    parameters: Optional[Dict[str, str]] = None,
    warmup: Optional[Sequence[dict]] = None,
    response_cache: bool = False,
) -> pb.ModelConfig:
    """Convenience builder for a ModelConfig proto.

    ``inputs``/``outputs``: (tensor name, Triton dtype string, dims) — dims
    exclude the batch dimension when ``max_batch_size > 0``, matching Triton
    config semantics."""
    cfg = pb.ModelConfig(
        name=name, platform=platform, backend=backend, max_batch_size=max_batch_size
    )
    for n, dt, dims in inputs:
        cfg.input.add(name=n, data_type=_DT_TO_PB[dt], dims=list(dims))
    for n, dt, dims in outputs:
        out = cfg.output.add(name=n, data_type=_DT_TO_PB[dt], dims=list(dims))
        if labels and n in labels:
            out.label_filename = f"{n}_labels.txt"
    if decoupled:
        cfg.model_transaction_policy.decoupled = True
    if preferred_batch_sizes or max_queue_delay_us:
        cfg.dynamic_batching.preferred_batch_size.extend(preferred_batch_sizes or [])
        cfg.dynamic_batching.max_queue_delay_microseconds = max_queue_delay_us
    if sequence_batching:
        cfg.sequence_batching.max_sequence_idle_microseconds = 60_000_000
    if instance_kind:
        grp = cfg.instance_group.add()
        grp.name = name
        grp.kind = pb.ModelInstanceGroup.Kind.Value(instance_kind)
        grp.count = 1
    for key, value in (parameters or {}).items():
        cfg.parameters[key].string_value = str(value)
    if response_cache:
        cfg.response_cache.enable = True
    # warmup: [{"name": ..., "batch_size": N, "count": N,
    #           "inputs": {tensor: (dtype str, dims, "zero"|"random")}}]
    for w in warmup or []:
        sample = cfg.model_warmup.add(
            name=w.get("name", "sample"),
            batch_size=w.get("batch_size", 0),
            count=w.get("count", 1))
        for tensor, (dt, dims, mode) in w["inputs"].items():
            spec = sample.inputs[tensor]
            spec.data_type = _DT_TO_PB[dt]
            spec.dims.extend(dims)
            if mode == "random":
                spec.random_data = True
            else:
                spec.zero_data = True
    return cfg


def resolve_instance_device(config: pb.ModelConfig):
    """Device placement from ``instance_group`` (Triton instance_group
    semantics: KIND_CPU pins host; KIND_AUTO/KIND_MODEL/KIND_TPU prefer the
    accelerator).  Small protocol-fixture models run KIND_CPU so the serving
    path isn't bottlenecked by per-request host<->device transfers."""
    import jax

    kind = None
    for grp in config.instance_group:
        kind = pb.ModelInstanceGroup.Kind.Name(grp.kind)
        break
    if kind == "KIND_CPU":
        return jax.devices("cpu")[0]
    return jax.devices()[0]


@dataclass
class ModelStats:
    """Per-model counters backing the statistics API (v2 `ModelStatistics`;
    client surface at reference http/_client.py:709-765)."""

    inference_count: int = 0
    execution_count: int = 0
    last_inference_ms: int = 0
    success_count: int = 0
    success_ns: int = 0
    fail_count: int = 0
    fail_ns: int = 0
    queue_count: int = 0
    queue_ns: int = 0
    infer_count: int = 0
    infer_ns: int = 0
    # gauge: requests currently inside the core's infer path
    pending_count: int = 0
    # dynamic batcher: cumulative (unpadded) batch size and executions, so
    # avg formed batch = batch_size_total / batch_execution_count
    batch_size_total: int = 0
    batch_execution_count: int = 0
    # layer-boundary counters (the statistics extension, docs/ARCHITECTURE.md):
    # cumulative (count, ns) pairs charged per row like ``infer_ns``, so a
    # request's means add up along its path.  Recorded where the work
    # happens, whether or not the request carries a TraceContext.
    # The five per-execution entries count what ``infer_count`` counts.
    request_count: int = 0      # frontend handler entry -> response built
    request_ns: int = 0
    queue_member_ns: int = 0    # each member's OWN enqueue -> assembly
    assembly_ns: int = 0        # concat + pad-to-bucket
    executor_wait_ns: int = 0   # run_in_executor called -> _exec starts
    dispatch_ns: int = 0        # model.execute called -> returned
    device_wait_ns: int = 0     # execute returned -> outputs on the host
    bucket_rows: int = 0        # rows executed, pad rows included
    batch_carry_count: int = 0  # executions the batcher closed at a bucket,
    batch_carry_rows: int = 0   # and the rows it left for the next batch
    batch_hold_count: int = 0   # executions whose close waited past the
    batch_hold_ns: int = 0      # window's end for the batch ahead; how long
    batch_early_count: int = 0  # executions an idle chip took before their
    batch_early_ns: int = 0     # window's end; the window time not waited
    # a formed step that found the chip out of this model's work: steps
    # with a part of the dry interval under each cause, and its ns
    # (``types.dry_split``; a step, not a row; a lower bound, blind across
    # a process-wide pause)
    dry_no_request_count: int = 0   # no request of the model was in the
    dry_no_request_ns: int = 0      # batcher
    dry_window_count: int = 0       # a request waited, the batcher's window
    dry_window_ns: int = 0          # was open
    dry_late_count: int = 0         # the window was over, the batch not
    dry_late_ns: int = 0            # closed (late pump, pause, hold)
    dry_host_count: int = 0         # assembly and the hop to the executor
    dry_host_ns: int = 0
    pause_count: int = 0        # collector / late-loop pauses that held
    pause_ns: int = 0           # requests of this model
    # counted on the device and read back with the answer; 0 for a model
    # whose step carries none.  An expert layer's routing
    # (models/latent_moe.py):
    expert_rows: int = 0          # (token, expert) pairs on held experts
    expert_tokens: int = 0        # tokens x expert layers run, no pad rows
    expert_rows_busiest: int = 0  # per layer and execution, the fullest
    #                               held expert's rows, summed
    # generation by diffusion over blocks (models/block_diffusion.py):
    denoise_passes: int = 0       # passes x sequences (none is run for
    #                               the cache alone: a block's keys ride
    #                               the next block's first pass)
    denoise_tokens: int = 0       # tokens committed
    experts_touched: int = 0      # per pass and layer, experts with a row
    # a stack run several times over one set of weights (models/looped.py):
    loop_steps: int = 0           # loop steps tokens took before they left
    loop_tokens: int = 0          # tokens through the stack
    # greedy generation through two kinds of state (models/hybrid_conv.py):
    decode_steps: int = 0         # decode steps x the sequences they ran
    decode_tokens: int = 0        # tokens those steps yielded
    # learned sparse attention (models/sparse_latent.py):
    dsa_queries: int = 0          # query rows x attention blocks
    dsa_pairs: int = 0            # (query, key) pairs those rows attended
    index_reused: int = 0         # query rows x blocks that reused a choice
    # sliding-window attention (models/window_moe.py):
    window_queries: int = 0       # query rows x windowed layers
    window_pairs: int = 0         # (query, key) pairs those layers attended
    lock: threading.Lock = field(default_factory=threading.Lock)
    # steps whose counters are still on the device: ({name: array with a
    # leading axis of batch rows}, real rows, tokens a row)
    _device_pending: Deque = field(default_factory=deque)

    def inc_pending(self) -> None:
        with self.lock:
            self.pending_count += 1

    def dec_pending(self) -> None:
        with self.lock:
            self.pending_count -= 1

    def record(self, step) -> None:
        """One executed step (``types.StepRecord``; called by
        ``InferenceCore._book`` alone).  The v2 entries charge the step's
        ``queue_ns`` and ``compute_ns`` to every row; the extension entries
        are the record's other durations, a row each."""
        rows = step.rows
        with self.lock:
            if not step.ok:
                self.fail_count += rows
                self.fail_ns += step.fail_ns * rows
                return
            self._served(rows, step.queue_ns, step.compute_ns,
                         step.member_queue_ns, step.bucket)
            self.assembly_ns += step.assembly_ns * rows
            self.executor_wait_ns += step.executor_wait_ns * rows
            self.dispatch_ns += step.window_ns * rows
            self.device_wait_ns += step.device_wait_ns * rows
            if step.formed:
                self.batch_size_total += rows
                self.batch_execution_count += 1
            if step.carried:
                self.batch_carry_count += 1
                self.batch_carry_rows += step.carried
            if step.held_ns:
                self.batch_hold_count += 1
                self.batch_hold_ns += step.held_ns
            if step.early_ns:
                self.batch_early_count += 1
                self.batch_early_ns += step.early_ns
            if step.t_dry:
                # the chip had run out of this model's work before the step
                # reached it: the interval's four causes, a step (it is
                # device time, not a row's wait)
                no_request, window, late, host = step.dry_ns
                self.dry_no_request_count += no_request > 0
                self.dry_no_request_ns += no_request
                self.dry_window_count += window > 0
                self.dry_window_ns += window
                self.dry_late_count += late > 0
                self.dry_late_ns += late
                self.dry_host_count += host > 0
                self.dry_host_ns += host

    def record_answered(self, rows: int, queue_ns: int, compute_ns: int,
                        ok: bool) -> None:
        """Rows answered with no step of their own to book: a cache hit, an
        ensemble's envelope (its members book their steps), a decoupled
        stream (the decode worker books its ticks)."""
        with self.lock:
            if ok:
                self._served(rows, queue_ns, compute_ns, queue_ns * rows,
                             rows)
            else:
                self.fail_count += rows
                self.fail_ns += (queue_ns + compute_ns) * rows

    def _served(self, rows: int, queue_ns: int, compute_ns: int,
                member_queue_ns: int, bucket: int) -> None:
        # caller holds ``lock``
        self.inference_count += rows
        self.execution_count += 1
        self.last_inference_ms = int(time.time() * 1000)
        self.success_count += rows
        self.success_ns += (queue_ns + compute_ns) * rows
        self.queue_count += rows
        self.queue_ns += queue_ns * rows
        self.infer_count += rows
        self.infer_ns += compute_ns * rows
        self.queue_member_ns += member_queue_ns
        self.bucket_rows += bucket

    def record_request(self, rows: int, ns: int) -> None:
        """A frontend answered a request of ``rows`` rows ``ns`` after its
        handler was entered (successes only, like ``success``)."""
        with self.lock:
            self.request_count += rows
            self.request_ns += ns * rows

    def charge_pause(self, ns: int, blocking: bool = True) -> bool:
        """Charge one process-wide pause of ``ns``.  ``blocking=False`` is
        for the collector's hook, which may run on a thread that already
        holds ``lock``: False means not charged, try again later."""
        if not self.lock.acquire(blocking):
            return False
        try:
            self.pause_count += 1
            self.pause_ns += ns
        finally:
            self.lock.release()
        return True

    def queue_device_counters(self, counters: Dict[str, Any], real_rows: int,
                              tokens_per_row: int = 0) -> None:
        """A model's ``host_post`` hands over what its step counted on the
        device, by name (``_fold_device_counter`` says what each array
        holds; all have a leading axis of batch rows and are still on the
        device), with the rows the batcher did not pad on: start their copy
        to the host and fold the earlier steps that have finished."""
        for array in counters.values():
            if hasattr(array, "copy_to_host_async"):
                array.copy_to_host_async()
        self._device_pending.append((counters, real_rows, tokens_per_row))
        self.settle_device_counters()

    def settle_device_counters(self) -> None:
        """Fold the queued steps' counters into their entries, oldest
        first, up to the first whose step is still running on the device:
        like ``inference_count``, the entries count finished steps, and
        reading them never waits for the device."""
        with self.lock:
            while self._device_pending:
                counters, real_rows, tokens_per_row = self._device_pending[0]
                for array in counters.values():
                    ready = getattr(array, "is_ready", None)
                    if ready is not None and not ready():
                        return
                self._device_pending.popleft()
                for name, array in counters.items():
                    self._fold_device_counter(
                        name, np.asarray(array)[:real_rows], tokens_per_row)

    def _fold_device_counter(self, name: str, counts, tokens_per_row: int):
        # caller holds ``lock``; ``counts`` holds the real rows alone
        if name == "expert_rows":
            # pairs routed to each held expert [rows, expert layers, experts]
            self.expert_rows += int(counts.sum())
            self.expert_tokens += \
                counts.shape[0] * tokens_per_row * counts.shape[1]
            self.expert_rows_busiest += int(
                counts.sum(axis=0).max(axis=-1).sum())
        elif name in ("denoise_passes", "denoise_tokens", "loop_steps",
                      "loop_tokens", "decode_steps", "decode_tokens",
                      "dsa_queries", "dsa_pairs", "index_reused",
                      "window_queries", "window_pairs"):
            # a count a row [rows]
            setattr(self, name, getattr(self, name) + int(counts.sum()))
        elif name == "experts_touched":
            # [rows], entry r counted over the rows 0 .. r: the last real
            # row's entry is the batch's without its padding
            self.experts_touched += int(counts[-1]) if len(counts) else 0
        else:
            raise KeyError(f"no device counter named {name!r}")

    def extension_entries(self) -> Dict[str, Dict[str, int]]:
        """The extension's ``inference_stats`` entries (caller holds
        ``lock``), in path order."""
        n = self.infer_count
        return {
            "request": {"count": self.request_count, "ns": self.request_ns},
            "queue_member": {"count": n, "ns": self.queue_member_ns},
            "batch_assembly": {"count": n, "ns": self.assembly_ns},
            "executor_wait": {"count": n, "ns": self.executor_wait_ns},
            "dispatch": {"count": n, "ns": self.dispatch_ns},
            "device_wait": {"count": n, "ns": self.device_wait_ns},
            "bucket_rows": {"count": self.bucket_rows, "ns": 0},
            "batch_carry": {"count": self.batch_carry_count, "ns": 0},
            "batch_carry_rows": {"count": self.batch_carry_rows, "ns": 0},
            "batch_hold": {"count": self.batch_hold_count,
                           "ns": self.batch_hold_ns},
            "batch_early": {"count": self.batch_early_count,
                            "ns": self.batch_early_ns},
            "dry_no_request": {"count": self.dry_no_request_count,
                               "ns": self.dry_no_request_ns},
            "dry_window": {"count": self.dry_window_count,
                           "ns": self.dry_window_ns},
            "dry_late": {"count": self.dry_late_count,
                         "ns": self.dry_late_ns},
            "dry_host": {"count": self.dry_host_count,
                         "ns": self.dry_host_ns},
            "pause": {"count": self.pause_count, "ns": self.pause_ns},
            "expert_rows": {"count": self.expert_rows, "ns": 0},
            "expert_tokens": {"count": self.expert_tokens, "ns": 0},
            "expert_rows_busiest": {"count": self.expert_rows_busiest,
                                    "ns": 0},
            "denoise_passes": {"count": self.denoise_passes, "ns": 0},
            "denoise_tokens": {"count": self.denoise_tokens, "ns": 0},
            "experts_touched": {"count": self.experts_touched, "ns": 0},
            "loop_steps": {"count": self.loop_steps, "ns": 0},
            "loop_tokens": {"count": self.loop_tokens, "ns": 0},
            "decode_steps": {"count": self.decode_steps, "ns": 0},
            "decode_tokens": {"count": self.decode_tokens, "ns": 0},
            "dsa_queries": {"count": self.dsa_queries, "ns": 0},
            "dsa_pairs": {"count": self.dsa_pairs, "ns": 0},
            "index_reused": {"count": self.index_reused, "ns": 0},
            "window_queries": {"count": self.window_queries, "ns": 0},
            "window_pairs": {"count": self.window_pairs, "ns": 0},
        }


class Model(abc.ABC):
    """Base model: subclasses implement ``execute`` (request-scoped).

    ``execute`` receives a dict of input arrays (numpy for host models;
    ``jax.Array`` for device-resident xla-shm inputs) plus request parameters
    (including sequence controls) and returns a dict of output arrays.

    Decoupled models (``transaction policy decoupled: true`` — reference
    repeat/square examples, SURVEY.md §2.7) instead yield zero or more
    response dicts from ``execute_decoupled``.
    """

    def __init__(self, config: pb.ModelConfig):
        self.config = config
        self.stats = ModelStats()

    # -- identity ----------------------------------------------------------
    @property
    def name(self) -> str:
        return self.config.name

    #: version number this instance serves (the registry stamps it when a
    #: repository model declares numbered version directories)
    served_version: str = "1"

    #: steps ``InferenceCore._run_model`` has numbered (``StepRecord.seq``)
    step_seq: int = 0

    #: True for a model that runs its own device loop (the decode worker):
    #: it books its own ticks and costs, a request's tenant rides its
    #: parameters, and ``InferenceCore._wire`` finds its ``attach_*`` sockets
    device_loop: bool = False

    @property
    def versions(self) -> List[str]:
        """Every version served under this model's name (the registry
        stamps the list on each loaded instance; programmatic models serve
        a single '1')."""
        return list(getattr(self, "_version_list", ("1",)))

    @property
    def decoupled(self) -> bool:
        return self.config.model_transaction_policy.decoupled

    @property
    def is_sequence(self) -> bool:
        return self.config.HasField("sequence_batching")

    @property
    def max_batch_size(self) -> int:
        return self.config.max_batch_size

    def metadata(self) -> dict:
        """v2 model-metadata JSON (client surface: http/_client.py:470-515)."""
        def tensor_md(io, batched):
            dims = list(io.dims)
            if batched:
                dims = [-1] + dims
            return {"name": io.name, "datatype": pb_to_datatype(io.data_type), "shape": dims}

        batched = self.config.max_batch_size > 0
        return {
            "name": self.name,
            "versions": self.versions,
            "platform": self.config.platform,
            "inputs": [tensor_md(i, batched) for i in self.config.input],
            "outputs": [tensor_md(o, batched) for o in self.config.output],
        }

    # -- compute -----------------------------------------------------------
    @abc.abstractmethod
    def execute(self, inputs: Dict[str, Any], parameters: Dict[str, Any]) -> Dict[str, Any]:
        ...

    def execute_decoupled(
        self, inputs: Dict[str, Any], parameters: Dict[str, Any]
    ) -> Iterator[Dict[str, Any]]:
        raise InferError(f"model '{self.name}' is not decoupled")

    def labels(self, output_name: str) -> Optional[List[str]]:
        """Classification labels for an output, if provided."""
        return None

    def flops_per_element(self) -> Optional[float]:
        """Analytic forward FLOPs per batch element — the live-MFU
        numerator (``nv_tpu_live_mfu``).  Resolution: the model config's
        ``flops_per_inference`` parameter (a float string), else None (no
        MFU series for this model — unknown must read as absent, not 0%).
        Memoized: the config never changes under a live instance."""
        cached = getattr(self, "_flops_pe_cache", False)
        if cached is not False:
            return cached
        value: Optional[float] = None
        if "flops_per_inference" in self.config.parameters:
            try:
                parsed = float(
                    self.config.parameters["flops_per_inference"]
                    .string_value)
                if parsed > 0:
                    value = parsed
            except ValueError:
                pass
        self._flops_pe_cache = value
        return value

    def unload(self) -> None:
        """Hook for releasing device buffers on model unload."""


class JaxModel(Model):
    """A model whose compute is a jitted pure function over arrays.

    ``fn(**inputs) -> dict[str, Array]`` is traced once per input-shape
    signature; jax handles the compile cache.  Host-side pre/post hooks cover
    non-arraylike work (e.g. BYTES handling, which stays host-side on TPU —
    SURVEY.md §7 hard parts (c)).
    """

    def __init__(
        self,
        config: pb.ModelConfig,
        fn: Callable[..., Dict[str, Any]],
        jit: bool = True,
        host_pre: Optional[Callable] = None,
        host_post: Optional[Callable] = None,
        donate_argnames: Optional[Sequence[str]] = None,
        output_labels: Optional[Dict[str, List[str]]] = None,
        analyzable: Optional[bool] = None,
    ):
        super().__init__(config)
        if jit:
            import jax

            fn = jax.jit(fn, donate_argnames=donate_argnames)
        # XLA cost analysis re-traces fn; that is invisible for a jitted
        # pure function, but a jit=False fn may carry host side effects,
        # so those models must declare tracing-safety to opt in
        self._analyzable = jit if analyzable is None else analyzable
        self._fn = fn
        self._host_pre = host_pre
        self._host_post = host_post
        self._output_labels = output_labels or {}
        self._device = None

    def execute(self, inputs: Dict[str, Any], parameters: Dict[str, Any]) -> Dict[str, Any]:
        import jax

        if self._device is None:
            self._device = resolve_instance_device(self.config)
        if self._host_pre is not None:
            inputs = self._host_pre(inputs, parameters)
        with jax.default_device(self._device):
            outputs = self._fn(**inputs)
        if self._host_post is not None:
            outputs = self._host_post(outputs, parameters)
        return outputs

    def labels(self, output_name: str) -> Optional[List[str]]:
        return self._output_labels.get(output_name)

    def analyze_cost(self, inputs: Dict[str, Any],
                     parameters: Optional[Dict[str, Any]] = None):
        """XLA cost analysis for one concrete input signature: AOT-lower
        the compute function (nothing executes) and extract scheduled
        FLOPs / bytes accessed / memory breakdown.  Mirrors ``execute``'s
        graph — same host_pre transform, same device — so the analyzed
        program is the one the signature actually runs.  Returns a
        ``costs.SignatureCost`` or None (backend exposes no analysis, fn
        untraceable standalone, ...); never raises — the core calls this
        once per new signature right after the first execution."""
        import jax

        from .costs import analyze_jax_callable

        if not self._analyzable:
            # analysis AOT-lowers through a fresh jit, which re-traces the
            # python body — for a jit=False model that never declared
            # tracing-safety the re-trace is a visible side effect
            return None
        try:
            if self._device is None:
                self._device = resolve_instance_device(self.config)
            if self._host_pre is not None:
                inputs = self._host_pre(dict(inputs), parameters or {})
            with jax.default_device(self._device):
                return analyze_jax_callable(self._fn, **inputs)
        except Exception:  # noqa: BLE001 — observability must never raise
            return None


class PyModel(Model):
    """Host-side (non-jitted) model: arbitrary python over numpy arrays —
    used for BYTES/string models and custom logic (the reference's "python
    backend" analog)."""

    def __init__(self, config: pb.ModelConfig, fn: Callable, decoupled_fn=None):
        super().__init__(config)
        self._fn = fn
        self._decoupled_fn = decoupled_fn

    def execute(self, inputs, parameters):
        return self._fn(inputs, parameters)

    def execute_decoupled(self, inputs, parameters):
        if self._decoupled_fn is None:
            return super().execute_decoupled(inputs, parameters)
        return self._decoupled_fn(inputs, parameters)


class EnsembleModel(Model):
    """Ensemble scheduling: a DAG of steps mapping tensors between member
    models (reference behavioral spec: ensemble_image_client.py, SURVEY.md
    §2.7; config message at model_config ensemble_scheduling).  Executed by
    the core, which resolves member models at infer time."""

    def __init__(self, config: pb.ModelConfig):
        super().__init__(config)
        if not config.HasField("ensemble_scheduling"):
            raise InferError(f"ensemble model '{config.name}' has no ensemble_scheduling")

    def execute(self, inputs, parameters):  # pragma: no cover - core inlines
        raise InferError("ensemble models are executed by the core")
