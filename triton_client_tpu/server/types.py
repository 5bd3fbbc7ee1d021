"""Transport-neutral request/response model for the serving harness.

Both frontends (HTTP ``http_server.py`` and gRPC ``grpc_server.py``) decode
into these structures; the core (``core.py``) only ever sees them.  This is
the harness-side mirror of the client's L2 tensor layer (SURVEY.md §1).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


@dataclass
class InputTensor:
    name: str
    datatype: str
    shape: Tuple[int, ...]
    # Exactly one of `data` (decoded ndarray) / `shm` (region reference).
    data: Optional[np.ndarray] = None
    shm: Optional["ShmRef"] = None
    parameters: Dict[str, Any] = field(default_factory=dict)


@dataclass
class ShmRef:
    region_name: str
    byte_size: int
    offset: int = 0


@dataclass
class RequestedOutput:
    name: str
    binary_data: bool = True  # HTTP only: whether to return binary or JSON
    class_count: int = 0
    shm: Optional[ShmRef] = None
    parameters: Dict[str, Any] = field(default_factory=dict)


@dataclass
class InferRequest:
    model_name: str
    model_version: str = ""
    id: str = ""
    inputs: List[InputTensor] = field(default_factory=list)
    outputs: List[RequestedOutput] = field(default_factory=list)
    parameters: Dict[str, Any] = field(default_factory=dict)
    # Trace propagation (client telemetry layer): the frontend fills these
    # from the `triton-request-id` / `traceparent` header (gRPC metadata);
    # the tracer records them and the response echoes the id back.
    client_request_id: str = ""
    traceparent: str = ""
    # Wire-decode window (span tracing): the frontend stamps when it began
    # and finished decoding the wire request so a sampled trace gets a
    # DECODE child span.  0 = frontend did not instrument decode.
    decode_start_ns: int = 0
    decode_end_ns: int = 0
    # A frontend that sets this owns trace finalization: the core hands the
    # sampled TraceContext back on the response (InferResponse.trace) so
    # SERIALIZE/NETWORK_WRITE spans land inside the emitted record.  Paths
    # that never finalize (generate, OpenAI, streaming) leave it False and
    # the core emits at the end of its own envelope, as before.
    trace_handoff: bool = False
    # Which wire the request arrived on ("http" / "grpc"; "" for in-process
    # callers) — recorded per request by the flight recorder.
    protocol: str = ""
    # Wire payload size (bytes) as received by the frontend (HTTP body
    # length / gRPC message ByteSize; 0 for in-process callers).  The
    # memory governor (server/memory.py) reserves this against the host
    # byte budget at admission and releases it when the envelope
    # completes.
    wire_bytes: int = 0
    # Absolute deadline on the server's monotonic clock (0 = none).  The
    # frontends derive it from the v2 `timeout` request parameter
    # (microseconds; both protocols) or the `triton-timeout-us` HTTP
    # header — the wire forms the client resilience layer propagates its
    # remaining deadline budget through.  An expired request is dropped at
    # dequeue / batch assembly without entering COMPUTE.
    deadline_ns: int = 0
    # -- QoS (server/qos.py) ----------------------------------------------
    # Tenant id resolved by the frontend (triton-tenant header, then the
    # basic-auth username, then "anonymous" — filled by the core if the
    # frontend left it empty).
    tenant: str = ""
    # v2 request priority (0 = highest), consumed out of `parameters` by
    # the frontend so priority never splits dynamic-batch parameter
    # groups; `tier` is the admission-resolved QoS class.
    priority: int = 0
    tier: int = 0
    # Filled by the core:
    arrival_ns: int = field(default_factory=lambda: time.monotonic_ns())

    def expired(self, now_ns: Optional[int] = None) -> bool:
        """Whether this request's deadline has already passed."""
        if not self.deadline_ns:
            return False
        return (now_ns if now_ns is not None
                else time.monotonic_ns()) >= self.deadline_ns

    @property
    def sequence_id(self):
        return self.parameters.get("sequence_id", 0)

    @property
    def sequence_start(self) -> bool:
        return bool(self.parameters.get("sequence_start", False))

    @property
    def sequence_end(self) -> bool:
        return bool(self.parameters.get("sequence_end", False))


@dataclass
class OutputTensor:
    name: str
    datatype: str
    shape: Tuple[int, ...]
    # Host ndarray at the frontend boundary; None when the output was
    # delivered through a shared-memory region (the core wrote it there and
    # the frontend must emit only shm params, no data):
    data: Optional[np.ndarray]
    shm: Optional[ShmRef] = None
    parameters: Dict[str, Any] = field(default_factory=dict)


@dataclass
class InferResponse:
    model_name: str
    model_version: str
    id: str = ""
    outputs: List[OutputTensor] = field(default_factory=list)
    parameters: Dict[str, Any] = field(default_factory=dict)
    # Sampled TraceContext handed to a finalizing frontend (see
    # InferRequest.trace_handoff); never serialized onto the wire.
    trace: Any = None
    # The ``ModelStats`` that counted this request and its rows (set by the
    # core; never serialized): the frontend charges the ``request`` entry
    # once it has built the wire response.
    stats: Any = None
    rows: int = 1

    def count_request(self, t_recv_ns: int) -> None:
        """The frontend entered its handler at ``t_recv_ns`` and has now
        built the response and handed it to the transport."""
        if self.stats is not None:
            self.stats.record_request(
                self.rows, time.monotonic_ns() - t_recv_ns)


def cut_by_step(lo: int, hi: int, first_enqueue: int, t_window_end: int,
                t_assembly: int, t_called: int) -> Tuple[int, int, int, int,
                                                         int]:
    """An interval ``[lo, hi]`` in which the chip had none of a model's
    work, cut by the time line of the step that followed into what the host
    was doing: ``(no_request, window, late, host, dispatch)`` ns.  No
    request of the step was in the batcher before ``first_enqueue``; from
    there to ``t_window_end`` the batcher held its window open (to
    ``t_assembly`` where the batch closed inside it); from the window's
    end to ``t_assembly`` the batch did not close though its window was
    over; from ``t_assembly`` to ``t_called`` it was assembled and crossed
    to the executor thread; after ``t_called`` it was inside
    ``model.execute``.  The points are clamped into the interval in the
    order the step passed them, so every nanosecond has one cause and the
    parts sum to ``hi - lo``."""
    enqueued = min(max(first_enqueue, lo), hi)
    assembly = min(max(t_assembly, enqueued), hi)
    window_end = min(max(t_window_end, enqueued), assembly)
    called = min(max(t_called, assembly), hi)
    return (enqueued - lo, window_end - enqueued, assembly - window_end,
            called - assembly, hi - called)


def dry_split(t_dry: int, first_enqueue: int, t_window_end: int,
              t_assembly: int, t_called: int) -> Tuple[int, int, int, int]:
    """The dry interval ``[t_dry, t_called]`` of a formed step by cause
    (``cut_by_step``; nothing of it lies after ``t_called``):
    ``(no_request, window, late, host)`` ns, which sum to ``t_called -
    t_dry``; all 0 where ``t_dry`` is 0 or not before ``t_called``
    (nothing ran dry).  A lower bound on what the chip felt, and blind
    across a process-wide pause: ``t_dry`` is the step ahead's
    ``t_on_host``, stamped only when the pause is over, next to this
    step's ``t_called`` (``profiler.device_gaps`` sees the stall whole)."""
    if not 0 < t_dry < t_called:
        return 0, 0, 0, 0
    return cut_by_step(t_dry, t_called, first_enqueue, t_window_end,
                       t_assembly, t_called)[:4]


class StepMember(NamedTuple):
    """One request's part of an executed step."""
    rows: int
    tenant: str
    trace: Any        # its TraceContext, or None
    enqueue_ns: int   # when it began to wait for the step


@dataclass
class StepRecord:
    """One execution of a model: what ran, for whom, and when.

    The caller (a formed batch, a direct request, an ensemble member)
    fills what it knows up to ``t_assembled``; ``InferenceCore._run_model``
    stamps the rest and hands the record to ``InferenceCore._book``, the
    one place that writes it into the statistics, the collector, the cost
    ledger and the members' traces.  Times are ``time.monotonic_ns()``
    points (0: not reached); every duration a book needs is a difference
    of two of them."""

    model: str
    version: str
    stats: Any             # the ModelStats it counts in; None: a warm-up
    path: str              # "batch" | "direct" | "member" (of an ensemble)
    rows: int              # real rows
    bucket: int            # rows run, pad rows included
    members: Sequence[StepMember] = ()
    seq: int = 0           # its number among the model's steps: the ``step``
    #                        argument its profiler annotations share
    carried: int = 0       # rows the batcher closed the batch without
    held_ns: int = 0       # how long past its window's end the batcher kept
    #                        the batch open for the one batch ahead
    early_ns: int = 0      # how long before its window's end a batch short
    #                        of the top bucket was assembled: the batcher
    #                        closed it, the chip having nothing to run
    queue_depth: int = 0   # requests left queued as the batch formed
    batcher: Any = None    # the _DynamicBatcher that formed it and learns
    #                        from it as it is booked (None: no batcher did)
    t_window_end: int = 0  # a formed batch: its first member's enqueue_ns +
    #                        the model's queue delay
    t_assembly: int = 0    # the batch's concat + pad began,
    t_assembled: int = 0   # and ended; a lone request: both when it ran
    t_submit: int = 0      # handed to the executor (0: ran inline)
    t_exec: int = 0        # the worker picked it up
    t_called: int = 0      # model.execute called,
    t_returned: int = 0    # and returned
    t_on_host: int = 0     # outputs read back (0: they stay on the device)
    t_dry: int = 0         # a formed batch: when the model's earlier steps
    #                        were last all on the host, where that was before
    #                        t_called (0: one still ran or was queued on the
    #                        device then, or none came before); known once
    #                        the step has ended, stamped as it is booked
    t_done: int = 0        # the caller has them: the v2 window's end
    device_loop: bool = False   # the model books its own ticks and costs
    signature: Optional[tuple] = None   # compile signature (XLA models)
    cost: Any = None       # its SignatureCost, where this step analysed it
    d2h_count: int = 0     # outputs read back from the device,
    d2h_bytes: int = 0     # and their bytes
    ok: bool = False

    @property
    def formed(self) -> bool:
        """The dynamic batcher formed it."""
        return self.path == "batch"

    @property
    def queue_ns(self) -> int:
        """The v2 ``queue`` entry: the first member's wait, a row."""
        return self.t_assembled - self.members[0].enqueue_ns

    @property
    def member_queue_ns(self) -> int:
        """Each member's own wait until the step began, summed by row."""
        return sum((self.t_assembly - m.enqueue_ns) * m.rows
                   for m in self.members)

    @property
    def assembly_ns(self) -> int:
        return self.t_assembled - self.t_assembly

    @property
    def compute_ns(self) -> int:
        """The v2 ``compute_infer`` window, as the caller lives it."""
        return self.t_done - self.t_assembled

    @property
    def executor_wait_ns(self) -> int:
        return self.t_exec - self.t_submit if self.t_submit else 0

    @property
    def window_ns(self) -> int:
        """``model.execute``: the ``dispatch`` entry, the COMPUTE span, the
        collector's compute window and what the ledger shares out."""
        return self.t_returned - self.t_called

    @property
    def device_wait_ns(self) -> int:
        return self.t_on_host - self.t_returned if self.t_on_host else 0

    @property
    def dry_ns(self) -> Tuple[int, int, int, int]:
        """``dry_split`` of this step: the ``dry_no_request``,
        ``dry_window``, ``dry_late`` and ``dry_host`` entries, a step."""
        return dry_split(self.t_dry, self.members[0].enqueue_ns,
                         self.t_window_end, self.t_assembly, self.t_called)

    @property
    def fail_ns(self) -> int:
        """What a failed step is charged a row: a direct request its queue
        time, a formed batch nothing, an ensemble member the time it ran
        (each path's convention since before the record)."""
        if self.path == "direct":
            return self.queue_ns
        return self.compute_ns if self.path == "member" else 0


class InferError(Exception):
    """Server-side inference error with an HTTP status / gRPC code mapping.

    ``retry_after_s`` carries server pushback for shed load (HTTP 429 →
    ``Retry-After`` header; gRPC RESOURCE_EXHAUSTED → ``retry-after-ms``
    trailing metadata) so a well-behaved client backs off for exactly the
    horizon the server asked for."""

    def __init__(self, msg: str, http_status: int = 400,
                 retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.http_status = http_status
        self.retry_after_s = retry_after_s
        # why admission refused this request ("memory" for byte-budget /
        # HBM-headroom sheds) — stamped onto the flight record so an
        # operator can tell memory sheds from queue-depth sheds
        self.shed_reason: Optional[str] = None


def apply_request_deadline(req: InferRequest,
                           header_us: Optional[str] = None) -> None:
    """Resolve a request's server-side deadline from its wire forms.

    The v2 ``timeout`` request parameter (microseconds, both protocols) is
    *consumed* here — it describes the transport contract, not the model,
    and leaving it in ``parameters`` would split dynamic-batch parameter
    groups per-deadline.  ``header_us`` is the HTTP ``triton-timeout-us``
    header, which wins over the body parameter when both are present (the
    header is restamped per retry attempt with the shrunken budget)."""
    raw = req.parameters.pop("timeout", None)
    if header_us is not None:
        raw = header_us
    if raw is None:
        return
    try:
        us = int(raw)
    except (TypeError, ValueError):
        raise InferError(
            f"invalid request timeout {raw!r}: expected an integer "
            "microseconds value")
    if us > 0:
        req.deadline_ns = time.monotonic_ns() + us * 1000


def apply_request_priority(req: InferRequest) -> None:
    """Consume the v2 ``priority`` request parameter (0 = highest) into
    ``req.priority``.  Consumed, like ``timeout``: priority steers dequeue
    order, not model semantics, and leaving it in ``parameters`` would
    split dynamic-batch parameter groups per priority class."""
    raw = req.parameters.pop("priority", None)
    if raw is None:
        return
    try:
        priority = int(raw)
    except (TypeError, ValueError):
        priority = -1  # fall through to the one rejection path below
    if priority < 0:
        # rejected, not clamped: a negative priority silently promoted to
        # tier 0 would grant preemption rights to malformed input (and
        # gRPC's uint64 param already rejects it client-side — both
        # protocols must agree)
        raise InferError(
            f"invalid request priority {raw!r}: expected a non-negative "
            "integer")
    req.priority = priority


def reshape_input(arr: np.ndarray, shape, name: str) -> np.ndarray:
    """Reshape client-provided tensor data, failing as a client error (HTTP
    400 / gRPC InvalidArgument) instead of an escaped ValueError."""
    try:
        return arr.reshape(shape)
    except (ValueError, TypeError) as e:
        raise InferError(
            f"invalid shape {list(shape)} for input '{name}': {e}")
