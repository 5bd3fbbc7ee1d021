"""Server-side request tracing behind the ``/v2/trace/setting`` API.

The reference client manages trace settings on a server that actually traces:
``update_trace_settings``/``get_trace_settings`` configure ``trace_file``,
``trace_level``, ``trace_rate``, ``trace_count`` (reference
src/python/library/tritonclient/http/_client.py:767-865 and
grpc/_client.py:832-979), and the Triton server then emits per-request
timestamp timelines to ``trace_file``.  This module is the server half for the
TPU harness: ``RequestTracer`` samples requests at ``trace_rate``, collects a
REQUEST/QUEUE/COMPUTE timeline, and appends one JSON object per traced request
to ``trace_file``.

File format: JSON Lines — each line is one complete object,

    {"id": 7, "model_name": "simple", "model_version": "1",
     "timestamps": [{"name": "REQUEST_START", "ns": ...}, ...],
     "spans": [{"name": "REQUEST", "start_ns": ..., "end_ns": ...,
                "parent": null},
               {"name": "COMPUTE", "start_ns": ..., "end_ns": ...,
                "parent": "REQUEST"}, ...]}

``timestamps`` mirrors the flat timestamp-list shape of Triton's trace
summary input and is kept for existing consumers; ``spans`` is the
Dapper/OpenTelemetry-style span tree recorded by the instrumentation points
in the core, the dynamic batcher, the shm staging paths, and both frontends
(root span ``REQUEST``; children among DECODE, QUEUE, BATCH_ASSEMBLY,
H2D_TRANSFER, COMPUTE, D2H_TRANSFER, SERIALIZE, NETWORK_WRITE).  The
``triton_client_tpu.tools.trace_summary`` CLI consumes either shape.  An
append-per-request stream (rather than one rewritten JSON array) keeps the
file well-formed at every instant and safe under concurrent writers.
``log_frequency`` > 0 rotates the stream into ``<trace_file>.0``,
``<trace_file>.1``, … with that many traces per file (reference server
contract); 0 (the default) appends to the single configured file forever.

``trace_level`` semantics:

* ``OFF`` — tracing disabled (default).
* ``TIMESTAMPS`` — emit per-request timelines to ``trace_file``.
* ``TENSORS`` — refused loudly at update time (HTTP 501 / gRPC UNIMPLEMENTED):
  tensor-payload capture would force a host copy of every traced tensor on the
  TPU path, and silently accepting-then-ignoring the level is worse than
  refusing it.
* ``PROFILE`` — TPU extension (SURVEY §5 maps trace settings onto "JAX
  profiler / XLA dump toggles"): while set, ``jax.profiler`` trace collection
  runs into ``<trace_file>.profile/`` for TensorBoard/Perfetto.

Timestamps use ``time.monotonic_ns()`` — the same clock as request
``arrival_ns`` and the statistics subsystem, so trace entries line up with
``/v2/models/*/stats`` durations.
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
from typing import Dict, List, Optional

import time

from .profiler import device_gaps
from .types import InferError

_KNOWN_LEVELS = {"OFF", "TIMESTAMPS", "TENSORS", "PROFILE"}


def token_event_stride(default: int = 8) -> int:
    """``TRITON_TPU_TRACE_TOKEN_STRIDE``: every Nth generated token of a
    traced stream gets a ``TOKEN[n]`` timestamp (the first token always
    stamps ``FIRST_TOKEN``).  Strided, not per-token: a 2k-token traced
    generation must not grow a 2k-entry timeline — the stride keeps the
    record bounded while the (t[n+k]-t[n])/k differences still recover
    ITL percentiles.  The same stride batches the frontends' per-chunk
    ``NETWORK_WRITE`` spans.  Junk or non-positive values fall back to
    the default (a bad env var must not break tracing)."""
    try:
        n = int(os.environ.get("TRITON_TPU_TRACE_TOKEN_STRIDE", default))
    except ValueError:
        return default
    return n if n > 0 else default


#: Per-stream tick entries kept on one trace record: a pathological
#: million-token generation must not pin an unbounded tick list in host
#: memory; past the cap the record keeps the first N (admission/TTFT end
#: of the timeline) and counts the rest in ``ticks_dropped``.
MAX_TICKS_PER_STREAM = 512

#: The trace context of the request currently being served on this task (or
#: thread, for synchronous helpers called from it).  Set by the core around a
#: traced request so deep layers that never see the request object — the shm
#: staging paths, model code calling the server log — can attach spans /
#: correlate log lines without plumbing a parameter through every signature.
_CURRENT_TRACE: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("triton_tpu_current_trace", default=None)


def current_trace() -> Optional["TraceContext"]:
    """The TraceContext of the request being served in this context, if the
    request was sampled for tracing (None otherwise)."""
    return _CURRENT_TRACE.get()


def set_current_trace(ctx: Optional["TraceContext"]):
    return _CURRENT_TRACE.set(ctx)


def reset_current_trace(token) -> None:
    _CURRENT_TRACE.reset(token)

#: Server defaults — a ``null``/empty update value clears a key back to these
#: (reference update_trace_settings contract).
TRACE_DEFAULTS: Dict[str, List[str]] = {
    "trace_file": ["trace.json"],
    "trace_level": ["OFF"],
    "trace_rate": ["1000"],
    "trace_count": ["-1"],
    "log_frequency": ["0"],
}


def validate_trace_update(settings: Dict[str, List[str]],
                          model_scope: bool = False) -> None:
    """Reject unsupported trace settings *before* they are applied.

    Raises ``InferError`` with http_status 501 for ``trace_level=TENSORS``
    (both frontends map this to their loud-unimplemented status) and 400 for
    unknown levels or non-numeric rate/count.  ``model_scope`` additionally
    refuses PROFILE: the jax profiler is process-global, so a per-model
    toggle would be accepted-but-inert — the failure mode this module
    exists to avoid.
    """
    for key, vals in settings.items():
        if key not in TRACE_DEFAULTS:
            raise InferError(f"unknown trace setting '{key}'", http_status=400)
        if not isinstance(vals, list) or not all(isinstance(v, str) for v in vals):
            raise InferError(
                f"trace setting '{key}' expects a list of strings",
                http_status=400,
            )
    levels = settings.get("trace_level")
    if levels is not None:
        for lvl in levels:
            if lvl not in _KNOWN_LEVELS:
                raise InferError(f"unknown trace_level '{lvl}'", http_status=400)
        if "TENSORS" in levels:
            raise InferError(
                "trace_level TENSORS is not implemented on the TPU path "
                "(tensor capture would force a per-request device->host copy); "
                "use TIMESTAMPS and/or PROFILE",
                http_status=501,
            )
        if model_scope and "PROFILE" in levels:
            raise InferError(
                "trace_level PROFILE is process-global (jax profiler); set "
                "it on the global trace settings, not per model",
                http_status=400,
            )
    for key in ("trace_rate", "trace_count", "log_frequency"):
        vals = settings.get(key)
        if vals is not None:
            try:
                ival = int(vals[0])
            except (TypeError, ValueError, IndexError):
                raise InferError(
                    f"trace setting '{key}' expects an integer", http_status=400
                )
            if key == "trace_rate" and ival <= 0:
                # clamping 0 to "trace everything" would invert the intent
                raise InferError("trace_rate must be positive", http_status=400)


class Span:
    """One interval in a traced request's span tree.  ``end()`` may run on a
    different thread than the creator (the executor resolves D2H there);
    attribute stores are GIL-atomic, so no lock is needed."""

    __slots__ = ("name", "start_ns", "end_ns", "parent", "attrs")

    def __init__(self, name: str, start_ns: int,
                 parent: Optional[str] = "REQUEST") -> None:
        self.name = name
        self.start_ns = int(start_ns)
        self.end_ns: Optional[int] = None
        self.parent = parent
        # optional span attributes ({"cached_tokens": 512, ...}) — emitted
        # as "attrs" on the span dict only when set, so the common
        # attribute-less span costs nothing extra on the wire
        self.attrs: Optional[Dict[str, object]] = None

    def end(self, ns: Optional[int] = None) -> None:
        self.end_ns = int(ns if ns is not None else time.monotonic_ns())

    def set_attr(self, key: str, value) -> None:
        attrs = self.attrs
        if attrs is None:
            attrs = self.attrs = {}
        attrs[key] = value


class TraceContext:
    """One traced request: collects (name, ns) timestamps plus a span tree,
    emitted on finish.  ``path`` is the trace_file of the scope that sampled
    this request (a per-model override may point somewhere else than the
    global file).  ``client_request_id``/``traceparent`` carry the
    client-propagated trace context (``triton-request-id`` header / gRPC
    metadata) so the emitted record joins with client-side telemetry on one
    id."""

    __slots__ = ("_tracer", "id", "model_name", "model_version",
                 "timestamps", "path", "client_request_id", "traceparent",
                 "spans", "log_frequency", "_root", "_done", "sampled",
                 "flight", "tick", "outcome", "cost")

    def __init__(self, tracer: "RequestTracer", trace_id: int,
                 model_name: str, model_version: str, path: str,
                 client_request_id: str = "", traceparent: str = "",
                 log_frequency: int = 0) -> None:
        self._tracer = tracer
        self.id = trace_id
        self.model_name = model_name
        self.model_version = model_version
        self.timestamps: List[Dict[str, int]] = []
        self.path = path
        self.client_request_id = client_request_id
        self.traceparent = traceparent
        self.spans: List[Span] = []
        self.log_frequency = log_frequency
        self._root: Optional[Span] = None
        self._done = False
        # False for a shadow context (flight-recorder arming): spans are
        # collected but never written to the trace file
        self.sampled = True
        # FlightRecord of this request when the flight recorder is on
        # (completed — and possibly pinned — when the context emits)
        self.flight = None
        # batcher tick record (device_stats): which bucket/occupancy this
        # request's batched execution rode — emitted with the trace so
        # trace_summary's buckets view can fold sampled traces by tick
        self.tick = None
        # how the envelope closed: "ok", or the first failure's message
        # (mark_failed) — streamed records emit it so a cancelled/errored
        # generation is tellable from a drained one in the trace file
        self.outcome = "ok"
        # cost-attribution stamp (server/costs.py): the tenant's share of
        # the batched compute window this request rode ({"tenant",
        # "device_us", ...}) — emitted with the record and mirrored on
        # the flight record
        self.cost = None

    def ts(self, name: str, ns: Optional[int] = None) -> None:
        if not self.sampled:
            # shadow contexts exist only to feed spans to the flight
            # recorder — the legacy timestamp list never leaves the
            # process, so skip its per-request dict allocations
            return
        self.timestamps.append(
            {"name": name, "ns": int(ns if ns is not None else time.monotonic_ns())}
        )

    # -- span tree ---------------------------------------------------------
    def begin_root(self, start_ns: int) -> Span:
        """Open the REQUEST root span; every later span nests inside it."""
        self._root = Span("REQUEST", start_ns, parent=None)
        self.spans.append(self._root)
        return self._root

    def begin_span(self, name: str, start_ns: Optional[int] = None,
                   parent: Optional[str] = "REQUEST") -> Span:
        span = Span(name,
                    start_ns if start_ns is not None else time.monotonic_ns(),
                    parent)
        self.spans.append(span)
        return span

    def add_span(self, name: str, start_ns: int, end_ns: int,
                 parent: Optional[str] = "REQUEST") -> Span:
        span = Span(name, start_ns, parent)
        span.end(end_ns)
        self.spans.append(span)
        return span

    def finish(self) -> None:
        """Close the REQUEST envelope (timestamp + root span).  Idempotent:
        the core closes on the error path, a finalizing frontend closes on
        success — whichever runs first wins."""
        if self._done:
            return
        self._done = True
        now = time.monotonic_ns()
        self.ts("REQUEST_END", now)
        if self._root is not None and self._root.end_ns is None:
            self._root.end(now)

    def mark_failed(self, exc: BaseException) -> None:
        """Stamp the context's (and flight record's) outcome from an
        exception.  First failure wins — a frontend error after a core
        error must not overwrite the root cause."""
        msg = str(exc) or type(exc).__name__
        if self.outcome == "ok":
            self.outcome = msg
        rec = self.flight
        if rec is not None and rec.outcome == "ok":
            rec.outcome = msg

    def mark_cancelled(self) -> None:
        """Consumer-initiated close (disconnect, stop sequence satisfied):
        the TRACE record is stamped so a cancelled stream is tellable from
        a drained one, but the flight/SLO outcome stays "ok" — the request
        was served as far as the client wanted; counting client walk-aways
        as failures would poison SLO burn rates and trigger false fleet
        scale/rollback actions."""
        if self.outcome == "ok":
            self.outcome = "cancelled"

    async def emit_async(self) -> None:
        """Finalize from a coroutine: a sampled context pays the executor
        hop for its file append (awaited, so trace files stay
        read-after-response deterministic); a shadow context completes
        inline — no IO, and the hop would be pure per-request overhead."""
        if self.sampled:
            import asyncio

            await asyncio.get_running_loop().run_in_executor(None, self.emit)
        else:
            self.emit()

    def emit(self) -> None:
        """Finalize the context: close the envelope, append to the trace
        file (sampled contexts only — a shadow context's spans never touch
        disk), and hand the completed request to the flight recorder.  The
        no-file path is cheap enough to run inline on the event loop."""
        self.finish()
        if self.sampled:
            self._tracer._emit(self)
        rec, self.flight = self.flight, None
        if rec is not None:
            recorder = self._tracer.flight_recorder
            if recorder is not None:
                recorder.complete(rec, self)


class StreamTraceContext(TraceContext):
    """One traced LONG-LIVED streaming request (decoupled gRPC stream /
    ``generate_stream`` SSE): stays open across the whole stream envelope,
    accumulates per-token timeline events and the decode ticks the
    sequence rode, and emits ONE record at stream close (or cancel/error
    via ``mark_failed`` — the record then carries ``outcome``).

    Per-token events are STRIDED (``token_event_stride``): the first chunk
    stamps ``FIRST_TOKEN``, then every Nth stamps ``TOKEN[n]`` — bounded
    record size at any generation length, with ITL percentiles recoverable
    from the strided differences.  ``ticks`` collects the decode worker's
    per-dispatch ``tick_seq`` entries (see ``models/decode.py``), the join
    key between this sequence's lane and the cohort-dispatch lane in the
    ``trace_summary --format chrome`` view.

    Thread model: ``record_chunk`` runs on the serving event loop (the
    stream envelope), ``add_tick`` on the decode worker thread, and the
    frontends' ``record_write`` back on the loop — list appends and
    attribute stores are GIL-atomic, same discipline as ``Span.end``."""

    __slots__ = ("stride", "token_count", "first_token_ns", "last_token_ns",
                 "ticks", "ticks_dropped", "_writes",
                 "cache_hit_tokens", "prefix_hash")

    def __init__(self, *args, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.stride = token_event_stride()
        self.token_count = 0
        self.first_token_ns: Optional[int] = None
        self.last_token_ns: Optional[int] = None
        self.ticks: List[Dict[str, int]] = []
        self.ticks_dropped = 0
        self._writes = 0
        # prefix/KV cache stamp (server/kvcache.py, set by the decode
        # worker at prefill): how many prompt tokens were restored from
        # cached blocks, and the hex digest of the deepest matched block
        self.cache_hit_tokens = 0
        self.prefix_hash: Optional[str] = None

    def record_chunk(self, ns: Optional[int] = None) -> int:
        """One streamed response chunk left the core: stamp the strided
        token timeline.  Returns the chunk's 0-based index."""
        now = int(ns if ns is not None else time.monotonic_ns())
        n = self.token_count
        self.token_count = n + 1
        if n == 0:
            self.first_token_ns = now
            self.ts("FIRST_TOKEN", now)
        elif n % self.stride == 0:
            self.ts(f"TOKEN[{n}]", now)
        self.last_token_ns = now
        return n

    def add_tick(self, tick: Dict[str, int]) -> None:
        """The decode worker dispatched a fused tick this sequence rode
        (worker thread).  Bounded: past MAX_TICKS_PER_STREAM the record
        keeps the admission-end prefix and counts the overflow."""
        if len(self.ticks) >= MAX_TICKS_PER_STREAM:
            self.ticks_dropped += 1
            return
        self.ticks.append(tick)

    def record_write(self, start_ns: int, end_ns: int) -> None:
        """A frontend flushed one chunk to the wire.  Spans are batched at
        the token stride — recording a NETWORK_WRITE span per token would
        double the record's span count for no extra insight."""
        n = self._writes
        self._writes = n + 1
        if n % self.stride == 0:
            self.add_span("NETWORK_WRITE", start_ns, end_ns)


class RequestTracer:
    """Samples requests per the live settings dict and writes the trace file.

    Holds a *reference* to ``InferenceCore.trace_settings`` so client updates
    take effect on the next request without re-plumbing.  Counters (the
    ``trace_rate`` sampling position and the ``trace_count`` budget) reset on
    ``settings_updated()`` — a fresh update starts a fresh sampling window,
    matching the reference server's per-update trace_count semantics.
    """

    def __init__(self, settings: Dict[str, List[str]]) -> None:
        from .log import AppendFile

        self._settings = settings
        self._lock = threading.Lock()      # sampling counters only
        # trace-file appends use their own lock (inside AppendFile) so a
        # slow disk never serializes the sampling decision of untraced
        # requests behind a write
        self._out = AppendFile()
        self._seq = 0          # requests seen since last settings update
        self._emitted = 0      # traces emitted since last settings update
        self._next_id = 0      # file-unique trace id — never reset
        # log_frequency rotation state per base path: {"count": traces in
        # the current indexed file, "index": current file suffix}.  The
        # index is monotonic for the tracer's lifetime — a settings refresh
        # must never rewind it and overwrite an already-written .0 file.
        self._rot_lock = threading.Lock()
        self._rotation: Dict[str, Dict[str, int]] = {}
        self._profiling = False
        # per-model overlays (reference per-model trace settings: a model
        # may override any key; unset keys inherit the global value); each
        # override scope samples with its own counters
        self._model_overrides: Dict[str, Dict[str, List[str]]] = {}
        self._model_counters: Dict[str, Dict[str, int]] = {}
        # the core's FlightRecorder (set by InferenceCore): emit() hands
        # every armed context's completed record to it
        self.flight_recorder = None
        # replica identity stamped into every emitted record (set once at
        # startup from --frontend-worker / TRITON_TPU_REPLICA / host:port,
        # or by the test harness): the join key that tells which replica
        # served which leg of a cross-replica journey
        self.replica = ""
        # optional OtlpExporter (set by InferenceCore when --otlp-endpoint
        # is configured): every emitted record is also submitted there
        self.otlp = None
        # the server's log (set by InferenceCore): a profiler that fails
        # to stop is reported there
        self.log = None

    # -- settings lifecycle ------------------------------------------------
    def settings_updated(self) -> None:
        """Called by both frontends after applying a GLOBAL settings
        update: a fresh sampling window for the global scope AND for every
        override scope — a model inheriting the global budget must not
        keep an exhausted counter across the refresh."""
        with self._lock:
            self._seq = 0
            self._emitted = 0
            for c in self._model_counters.values():
                c["seq"] = 0
                c["emitted"] = 0
        self._sync_profiler()

    def update_model(self, model_name: str,
                     update: Dict[str, List[str]],
                     cleared: Optional[List[str]] = None) -> None:
        """Apply a per-model settings update (already validated): explicit
        values override the global scope; ``cleared`` keys fall back to
        inheriting it (reference null-in-model-scope contract)."""
        with self._lock:
            ov = self._model_overrides.setdefault(model_name, {})
            for k in cleared or []:
                ov.pop(k, None)
            ov.update(update)
            if not ov:
                self._model_overrides.pop(model_name, None)
            self._model_counters[model_name] = {"seq": 0, "emitted": 0}

    def effective_settings(self, model_name: Optional[str]) -> Dict[str, List[str]]:
        """The settings scope a model actually traces under (global merged
        with its overlay) — what per-model GET returns."""
        with self._lock:
            eff = {k: list(v) for k, v in self._settings.items()}
            for k, v in self._model_overrides.get(model_name, {}).items():
                eff[k] = list(v)
        return eff

    def apply(self, update: Dict[str, List[str]]) -> None:
        """Apply a validated GLOBAL settings update and open a fresh
        sampling window.  Where the update turns ``PROFILE`` on and the
        profiler cannot start, every key is put back as it was and the
        ``InferError`` (503) goes to the caller."""
        before = {k: self._settings.get(k) for k in update}
        self._settings.update(update)
        try:
            self.settings_updated()
        except InferError:
            for k, v in before.items():
                if v is None:
                    self._settings.pop(k, None)
                else:
                    self._settings[k] = v
            raise

    def _sync_profiler(self) -> None:
        want = "PROFILE" in (self._settings.get("trace_level") or [])
        if want and not self._profiling:
            import jax

            try:
                jax.profiler.start_trace(self._profile_dir())
            except Exception as e:
                # unavailable, or a session is already running elsewhere in
                # the process: say so, rather than report a level that
                # traces nothing
                raise InferError(
                    f"trace_level PROFILE: the JAX profiler did not start: "
                    f"{type(e).__name__}: {e}", http_status=503) from e
            self._profiling = True
        elif not want and self._profiling:
            self._stop_profiler()

    def _stop_profiler(self) -> None:
        import jax

        self._profiling = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            # the level is off either way; the trace file may be missing
            if self.log is not None:
                self.log.error(
                    f"trace: jax.profiler.stop_trace failed, "
                    f"{self._profile_dir()} may be incomplete: "
                    f"{type(e).__name__}: {e}")
            return
        try:
            gaps = device_gaps(self._profile_dir())
            if gaps is None:
                return  # no device line: nothing to account for
            path = os.path.join(os.path.dirname(gaps["profile"]),
                                "gaps.json")
            with open(path, "w") as f:
                json.dump(gaps, f)
        except Exception as e:
            # the profile itself is written; its reduction is a service
            if self.log is not None:
                self.log.error(f"trace: the device's idle gaps were not "
                               f"accounted: {type(e).__name__}: {e}")
            return
        if self.log is not None:
            idle = sum(d["idle_ns"] for d in gaps["devices"].values())
            self.log.info(
                f"trace: device idle {idle / 1e6:.1f} ms by cause: "
                + ", ".join(f"{cause} {ns / 1e6:.1f}"
                            for cause, ns in gaps["by_cause"].items())
                + f" ({path})")

    def _profile_dir(self) -> str:
        return self._trace_file() + ".profile"

    def shutdown(self) -> None:
        otlp, self.otlp = self.otlp, None
        if otlp is not None:
            otlp.shutdown()
        self._out.close()
        if self._profiling:
            self._stop_profiler()

    # -- per-request sampling ----------------------------------------------
    def _trace_file(self, eff: Optional[Dict[str, List[str]]] = None) -> str:
        vals = (eff if eff is not None
                else self._settings).get("trace_file") or ["trace.json"]
        return vals[0] if vals and vals[0] else "trace.json"

    @staticmethod
    def _eff_int(eff, key, default):
        vals = eff.get(key)
        try:
            return int(vals[0])
        except (TypeError, ValueError, IndexError):
            return default

    def maybe_start(self, model_name: str, model_version: str,
                    client_request_id: str = "",
                    traceparent: str = "",
                    cls: type = TraceContext) -> Optional[TraceContext]:
        with self._lock:
            ov = self._model_overrides.get(model_name)
            eff = self._settings if ov is None else {**self._settings, **ov}
            levels = eff.get("trace_level") or ["OFF"]
            if "TIMESTAMPS" not in levels:
                return None
            rate = max(1, self._eff_int(eff, "trace_rate", 1000))
            count = self._eff_int(eff, "trace_count", -1)
            if ov is None:
                self._seq += 1
                seq, emitted = self._seq, self._emitted
            else:
                # an override scope samples with its own counters — its
                # rate/count budget must not be consumed by other models
                c = self._model_counters.setdefault(
                    model_name, {"seq": 0, "emitted": 0})
                c["seq"] += 1
                seq, emitted = c["seq"], c["emitted"]
            if (seq - 1) % rate != 0:
                return None
            if count >= 0 and emitted >= count:
                return None
            if ov is None:
                self._emitted += 1
            else:
                c["emitted"] += 1
            self._next_id += 1
            trace_id = self._next_id
            path = self._trace_file(eff)
            log_frequency = max(0, self._eff_int(eff, "log_frequency", 0))
        return cls(self, trace_id, model_name, model_version, path,
                   client_request_id, traceparent,
                   log_frequency=log_frequency)

    def maybe_start_stream(self, model_name: str, model_version: str,
                           client_request_id: str = "",
                           traceparent: str = ""
                           ) -> Optional[StreamTraceContext]:
        """Sample a long-lived streaming request: same settings scope and
        counters as ``maybe_start``, but the returned context stays open
        across the whole decoupled stream (token timeline + tick joins)
        and emits once at stream close."""
        return self.maybe_start(model_name, model_version,
                                client_request_id, traceparent,
                                cls=StreamTraceContext)

    def start_shadow(self, model_name: str, model_version: str,
                     client_request_id: str = "",
                     traceparent: str = "",
                     cls: type = TraceContext) -> TraceContext:
        """An armed-but-unsampled context for the flight recorder: the full
        span instrumentation runs so a tail-latency outlier can be captured
        retroactively, but nothing reaches the trace file and neither the
        sampling counters nor the file-unique id sequence move.  No lock:
        this runs on every request when the recorder is on."""
        ctx = cls(self, 0, model_name, model_version, "",
                  client_request_id, traceparent)
        ctx.sampled = False
        return ctx

    def start_stream_shadow(self, model_name: str, model_version: str,
                            client_request_id: str = "",
                            traceparent: str = "") -> StreamTraceContext:
        """Shadow-arm a STREAM (flight recorder / SLO watch): the full
        stream instrumentation — lifecycle spans, token timeline, tick
        joins — runs so an SLO-breaching generation lands in the flight
        recorder with its whole timeline, but nothing touches the trace
        file."""
        return self.start_shadow(model_name, model_version,
                                 client_request_id, traceparent,
                                 cls=StreamTraceContext)

    def record_refusal(self, model_name: str, *,
                       shed_reason: str = "", status: int = 0,
                       tenant: str = "", protocol: str = "",
                       client_request_id: str = "",
                       traceparent: str = "") -> None:
        """A request was REFUSED before admission (QoS 429, memory 413/429,
        drain 503): emit a minimal trace record carrying the propagated
        ``traceparent`` and the ``shed_reason`` so the journey join can tell
        a shed attempt from a lost one.  Zero-cost when tracing is off: the
        first line bails before any allocation.  Refusals do not consume the
        rate/count sampling budget — a shed storm must not starve the trace
        file of the successes it is shedding to protect."""
        if "TIMESTAMPS" not in (self._settings.get("trace_level") or ["OFF"]):
            return
        now = time.monotonic_ns()
        with self._lock:
            self._next_id += 1
            rec_id = self._next_id
            path = self._trace_file()
        record: Dict[str, object] = {
            "id": rec_id,
            "model_name": model_name,
            "model_version": "",
            "timestamps": [{"name": "REFUSED", "ns": now}],
            "spans": [{"name": "REQUEST", "start_ns": now,
                       "end_ns": now, "parent": None}],
            "refused": True,
            "outcome": "shed",
        }
        if shed_reason:
            record["shed_reason"] = shed_reason
        if status:
            record["status"] = status
        if tenant:
            record["tenant"] = tenant
        if protocol:
            record["protocol"] = protocol
        if client_request_id:
            record["triton_request_id"] = client_request_id
        if traceparent:
            record["traceparent"] = traceparent
        if self.replica:
            record["replica"] = self.replica
        otlp = self.otlp
        if otlp is not None:
            otlp.submit(record)
        self._out.append(path, json.dumps(record) + "\n")

    def _emit(self, ctx: TraceContext) -> None:
        record = {
            "id": ctx.id,
            "model_name": ctx.model_name,
            "model_version": ctx.model_version,
            "timestamps": ctx.timestamps,
        }
        if ctx.spans:
            # span tree alongside — never instead of — the legacy shape:
            # existing consumers keep reading "timestamps" unchanged
            record["spans"] = [
                {"name": s.name, "start_ns": s.start_ns,
                 # an unclosed span (instrumentation raced shutdown) emits
                 # as a point rather than poisoning the record
                 "end_ns": s.end_ns if s.end_ns is not None else s.start_ns,
                 "parent": s.parent,
                 **({"attrs": s.attrs} if s.attrs else {})}
                for s in ctx.spans
            ]
        if ctx.tick is not None:
            # the batcher tick this request rode (bucket, occupancy, pad
            # waste, queue depth) — trace_summary folds these per bucket
            record["tick"] = ctx.tick
        if ctx.cost is not None:
            # per-tenant cost stamp: this request's attributed share of
            # the batched compute window (server/costs.py)
            record["cost"] = ctx.cost
        if isinstance(ctx, StreamTraceContext):
            # stream records additionally carry the token count, the close
            # outcome, and the decode ticks the sequence rode (tick_seq is
            # the join key to the tick-profiler rows / the chrome view's
            # decode-worker lane)
            record["tokens"] = ctx.token_count
            record["outcome"] = ctx.outcome
            # prefix-cache stamp: always present on stream records (0 /
            # null on a cold prefill) so downstream consumers can compute
            # fleet hit ratios without key-existence special cases
            record["cache_hit_tokens"] = ctx.cache_hit_tokens
            record["prefix_hash"] = ctx.prefix_hash
            if ctx.ticks:
                record["ticks"] = ctx.ticks
            if ctx.ticks_dropped:
                record["ticks_dropped"] = ctx.ticks_dropped
        # propagated client trace context: the join key between this record
        # and the client's telemetry (absent keys = request was not stamped)
        if ctx.client_request_id:
            record["triton_request_id"] = ctx.client_request_id
        if ctx.traceparent:
            record["traceparent"] = ctx.traceparent
        if self.replica:
            record["replica"] = self.replica
        otlp = self.otlp
        if otlp is not None:
            # never blocks: the exporter queues (or drops) under its own
            # lock, so a slow collector cannot slow the emitting request
            otlp.submit(record)
        line = json.dumps(record)
        # ctx.path is the sampling scope's file, not necessarily global;
        # an unwritable trace_file must never fail the inference that
        # happened to be sampled (AppendFile swallows OSError)
        self._out.append(self._rotated_path(ctx), line + "\n")

    def _rotated_path(self, ctx: TraceContext) -> str:
        """The file this trace lands in: the configured path itself when
        ``log_frequency`` is 0, else ``<path>.<index>`` with the index
        advancing every ``log_frequency`` emitted traces (reference server
        rotation contract)."""
        if ctx.log_frequency <= 0:
            return ctx.path
        with self._rot_lock:
            st = self._rotation.setdefault(ctx.path, {"count": 0, "index": 0})
            if st["count"] >= ctx.log_frequency:
                st["index"] += 1
                st["count"] = 0
            st["count"] += 1
            return f"{ctx.path}.{st['index']}"
