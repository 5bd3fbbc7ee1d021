"""Always-on host profiling: where does *host* time go per token?

The device side of the stack is thoroughly observed (device_stats duty
cycles, cost ledger roofline verdicts, per-tick traces) — but the Python
host that feeds it is not.  An event-loop stall, a GC pause stretching
tick assembly, or GIL contention between the frontend and the decode
workers all show up downstream as mysterious latency with no attributed
cause.  ``HostProfiler`` closes that gap with three always-on, bounded
observers:

* a **sampling profiler** — a daemon thread walking
  ``sys._current_frames()`` at ``TRITON_TPU_PROFILE_HZ`` (default ~19 Hz,
  0 disables it) and folding each thread's stack into per-role rolling
  windows.  19 Hz is deliberately prime-ish: a sampler phase-locked to a
  10 ms batching window or a 100 Hz timer would alias and systematically
  miss (or always hit) the same code; an odd rate decorrelates.  At 19 Hz
  the sampler costs one ``sys._current_frames()`` walk per period —
  measured well under the 2% throughput bound (see BENCH
  ``profiler_overhead``).
* an **event-loop lag probe** — a self-rescheduling ``call_later``
  callback per frontend loop that measures the delta between when asyncio
  *should* have run it and when it *did*.  That delta IS the scheduling
  delay every coroutine on that loop experienced.
* **GC pause accounting** via ``gc.callbacks`` — per-generation pause
  totals, because a gen-2 collection mid-decode-tick is precisely the
  kind of host stall the roadmap's tick-scheduling work must rule out.

The last two see **pauses**: every collection, and every probe firing
that ran ``PAUSE_LATE_NS`` late, is a pause ``(kind, start_ns, end_ns)``
on ``time.monotonic_ns()`` — the request spans' clock.  Each is handed to
``on_pause`` (the core charges it to the models that had requests
pending); those of ``PAUSE_KEEP_NS`` or more are kept as events in one
bounded deque, which the flight recorder lays beside a slow request's
REQUEST span (``pauses_between``) and from which the rolling
``nv_host_loop_lag_us`` maximum and ``snapshot()``'s series are computed.

All three surface through ``metric_rows()`` into the single-declaration
``nv_host_*`` metric families, through ``snapshot()`` for JSON debug and
incident bundles, and through ``collapsed()`` as flamegraph-ready
collapsed-stack text (``/v2/debug/profile``).

Memory is bounded by construction: folded stacks aggregate into a
two-epoch rotating window (current + previous, rotated every
``window_s``) capped at ``max_stacks`` distinct stacks per epoch;
overflow folds into a synthetic ``~overflow`` frame rather than growing.
"""

from __future__ import annotations

import bisect
import contextlib
import gc
import glob
import heapq
import itertools
import os
import sys
import threading
import time
import traceback
from collections import Counter, deque
from typing import Any, Dict, List, Optional, Tuple

from .types import cut_by_step

PROFILE_HZ_ENV = "TRITON_TPU_PROFILE_HZ"
DEFAULT_PROFILE_HZ = 19.0

# distinct folded stacks kept per epoch per role — beyond this, samples
# fold into "~overflow" (bounded memory beats perfect attribution)
DEFAULT_MAX_STACKS = 2048
# epoch length of the rolling window: collapsed() always covers between
# one and two windows of history
DEFAULT_WINDOW_S = 60.0
# frames kept per sample; deeper stacks truncate at the leaf end
MAX_STACK_DEPTH = 64
# loop-lag probe cadence: every stall of the loop of 20 ms or more is seen,
# its length to within 20 ms (fifty timer callbacks a second on a loop that
# handles thousands of events)
PROBE_INTERVAL_S = 0.02
# a probe firing this late is a pause; a pause this long is kept as an event
PAUSE_LATE_NS = 10_000_000
PAUSE_KEEP_NS = 5_000_000
_PAUSE_KEEP = 512
_GC_KINDS = ("gc0", "gc1", "gc2")
_NO_SPAN = contextlib.nullcontext()


def annotation(name: str, **kwargs):
    """A span on the JAX profiler's clock (``jax.profiler.TraceAnnotation``)
    for a SYNCHRONOUS section: a TraceMe belongs to its thread, so one held
    across an ``await`` would overlap its neighbours on the loop's line.
    With no profiler session it is an inactive TraceMe — one context
    manager.  A process that has not imported JAX has no session, and is
    not made to import it for this."""
    cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    return cls(name, **kwargs) if cls is not None else _NO_SPAN


# what the host was doing in a gap of the device's time line
# (``types.cut_by_step``): the dry account's four causes and, from
# ``t_called`` to the gap's end, ``dispatch``: inside ``model.execute``
# before a program ran
GAP_CAUSES = ("no_request", "window", "late", "host", "dispatch")
_OP_LINE = "XLA Ops"
_GAPS_KEPT = 32


def attribute_gaps(op_lines: Dict[str, list], records: List[dict],
                   window: Optional[Tuple[int, int]] = None) -> dict:
    """Every idle gap of every device, charged to the step that follows it
    and cut by that step's host points.

    ``op_lines`` maps a device to its op line's ``(name, start, end)``
    events, ns on the trace's clock.  ``records`` are the ``step.record``
    events: their arguments and ``at``, the event's own start on that
    clock; ``at - now`` maps the record's ``monotonic_ns`` points onto it.
    The gaps are those between the ops inside ``window`` (``(lo, hi)``;
    default: each device's first op to its last).  The step after a gap is
    the one called last before the gap's end whose outputs were not yet on
    the host then; a gap with none is charged to ``"none"`` (a program of
    no recorded step follows, such as a device loop's tick, or a step the
    trace ended before).  Returns ``by_cause`` (ns, summed over devices),
    ``devices`` (each one's ``window_ns``, ``idle_ns`` and ``by_cause``)
    and ``gaps``, the longest ones with their step and parts."""
    steps = []
    for r in records:
        offset = r["at"] - r["now"]
        # a step no batcher formed (a direct request, an ensemble member;
        # a warm-up, which has no member either) has no window and no
        # close: from its arrival until the call it is the host's
        formed = r["t_window_end"] > 0
        enqueue = r["first_enqueue"] or r["t_assembly"]
        steps.append((r["t_called"] + offset, r["t_on_host"] + offset,
                      enqueue + offset,
                      (r["t_window_end"] if formed else enqueue) + offset,
                      (r["t_assembly"] if formed else enqueue) + offset,
                      r.get("model", ""), r.get("step", 0)))
    steps.sort()
    called = [step[0] for step in steps]
    # the latest end among a step and those called before it: where that
    # lies before a gap's end no step called by then is still out
    ends = list(itertools.accumulate((step[1] for step in steps), max))
    devices, longest = {}, []
    for device, events in sorted(op_lines.items()):
        lo, hi = window or (min((e[1] for e in events), default=0),
                            max((e[2] for e in events), default=0))
        spans = []
        for _, start, end in sorted(events, key=lambda e: e[1]):
            start, end = max(start, lo), min(end, hi)
            if end <= start:
                continue
            if spans and start <= spans[-1][1]:
                spans[-1][1] = max(spans[-1][1], end)
            else:
                spans.append([start, end])
        edges = [lo] + [t for span in spans for t in span] + [hi]
        by_cause = dict.fromkeys(GAP_CAUSES + ("none",), 0)
        for gap_lo, gap_hi in zip(edges[0::2], edges[1::2]):
            if gap_hi <= gap_lo:
                continue
            at = bisect.bisect_right(called, gap_hi) - 1
            while at >= 0 and steps[at][1] < gap_hi:
                at = at - 1 if ends[at] >= gap_hi else -1
            if at < 0:
                by_cause["none"] += gap_hi - gap_lo
                parts, model, step = None, "", 0
            else:
                t_called, _, enqueue, window_end, assembly, model, step = \
                    steps[at]
                parts = cut_by_step(gap_lo, gap_hi, enqueue, window_end,
                                    assembly, t_called)
                for cause, ns in zip(GAP_CAUSES, parts):
                    by_cause[cause] += ns
            longest.append((gap_hi - gap_lo, device, gap_lo, model, step,
                            parts))
        devices[device] = {
            "window_ns": hi - lo,
            "idle_ns": (hi - lo) - sum(e - s for s, e in spans),
            "by_cause": by_cause}
    return {
        "by_cause": {cause: sum(d["by_cause"][cause]
                                for d in devices.values())
                     for cause in GAP_CAUSES + ("none",)},
        "devices": devices,
        "gaps": [{"ns": ns, "device": device, "start": start,
                  "model": model, "step": step,
                  "parts": dict(zip(GAP_CAUSES, parts)) if parts else None}
                 for ns, device, start, model, step, parts
                 in heapq.nlargest(_GAPS_KEPT, longest,
                                   key=lambda gap: gap[0])]}


def device_gaps(trace_dir: str,
                window: Optional[Tuple[int, int]] = None) -> Optional[dict]:
    """``attribute_gaps`` over the newest profile under ``trace_dir`` (a
    ``jax.profiler`` session's directory): the devices' ``XLA Ops`` lines
    and the ``step.record`` events of the host's lines, which share the
    trace's clock; ``profile`` names the file read.  None where the trace
    holds no device op line (a CPU run: its ops lie on host threads)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    op_lines, records = {}, []
    path = max(paths)  # a session's directory is named by when it began
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device:
                if line.name == _OP_LINE:
                    op_lines[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                continue
            records += [{**dict(e.stats), "at": e.start_ns}
                        for e in line.events if e.name == "step.record"]
    if not op_lines:
        return None
    return {"profile": path, **attribute_gaps(op_lines, records, window)}


def profile_hz_from_env(default: float = DEFAULT_PROFILE_HZ) -> float:
    """Sampler rate from ``TRITON_TPU_PROFILE_HZ`` (0 = off)."""
    raw = os.environ.get(PROFILE_HZ_ENV, "")
    if not raw:
        return default
    try:
        return max(0.0, float(raw))
    except ValueError:
        return default


def classify_thread(name: str) -> str:
    """Map a thread name onto its serving role.

    The roles mirror the pipeline stages an operator reasons about:
    ``frontend`` (event loops answering requests), ``decode`` (the
    per-model decode worker driving ticks), ``readback`` (device→host
    copy executors, including the ordered gen reader), ``batcher``
    (asyncio's default executor, where batched execute calls run), and
    ``other`` for everything else.
    """
    if "-decode-worker" in name:
        return "decode"
    if "-readback" in name or "-gen" in name:
        return "readback"
    if name == "MainThread" or name.startswith("tc-tpu-server"):
        return "frontend"
    if name.startswith("asyncio_") or "ThreadPoolExecutor" in name:
        return "batcher"
    return "other"


def fold_stack(frame, limit: int = MAX_STACK_DEPTH) -> str:
    """Collapse a frame chain into ``file:func;file:func`` root-first —
    the flamegraph collapsed-stack convention (Brendan Gregg format)."""
    parts: List[str] = []
    f = frame
    while f is not None and len(parts) < limit:
        code = f.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:{code.co_name}")
        f = f.f_back
    parts.reverse()
    return ";".join(parts)


def dump_threads() -> str:
    """Faulthandler-style dump of every thread's current stack.

    Pure Python so it can be written into an incident bundle from any
    thread at any time (``faulthandler`` itself can only write to a file
    descriptor registered up front)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: List[str] = []
    for ident, frame in sorted(sys._current_frames().items()):
        name = names.get(ident, "?")
        out.append(f"Thread 0x{ident:x} ({name}) "
                   f"[role={classify_thread(name)}]:")
        out.extend(line.rstrip("\n")
                   for line in traceback.format_stack(frame))
        out.append("")
    return "\n".join(out)


class _Capture:
    """A live incident capture: the sampler feeds every sample into it
    while registered, independent of window rotation."""

    def __init__(self) -> None:
        self.counts: Counter = Counter()  # (role, stack) -> samples
        self.samples = 0


class HostProfiler:
    """Always-on sampling profiler + loop-lag probe + GC accounting.

    ``start()`` registers the GC callback and (when ``hz > 0``) launches
    the sampler thread; the loop-lag probes are installed separately per
    frontend loop via :meth:`install_loop_probe`.  Everything stops
    cleanly via :meth:`stop` — the profiler owns no resources a test
    harness can leak.
    """

    def __init__(self, hz: Optional[float] = None,
                 window_s: float = DEFAULT_WINDOW_S,
                 max_stacks: int = DEFAULT_MAX_STACKS):
        self.hz = profile_hz_from_env() if hz is None else max(0.0, hz)
        self.window_s = window_s
        self.max_stacks = max_stacks
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._started = False
        # -- folded-stack windows: two epochs, rotated every window_s --
        self._epoch: Counter = Counter()       # (role, stack) -> samples
        self._prev_epoch: Counter = Counter()
        self._epoch_started = time.monotonic()
        self._samples_by_role: Counter = Counter()  # cumulative, per role
        self._captures: List[_Capture] = []
        # boost: incident captures temporarily raise the sampling rate
        self._boost_hz = 0.0
        self._boost_until = 0.0
        # thread-name map, refreshed when the ident set changes (a
        # threading.enumerate() per sample would dominate sampler cost)
        self._names: Dict[int, str] = {}
        self._names_key: frozenset = frozenset()
        # -- pauses ----------------------------------------------------
        # the one event store: (kind, start_ns, end_ns) on monotonic_ns,
        # kind "gc<generation>" or "loop:<probe name>", PAUSE_KEEP_NS or
        # longer.  Appended lock-free (deque.append is atomic) by the
        # collector's hook and the probes; readers copy it with list().
        self._pauses: deque = deque(maxlen=_PAUSE_KEEP)
        # called with every pause's length in ns, kept or not, on the
        # thread the pause ended on — the collector's hook included, so
        # it must not block on a lock that thread may hold
        self.on_pause = None
        # -- loop-lag probes -------------------------------------------
        # loop name -> {"last_us", "gc_ns"}; written by its probe alone
        self._loops: Dict[str, Dict[str, Any]] = {}
        # -- GC accounting ---------------------------------------------
        # _on_gc runs re-entrantly on WHATEVER thread triggered the
        # collection — including one already holding self._lock (an
        # allocation inside metric_rows/snapshot can start a GC).  It
        # therefore never takes the lock: it is the only writer of these
        # totals (one collection runs at a time), readers read the ints.
        self._gc_start_ns: Optional[int] = None
        self._gc_span = None                          # host.gc, gen 2 only
        self._gc_pause_ns = [0, 0, 0]                 # by generation
        self._gc_collections = [0, 0, 0]
        self._gc_total_ns = 0
        self._gc_registered = False

    # -- lifecycle ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.hz > 0.0

    def start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
        if not self._gc_registered:
            gc.callbacks.append(self._on_gc)
            self._gc_registered = True
        if self.enabled:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="tc-tpu-host-profiler")
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            self._started = False
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None
        if self._gc_registered:
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass
            self._gc_registered = False

    # -- sampler -----------------------------------------------------------

    def _effective_hz(self) -> float:
        if time.monotonic() < self._boost_until:
            return max(self.hz, self._boost_hz)
        return self.hz if self.hz > 0 else 0.0

    def _run(self) -> None:
        own = threading.get_ident()
        while not self._stop.is_set():
            hz = self._effective_hz()
            if hz <= 0:
                self._stop.wait(0.25)
                continue
            self._stop.wait(1.0 / hz)
            if self._stop.is_set():
                break
            self._sample_once(exclude={own})

    def _thread_names(self, idents) -> Dict[int, str]:
        key = frozenset(idents)
        if key != self._names_key:
            self._names = {t.ident: t.name for t in threading.enumerate()
                           if t.ident is not None}
            self._names_key = key
        return self._names

    def _sample_once(self, exclude=frozenset()) -> None:
        frames = sys._current_frames()
        names = self._thread_names(frames.keys())
        now = time.monotonic()
        with self._lock:
            if now - self._epoch_started >= self.window_s:
                self._prev_epoch = self._epoch
                self._epoch = Counter()
                self._epoch_started = now
            for ident, frame in frames.items():
                if ident in exclude:
                    continue
                role = classify_thread(names.get(ident, f"tid-{ident}"))
                stack = fold_stack(frame)
                key = (role, stack)
                # cap distinct stacks per epoch: overflow folds into a
                # synthetic frame so totals stay honest while memory
                # stays bounded
                if (key not in self._epoch
                        and len(self._epoch) >= self.max_stacks):
                    key = (role, "~overflow")
                self._epoch[key] += 1
                self._samples_by_role[role] += 1
                for cap in self._captures:
                    cap.counts[key] += 1
                    cap.samples += 1

    # -- incident capture --------------------------------------------------

    def boost(self, hz: float, duration_s: float) -> None:
        """Temporarily raise the sampling rate (incident deep capture)."""
        self._boost_hz = max(self._boost_hz, hz)
        self._boost_until = max(self._boost_until,
                                time.monotonic() + duration_s)

    def capture_window(self, duration_s: float = 1.0,
                       hz: float = 97.0) -> str:
        """Boosted-rate capture for an incident bundle: sample at ``hz``
        for ``duration_s`` and return the window as collapsed-stack text.

        Rides the live sampler thread when one is running (a registered
        capture sink sees every sample regardless of epoch rotation);
        when the always-on sampler is off (``hz=0`` deployments), samples
        inline on the caller's thread — an incident capture must work
        exactly when profiling was disabled to save the 2%.
        """
        cap = _Capture()
        t = self._thread
        if t is not None and t.is_alive() and not self._stop.is_set():
            with self._lock:
                self._captures.append(cap)
            self.boost(hz, duration_s)
            time.sleep(duration_s)
            with self._lock:
                try:
                    self._captures.remove(cap)
                except ValueError:
                    pass
        else:
            own = threading.get_ident()
            deadline = time.monotonic() + duration_s
            period = 1.0 / max(hz, 1.0)
            with self._lock:
                self._captures.append(cap)
            try:
                while time.monotonic() < deadline:
                    self._sample_once(exclude={own})
                    time.sleep(period)
            finally:
                with self._lock:
                    try:
                        self._captures.remove(cap)
                    except ValueError:
                        pass
        return self._render_collapsed(cap.counts)

    # -- loop-lag probe ----------------------------------------------------

    def install_loop_probe(self, loop, name: str = "frontend",
                           interval_s: float = PROBE_INTERVAL_S) -> None:
        """Install the self-rescheduling lag probe on ``loop``.

        Each firing measures ``actual - expected`` run time: exactly the
        scheduling delay every other callback on that loop paid.  The
        probe survives until :meth:`stop` (it simply stops rescheduling);
        a closed loop drops the pending timer harmlessly.
        """
        with self._lock:
            if name in self._loops:
                # second frontend on the SAME loop (http + metrics app
                # share one): one probe per loop is enough
                return
            state = {"last_us": 0.0, "gc_ns": self._gc_total_ns}
            self._loops[name] = state
        kind = f"loop:{name}"

        def _tick(expected: float) -> None:
            if self._stop.is_set():
                return
            now = loop.time()
            end_ns = time.monotonic_ns()
            lag_ns = max(0, int((now - expected) * 1e9))
            state["last_us"] = lag_ns / 1e3
            # collections since the last firing were charged by the
            # collector's hook: a firing they made late is charged net of
            # them, so no millisecond is charged twice
            gc_ns = self._gc_total_ns
            collected, state["gc_ns"] = gc_ns - state["gc_ns"], gc_ns
            if lag_ns >= PAUSE_LATE_NS:
                self._pauses.append((kind, end_ns - lag_ns, end_ns))
                self._charge(lag_ns - collected)
            loop.call_later(interval_s, _tick, now + interval_s)

        loop.call_soon_threadsafe(
            lambda: loop.call_later(
                interval_s, _tick, loop.time() + interval_s))

    # -- GC accounting -----------------------------------------------------

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        # CPython runs one collection at a time under the GIL, so a
        # single start stamp is race-free.  Lock-free on purpose: the
        # callback fires on the thread that tripped the collection,
        # which may already hold self._lock (see __init__).
        gen = min(2, int(info.get("generation", 0)))
        if phase == "start":
            if gen == 2:
                # the 40-60 ms ones, on the profiler's clock; the young
                # generations fire too often to pay an object each
                self._gc_span = annotation("host.gc")
                self._gc_span.__enter__()
            self._gc_start_ns = time.monotonic_ns()
        elif phase == "stop" and self._gc_start_ns is not None:
            end_ns = time.monotonic_ns()
            start_ns, self._gc_start_ns = self._gc_start_ns, None
            span, self._gc_span = self._gc_span, None
            if span is not None:
                span.__exit__(None, None, None)
            dt = end_ns - start_ns
            self._gc_pause_ns[gen] += dt
            self._gc_collections[gen] += 1
            self._gc_total_ns += dt
            if dt >= PAUSE_KEEP_NS:
                self._pauses.append((_GC_KINDS[gen], start_ns, end_ns))
            self._charge(dt)

    def _charge(self, ns: int) -> None:
        sink = self.on_pause
        if sink is not None and ns > 0:
            sink(ns)

    def pauses_between(self, start_ns: int, end_ns: int) -> List[Dict[str, Any]]:
        """The kept pauses that overlap ``[start_ns, end_ns]`` on
        ``time.monotonic_ns()``, oldest first."""
        return [{"kind": k, "start_ns": s, "end_ns": e}
                for k, s, e in list(self._pauses)
                if s < end_ns and e > start_ns]

    # -- output surfaces ---------------------------------------------------

    @staticmethod
    def _render_collapsed(counts: Counter) -> str:
        lines = [f"{role};{stack} {n}"
                 for (role, stack), n in sorted(counts.items(),
                                                key=lambda kv: -kv[1])]
        return "\n".join(lines) + ("\n" if lines else "")

    def collapsed(self, role: Optional[str] = None) -> str:
        """Rolling-window folded stacks as collapsed-stack text (feed
        straight to ``flamegraph.pl`` / speedscope)."""
        with self._lock:
            merged = self._prev_epoch + self._epoch
        if role is not None:
            merged = Counter({k: v for k, v in merged.items()
                              if k[0] == role})
        return self._render_collapsed(merged)

    def top_stacks(self, n: int = 10,
                   role: Optional[str] = None) -> List[Tuple[str, str, int]]:
        """(role, folded stack, samples) for the n hottest stacks in the
        rolling window — the incident-report and debug-JSON shape."""
        with self._lock:
            merged = self._prev_epoch + self._epoch
        items = [(r, s, c) for (r, s), c in merged.items()
                 if role is None or r == role]
        items.sort(key=lambda t: -t[2])
        return items[:n]

    def _loop_series(self) -> Dict[str, List[Tuple[int, float]]]:
        """Per probed loop, its kept pauses inside the rolling window as
        ``(end_ns, lag_us)``, oldest first."""
        cutoff = time.monotonic_ns() - int(self.window_s * 1e9)
        out: Dict[str, List[Tuple[int, float]]] = {}
        for kind, start, end in list(self._pauses):
            if end >= cutoff and kind.startswith("loop:"):
                out.setdefault(kind[5:], []).append((end, (end - start) / 1e3))
        return out

    def loop_lag(self, series=None) -> Dict[str, Dict[str, float]]:
        """Per probed loop, the last firing's lag and the rolling maximum:
        the longest kept pause of the loop inside ``window_s``, or the
        last lag where that is longer."""
        if series is None:
            series = self._loop_series()
        with self._lock:
            loops = {name: st["last_us"] for name, st in self._loops.items()}
        return {name: {"last_us": last,
                       "max_us": max([last] + [us for _, us in
                                               series.get(name, [])])}
                for name, last in loops.items()}

    def metric_rows(self) -> Dict[str, List[Tuple[Dict[str, str], float]]]:
        """Rows for the single-declaration ``nv_host_*`` families in
        ``metrics.collect_families`` (keys are family short-names)."""
        lag = [({"loop": name}, st["max_us"])
               for name, st in sorted(self.loop_lag().items())]
        pauses = [({"generation": str(gen)}, self._gc_pause_ns[gen] / 1e3)
                  for gen in range(3) if self._gc_collections[gen]]
        with self._lock:
            samples = [({"role": role}, float(n))
                       for role, n in sorted(self._samples_by_role.items())]
        return {"loop_lag": lag, "gc_pause": pauses, "samples": samples}

    def snapshot(self) -> Dict[str, Any]:
        """JSON shape for ``/v2/debug/profile?format=json`` and incident
        bundles."""
        series = self._loop_series()
        lags = self.loop_lag(series)
        with self._lock:
            merged = self._prev_epoch + self._epoch
            top = sorted(((r, s, c) for (r, s), c in merged.items()),
                         key=lambda t: -t[2])[:50]
            return {
                "hz": self.hz,
                "enabled": self.enabled,
                "window_s": self.window_s,
                "samples_by_role": dict(self._samples_by_role),
                "distinct_stacks": len(merged),
                "top_stacks": [{"role": r, "stack": s, "samples": c}
                               for r, s, c in top],
                "loop_lag": {
                    name: {"last_us": st["last_us"],
                           "max_us": st["max_us"],
                           "series": [
                               {"ts_mono": end / 1e9, "lag_us": us}
                               for end, us in series.get(name, [])[-64:]]}
                    for name, st in lags.items()},
                "gc": {
                    str(gen): {
                        "pause_us_total": self._gc_pause_ns[gen] / 1e3,
                        "collections": self._gc_collections[gen]}
                    for gen in range(3) if self._gc_collections[gen]},
            }
