"""Inference core: the transport-neutral engine behind both frontends.

Responsibilities (the server half of the call stacks in SURVEY.md §3):

* request validation against the model config,
* shared-memory input/output resolution (system + xla registries),
* dynamic batching with pad-to-bucket (XLA-friendly: bounded shape set),
* sequence routing (no cross-request batching for stateful models),
* decoupled response streams with ``triton_final_response`` flagging,
* ensemble DAG execution,
* classification outputs (``class_count`` → "score:index[:label]" strings),
* per-model statistics.

Concurrency model: the core is asyncio-native; model compute runs in a
thread-pool executor so the event loop keeps serving while XLA executes
(jax dispatch is async, but host staging/conversion is not).
"""

from __future__ import annotations

import asyncio
import bisect
import collections
import contextvars
import threading
import time
from typing import Any, AsyncIterator, Dict, List, Optional, Set, Tuple

import numpy as np

from ..protocol import inference_pb2 as pb
from ..utils import np_to_triton_dtype, triton_to_np_dtype
from .model import EnsembleModel, JaxModel, Model, pb_to_datatype
from .registry import ModelRegistry
from .shm import SystemShmRegistry, XlaShmRegistry
from .costs import CostLedger, classify_roofline
from .device_stats import DeviceStatsCollector, SloEngine, SloObjective
from .flight_recorder import FlightRecorder
from .log import ServerLog, log_off_loop
from .memory import MemoryGovernor
from .profiler import annotation
from .qos import DEFAULT_TENANT, QosManager, TieredQueue
from .trace import RequestTracer, TRACE_DEFAULTS
from .types import (
    InferError,
    InferRequest,
    InferResponse,
    InputTensor,
    OutputTensor,
    RequestedOutput,
    StepMember,
    StepRecord,
)


class _InlineProfile:
    """Adaptive record deciding whether a model may execute inline on the
    event loop instead of paying the thread-pool hop (~2 context switches,
    worth ~25% throughput on sub-millisecond host models).

    A model earns inline execution per input-shape signature, only after the
    signature has executed at least once off-loop (so XLA compilation can
    never happen inline) and only while its execute-time EMA stays under the
    budget.  A slow inline call raises the EMA and demotes it back to the
    executor."""

    __slots__ = ("seen", "ema", "generation")
    MAX_INLINE_S = 0.001
    ALPHA = 0.3

    def __init__(self, generation: int = 0) -> None:
        self.seen: set = set()
        self.ema: Dict[tuple, float] = {}
        self.generation = generation

    def observe(self, sig: tuple, dt: float) -> None:
        if sig not in self.seen:
            # first execution of a signature may include XLA compilation —
            # record the signature but keep the sample out of the EMA
            self.seen.add(sig)
            return
        prev = self.ema.get(sig)
        self.ema[sig] = dt if prev is None else (
            self.ALPHA * dt + (1 - self.ALPHA) * prev)

    def allows(self, sig: tuple) -> bool:
        # per-signature gating: a new (larger/slower) signature must earn its
        # own off-loop EMA before it may run inline
        ema = self.ema.get(sig)
        return ema is not None and ema < self.MAX_INLINE_S


class _ResponseCache:
    """TTL + byte-budget LRU answering identical requests without
    executing the model (Triton ``response_cache.enable``).

    Keyed on (model, registry generation, input bytes, request parameters,
    requested outputs).  Only stateless wire requests cache: sequence,
    shared-memory, decoupled, and ensemble requests bypass it.

    Two eviction levers on top of the entry-count LRU:

    * **per-model TTL** — the model config's ``response_cache.ttl_s``
      parameter; an entry past its TTL answers as a miss and is evicted,
    * **byte budget** — ``budget_bytes`` (CLI ``--cache-budget-bytes``)
      caps the summed entry payload across models; inserts evict LRU
      entries until the total fits.

    Every eviction (LRU, budget, or TTL expiry) lands in
    ``evictions_by_model`` -> ``nv_cache_num_evictions_per_model``."""

    MAX_ENTRIES = 64
    MAX_ITEM_BYTES = 8 << 20
    # inputs above this size are not worth hashing on the event loop (the
    # key is computed inline; SHA-256 of 1 MiB is ~0.5 ms — larger requests
    # bypass the cache entirely)
    MAX_KEY_BYTES = 1 << 20

    def __init__(self, budget_bytes: Optional[int] = None) -> None:
        from collections import OrderedDict

        # key -> (frozen outputs, expires_at monotonic or None, nbytes)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._total_bytes = 0
        self.budget_bytes = budget_bytes  # None/0 = no byte budget
        self.hits = 0
        self.misses = 0
        # per-model lookup outcomes (key[0] is the model name) backing the
        # nv_cache_num_{hits,misses,evictions}_per_model metrics
        self.hits_by_model: Dict[str, int] = {}
        self.misses_by_model: Dict[str, int] = {}
        self.evictions_by_model: Dict[str, int] = {}

    @staticmethod
    def key(model: Model, generation: int, request: InferRequest,
            inputs: Dict[str, Any]) -> Optional[tuple]:
        import hashlib

        total = 0
        for v in inputs.values():
            if not isinstance(v, np.ndarray):
                return None  # device-resident input — not cacheable
            total += _ResponseCache._nbytes(v)
        if total > _ResponseCache.MAX_KEY_BYTES:
            return None
        h = hashlib.sha256()
        for name in sorted(inputs):
            v = inputs[name]
            h.update(name.encode())
            h.update(str(v.dtype).encode())
            h.update(str(v.shape).encode())
            h.update(v.tobytes() if v.dtype != object
                     else repr(v.tolist()).encode())
        h.update(repr(sorted(request.parameters.items())).encode())
        h.update(repr(sorted(
            (o.name, o.class_count) for o in request.outputs)).encode())
        # keyed on the RESOLVED instance's version, not the request's
        # (usually empty) version string: a rolling update flips which
        # instance an unversioned request reaches, and a stale entry
        # from the old version must read as a miss for the new one
        return (model.name, generation, model.served_version, h.hexdigest())

    def _evict(self, key: tuple, entry: tuple) -> None:
        self._total_bytes -= entry[2]
        self.evictions_by_model[key[0]] = \
            self.evictions_by_model.get(key[0], 0) + 1

    def get(self, key: tuple) -> Optional[Dict[str, np.ndarray]]:
        entry = self._entries.get(key)
        if entry is not None and entry[1] is not None \
                and time.monotonic() >= entry[1]:
            # past its model's TTL: evicted here (lazily, on lookup) and
            # answered as a miss so the fresh execution re-populates
            del self._entries[key]
            self._evict(key, entry)
            entry = None
        if entry is None:
            self.misses += 1
            self.misses_by_model[key[0]] = \
                self.misses_by_model.get(key[0], 0) + 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        self.hits_by_model[key[0]] = self.hits_by_model.get(key[0], 0) + 1
        return entry[0]

    @staticmethod
    def _nbytes(v: np.ndarray) -> int:
        if v.dtype != object:
            return v.nbytes
        return sum(len(x) if isinstance(x, (bytes, str)) else 64
                   for x in v.reshape(-1))

    def put(self, key: tuple, outputs: Dict[str, Any],
            ttl_s: Optional[float] = None) -> None:
        total = 0
        for v in outputs.values():
            if not isinstance(v, np.ndarray):
                return
            total += self._nbytes(v)
        if total > self.MAX_ITEM_BYTES:
            return
        if self.budget_bytes and total > self.budget_bytes:
            return  # larger than the whole budget: caching it is churn
        # freeze private copies: the cache must not mutate the caller's live
        # arrays (a model may retain/reuse its output buffer), and mutation
        # of a cached entry must raise rather than corrupt later hits
        frozen = {}
        for n, v in outputs.items():
            v = v.copy()
            v.flags.writeable = False
            frozen[n] = v
        old = self._entries.pop(key, None)
        if old is not None:
            self._total_bytes -= old[2]  # replacement, not an eviction
        expires = (time.monotonic() + ttl_s
                   if ttl_s is not None and ttl_s > 0 else None)
        self._entries[key] = (frozen, expires, total)
        self._total_bytes += total
        while len(self._entries) > self.MAX_ENTRIES or (
                self.budget_bytes
                and self._total_bytes > self.budget_bytes):
            k, entry = self._entries.popitem(last=False)
            self._evict(k, entry)

    @property
    def total_bytes(self) -> int:
        return self._total_bytes


class _DynamicBatcher:
    """Queue + bucket batcher for one model.

    Groups concurrent requests up to ``max_queue_delay_microseconds`` /
    preferred batch sizes (reference behavior contract: BASELINE config #4
    "dynamic batching"), concatenates along the batch axis, executes once
    at one of the configured buckets so XLA sees a bounded set of shapes,
    splits results.

    A batch is sized against the device's backlog, read from one
    observable: the batches of this model in flight (``_batch_tasks``),
    and with one in flight how long it still has to run.

    * **None in flight**: rows that fill a bucket exactly go at once: the
      chip has nothing to run, and a partner would only be waited for.
      Rows that fill none collect for the queue delay from the first
      member's arrival, are padded to the smallest bucket ≥ their rows
      and go.  A pad row costs the chip what a real one costs, so a model
      whose buckets start above its requests' rows still collects
      partners for it.  That is the latency path of an idle server and
      the reason padding exists.  A batch forming behind one that ends
      is under this rule from then on.
    * **One in flight**: at the window's end the batch closes at the
      bucket it fills: the longest prefix of the collected requests whose
      rows sum to a bucket exactly (else the prefix that leaves the fewest
      pad rows), and the requests after it lead the next batch
      (``_carry``).  A pad row would wait on the device behind the batch
      ahead like a real one; carried, its place goes to a request that
      arrives meanwhile (the reference's ``preferred_batch_size``:
      dispatch a preferred size from what is queued, leave the rest).
      The queue delay still decides here: closed at once, a batch behind
      one starts no sooner on the chip, and where the host needs about as
      long as a step to stand a batch on the device (``bert_large``:
      1.9 ms beside 2.0) one-row batches then run where one of two rows
      would do, and the median request rides the queue's spread (PR 41).
    * **One in flight that outlasts the host's lead**: a batch short of
      the top bucket stays open past its window, until the batch ahead is
      expected to have no more than ``lead`` left to run, and then closes
      as above.  Dispatched earlier it would wait on the device's FIFO
      until that batch ends; held here it starts on the chip at the same
      moment with more rows.  Both times are observed, neither is set:
      the batch ahead's end is its start on the device (its dispatch, or
      the end of the step before it) plus the shortest of its bucket's
      last ``_STEPS_KEPT`` service times; ``lead`` is what the host has
      lately needed between closing a batch and standing it on the device
      (``_lead_ns``).  A bucket with no step on record is not waited for:
      the window's end closes the batch, as does the batch ahead ending
      early.
    * **Two or more in flight**: a batch short of the top bucket stays
      open.  Dispatched it would wait on the device's FIFO for both; held
      here it costs its members nothing and grows.  One running and one
      behind it keep the chip fed, so the depth is the literal 2.

    A batch forms once its in-flight permit is held, so whatever arrives
    while every permit is taken joins the batch that is forming and not
    the one after.  While it is held behind two batches, or behind one
    that outlasts the lead, the forming batch leaves requests in the
    queue (still ordered by tier, still preemptible) until it can close
    with them.

    Queue items are ``(inputs, params, fut, enqueue_ns, trace,
    deadline_ns, (tenant, tier))``; an item whose deadline already passed
    is dropped at dequeue (a carried one again when its batch forms) and
    again at batch assembly — zero compute for a request whose client
    gave up while it queued.

    The queue is the QoS layer's :class:`TieredQueue`: strict-priority (or
    weighted-fair) dequeue across tiers, FIFO within one, with the
    best-effort lane preemptible under admission pressure (see
    ``InferenceCore._admit``).  A carried request never goes back through
    it: it was dequeued in order and keeps its place.
    """

    # Batches in flight concurrently: device dispatch is async, so a batch
    # queued on the device behind the running one starts the moment that
    # one ends, while the host assembles the next and splits the last.
    # Only batches that reach the top bucket go this deep (class
    # docstring).  This is the static default; the fleet controller's
    # autoscaler moves the live value per model through ``set_instances``
    # (server/fleet.py).
    MAX_INFLIGHT = 4
    # Steps the one-ahead rule reads its two times from: a bucket's
    # service time is the shortest of its last ones (one stalled step must
    # not teach the batcher to wait), the host's close-to-device time the
    # longest of the model's last ones (a step that compiled is forgotten
    # after as many).
    _STEPS_KEPT = 4
    # ``lead`` over that close-to-device time: the pump's timer, the
    # executor's pick-up and the dispatch call each run late by their own
    # length now and then.  Too long a lead closes a batch as the window's
    # end alone would; too short a one idles the chip.
    _LEAD_FACTOR = 4

    def __init__(self, core: "InferenceCore", model: Model):
        self._core = core
        self._model = model
        dbcfg = model.config.dynamic_batching
        self._max_delay_ns = dbcfg.max_queue_delay_microseconds * 1000
        self._buckets = sorted(dbcfg.preferred_batch_size) or []
        self._max_bs = model.config.max_batch_size
        # rows at which a batch is full and goes whatever is ahead
        self._cap = min(self._buckets[-1], self._max_bs) \
            if self._buckets else self._max_bs
        self._queue: TieredQueue = TieredQueue(
            core.qos.tiers, weights=core.qos.weights)
        self._task: Optional[asyncio.Task] = None
        # instance parallelism (concurrent in-flight batches): the fleet
        # controller's actuation target — a batcher born while the model
        # is scaled inherits the scaled value, not the static default
        self.instances = self.MAX_INFLIGHT
        if core.fleet is not None:
            desired = core.fleet.desired_instances(model.name)
            if desired is not None:
                self.instances = desired
        self._inflight = asyncio.Semaphore(self.instances)
        # permits swallowed (not re-released) on batch completion while a
        # scale-IN is settling: shrinking never cancels in-flight batches
        # and never touches the queue — concurrency just tapers down as
        # running batches finish
        self._shrink_debt = 0
        # batches in flight, in dispatch order: task -> (bucket, when the
        # pump dispatched it, ns)
        self._batch_tasks: Dict[asyncio.Task, Tuple[int, int]] = {}
        # what the finished steps taught (``_observe``): per bucket the
        # service times of the last steps, when the last one's outputs
        # were on the host, and the last steps' close-to-device times
        self._service_ns: Dict[int, collections.deque] = {}
        self._last_end_ns = 0
        # the formed steps whose execution has not ended, oldest first:
        # after a pause the read-backs' threads wake in any order, and a
        # step observed before one called ahead of it found no chip dry
        self._out: List[StepRecord] = []
        self._host_ns: collections.deque = collections.deque(
            maxlen=self._STEPS_KEPT)
        # what the pump holds outside the queue: the batch that is forming,
        # and the requests dequeued for it that lead the next one instead
        # (a batch closed at a bucket leaves its tail here; so does a
        # request that would overflow max_batch_size), oldest first
        self._pending: list = []
        self._carry: list = []
        # set by an arrival and by a batch finishing: the two events that
        # can change what the forming batch should do
        self._wake = asyncio.Event()
        # registry generation of the bound model; InferenceCore._batcher
        # retires this batcher when the instance behind the name is swapped
        self.generation = 0

    def set_instances(self, n: int) -> None:
        """Resize in-flight batch parallelism (event-loop only, like every
        semaphore touch).  Growth releases permits immediately; shrink
        accrues debt that completion callbacks absorb — queued work is
        never dropped and running batches are never interrupted."""
        n = max(1, int(n))
        delta = n - self.instances
        self.instances = n
        if delta > 0:
            settle = min(delta, self._shrink_debt)
            self._shrink_debt -= settle
            for _ in range(delta - settle):
                self._inflight.release()
        elif delta < 0:
            self._shrink_debt += -delta

    def start(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._run())

    def idle(self) -> bool:
        """Nothing queued, forming, carried or in flight."""
        return (self._queue.empty() and not self._batch_tasks
                and not self._pending and not self._carry)

    async def submit(self, inputs: Dict[str, np.ndarray],
                     parameters: Dict[str, Any], trace=None,
                     deadline_ns: int = 0, tenant: str = "",
                     tier: int = 0):
        fut = asyncio.get_running_loop().create_future()
        self.start()
        await self._queue.put(
            (inputs, parameters, fut, time.monotonic_ns(), trace,
             deadline_ns, (tenant, tier)), tier=tier)
        self._wake.set()
        return await fut

    def _drop_if_expired(self, item) -> bool:
        """Fail an item whose deadline passed while it queued (the v2
        "deadline exceeded" error, before any concat/pad/compute work)."""
        deadline_ns = item[5]
        if not deadline_ns or time.monotonic_ns() < deadline_ns:
            return False
        self._core.count_deadline_exceeded(self._model.name)
        fut = item[2]
        if not fut.done():
            fut.set_exception(InferError(
                f"request to model '{self._model.name}' exceeded its "
                "deadline while queued", http_status=504))
        return True

    def _bucket_for(self, rows: int) -> Optional[int]:
        """The smallest configured bucket that holds ``rows``."""
        at = bisect.bisect_left(self._buckets, rows)
        return self._buckets[at] if at < len(self._buckets) else None

    def _bucket_prefix(self, pending: list) -> Tuple[int, int]:
        """How many of ``pending``'s requests, from the front, run now while
        a batch is ahead, and their rows: the prefix that leaves the fewest
        pad rows, the longest of those (so the longest that fills a bucket
        exactly where one does).  All of them (0 rows) where the first
        request alone is past the top bucket."""
        best, best_rows, best_pad, rows = len(pending), 0, None, 0
        for k, item in enumerate(pending, 1):
            rows += _batch_count(item[0])
            bucket = self._bucket_for(rows)
            if bucket is None:
                break
            if best_pad is None or bucket - rows <= best_pad:
                best, best_rows, best_pad = k, rows, bucket - rows
        return best, best_rows

    def _observe(self, step: StepRecord) -> None:
        """What a finished step teaches the one-ahead rule, and when the
        chip ran dry before it (``InferenceCore._book`` calls this first,
        on the event loop, once the step has ended).  Its service time runs
        from when it could begin on the device (``t_called``, or the end of
        the step ahead of it where that is later) until its outputs were on
        the host; its close-to-device time from the assembly's start until
        ``model.execute`` returned.  Where the steps ahead were all on the
        host by ``t_called`` the step found the chip dry since then
        (``t_dry``): only now is that known, a step ahead that still ran at
        ``t_called`` having been observed since, or being out still
        (``_out``)."""
        ahead_end = self._last_end_ns
        if ahead_end < step.t_called and not any(
                0 < ahead.t_called < step.t_called for ahead in self._out):
            step.t_dry = ahead_end  # 0: the model's first step
        if step.ok:
            self._service_ns.setdefault(step.bucket, collections.deque(
                maxlen=self._STEPS_KEPT)).append(
                    step.t_on_host - max(step.t_called, ahead_end))
            self._host_ns.append(step.t_returned - step.t_assembly)
        self._last_end_ns = max(ahead_end, step.t_on_host or step.t_done)

    def _lead_ns(self) -> int:
        """How long before the batch ahead ends a held batch has to close
        to stand on the device by then: ``_LEAD_FACTOR`` times the longest
        close-to-device time lately seen, and never less than the queue
        delay.  A model whose ``execute`` is the step itself (host-placed:
        its batches run side by side, not in a FIFO) has a lead of four
        steps and is never held."""
        return max(self._max_delay_ns,
                   self._LEAD_FACTOR * max(self._host_ns, default=0))

    def _hold_until_ns(self) -> int:
        """With one batch in flight: when it is expected to have ``lead``
        left to run (``time.monotonic_ns()``); 0 where its bucket has no
        step on record."""
        (bucket, dispatched_ns), = self._batch_tasks.values()
        seen = self._service_ns.get(bucket)
        if not seen:
            return 0
        return (max(dispatched_ns, self._last_end_ns) + min(seen)
                - self._lead_ns())

    async def _form(self) -> Tuple[list, int, int]:
        """Collect the next batch (permit held) and close it by the class
        docstring's rules.  Returns its requests, the rows carried over to
        the next batch by a close at a bucket, and how long past its
        window's end it stayed open for the one batch ahead (ns); no
        requests when every one taken had expired and the queue is
        empty."""
        pending, carry, queue, cap = (self._pending, self._carry,
                                      self._queue, self._cap)
        total = 0
        overflowed = False
        # when the window's end alone would have closed the batch, once
        # the one batch ahead has kept it open beyond that
        held_since = 0
        while True:
            self._wake.clear()
            ahead = len(self._batch_tasks)
            now = time.monotonic_ns()
            hold_until = self._hold_until_ns() if ahead == 1 else 0
            # carried requests first (they left the queue in order); from
            # the queue everything, or while the batch is held nothing
            # until it holds enough to fill the batch (a request has a row
            # or more)
            held_back = (ahead >= 2 or now < hold_until) and \
                total + len(carry) + queue.qsize() < cap
            while total < cap and (
                    carry or not (held_back or queue.empty())):
                item = carry.pop(0) if carry else queue.get_nowait()
                if self._drop_if_expired(item):
                    continue  # expired at dequeue: zero compute
                count = _batch_count(item[0])
                if pending and total + count > self._max_bs:
                    # merging would break the max_batch_size contract
                    # (an untested shape the model was never warmed for);
                    # the request seeds the next batch instead
                    carry.insert(0, item)
                    overflowed = True
                    break
                pending.append(item)
                total += count
            if overflowed or total >= cap:
                break
            if ahead >= 2:
                await self._wake.wait()
                continue
            if not pending:
                return [], 0, 0
            window_end = pending[0][3] + self._max_delay_ns
            if not held_since and hold_until > max(window_end, now):
                held_since = max(window_end, now)
            # an idle chip takes rows that fill a bucket at once; else the
            # window's end, or the one batch ahead's hold past it
            close_at = now if not ahead and self._bucket_for(total) == total \
                else max(window_end, hold_until)
            timeout = (close_at - now) / 1e9
            if timeout <= 0:
                break
            try:
                async with asyncio.timeout(timeout):
                    await self._wake.wait()
            except TimeoutError:
                pass
        batch, carried = pending[:], 0
        if ahead and self._buckets:
            keep, rows = self._bucket_prefix(batch)
            if keep < len(batch):
                carried = total - rows
                carry[:0] = batch[keep:]
                del batch[keep:]
        pending.clear()
        held_ns = max(0, time.monotonic_ns() - held_since) \
            if held_since else 0
        return batch, carried, held_ns

    def _batch_done(self, task) -> None:
        if self._shrink_debt > 0:
            # a pending scale-in absorbs this permit instead of
            # re-releasing it — concurrency tapers to the new target as
            # batches finish
            self._shrink_debt -= 1
        else:
            self._inflight.release()
        self._batch_tasks.pop(task, None)
        self._wake.set()

    async def _run(self) -> None:
        try:
            while True:
                if not self._carry:
                    self._carry.append(await self._queue.get())
                await self._inflight.acquire()
                batch, carried, held_ns = await self._form()
                if not batch:
                    self._inflight.release()  # whatever it took had expired
                    continue
                rows = sum(_batch_count(item[0]) for item in batch)
                task = asyncio.get_running_loop().create_task(
                    self._execute_batch(batch, carried, held_ns))
                self._batch_tasks[task] = (
                    self._bucket_for(rows) or rows, time.monotonic_ns())
                task.add_done_callback(self._batch_done)
        except asyncio.CancelledError:
            # shutdown mid-batch: fail whatever we were holding
            for item in self._pending + self._carry:
                fut = item[2]
                if not fut.done():
                    fut.set_exception(InferError("server is shutting down", 503))
            self._pending.clear()
            self._carry.clear()
            raise

    async def _execute_batch(self, pending, carried: int = 0,
                             held_ns: int = 0) -> None:
        # Requests with different parameters must not share an execution —
        # the model sees one parameters dict per execute (reference dynamic
        # batching merges only parameter-compatible requests).
        groups: Dict[tuple, list] = {}
        for item in pending:
            key = tuple(sorted((k, repr(v)) for k, v in item[1].items()))
            groups.setdefault(key, []).append(item)
        # ``carried`` (rows a close at a bucket left for the next batch) and
        # ``held_ns`` (how long the batch ahead kept this one open) are
        # counted once, with the first group's execution
        await asyncio.gather(
            *(self._execute_group(g, *((0, 0) if k else (carried, held_ns)))
              for k, g in enumerate(groups.values())))

    async def _execute_group(self, pending, carried: int = 0,
                             held_ns: int = 0) -> None:
        # last deadline gate before compute: a member that expired between
        # dequeue and its batch forming must not ride the execution
        pending = [p for p in pending if not self._drop_if_expired(p)]
        if not pending:
            return
        counts = [_batch_count(p[0]) for p in pending]
        total = sum(counts)
        padded = self._bucket_for(total) or total
        model = self._model
        # a batch short of the top bucket assembled before its window's end
        # was taken by an idle chip (the class docstring's rules)
        window_end = pending[0][3] + self._max_delay_ns
        t_assembly = time.monotonic_ns()
        # queue depth: the backlog the chosen bucket geometry leaves
        # waiting while this batch forms, sampled before any concat/pad
        step = StepRecord(
            model.name, model.served_version, model.stats, "batch",
            rows=total, bucket=padded,
            members=[StepMember(count, p[6][0], p[4], p[3])
                     for p, count in zip(pending, counts)],
            carried=carried, held_ns=held_ns,
            early_ns=max(0, window_end - t_assembly)
            if total < self._cap else 0,
            queue_depth=self._queue.qsize(), batcher=self,
            t_window_end=window_end, t_assembly=t_assembly)
        self._out.append(step)
        try:
            merged = {}
            for n in pending[0][0]:
                parts = [p[0][n] for p in pending]
                arr = np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
                if padded > total:
                    pad_widths = [(0, padded - total)] + [(0, 0)] * (arr.ndim - 1)
                    arr = np.pad(arr, pad_widths)
                merged[n] = arr
            step.t_assembled = time.monotonic_ns()
            # keep_device=set(): every output resolves D2H on the executor
            # thread, not the event loop — a blocking np.asarray here would
            # stall every other request for the full device round trip.
            outputs = await self._core._run_model(
                model, merged, pending[0][1], set(), step)
            offset = 0
            for item, count in zip(pending, counts):
                fut = item[2]
                part = {
                    n: v[offset : offset + count] for n, v in outputs.items()
                }
                offset += count
                if not fut.done():
                    fut.set_result(part)
        except Exception as e:
            if not step.t_assembled:
                # the assembly itself failed (members' shapes disagree):
                # nothing ran, so _run_model booked nothing
                self._core._book(step)
            for item in pending:
                fut = item[2]
                if not fut.done():
                    fut.set_exception(e)
        finally:
            self._out.remove(step)


def _model_cache_ttl(model: Model) -> Optional[float]:
    """Per-model response-cache TTL from the config's
    ``response_cache.ttl_s`` parameter (None = entries never expire)."""
    if "response_cache.ttl_s" not in model.config.parameters:
        return None
    try:
        ttl = float(model.config.parameters[
            "response_cache.ttl_s"].string_value)
    except ValueError:
        return None
    return ttl if ttl > 0 else None


class DeviceFaultManager:
    """Device-fault accounting and the per-model quarantine state machine.

    The decode worker (and the tick-stall watchdog) report every failed
    dispatch here (``record_fault``); ``threshold`` faults inside the
    sliding ``window_s`` flip the model to *quarantined*: not-ready on
    both protocols (``InferenceCore.model_ready``), typed retryable 503
    with pushback at admission (``refusal_reason="quarantine"``, message
    carries the ``quarantined`` marker the client resilience layer
    classifies on), and a ``device_fault`` incident bundle.  Probe
    dispatches run on a doubling backoff (``maybe_probe``, driven by the
    FleetController's evaluate loop or any periodic caller): a
    registered probe callback that succeeds un-quarantines; repeated
    probe failures beyond ``escalate_after`` invoke ``escalation_cb``
    (the fleet/supervisor hook — restart the worker, scale out
    elsewhere).  Models with no registered probe release optimistically
    when their backoff expires — a persistent fault re-trips the K-in-
    window detector on the next dispatch, so flapping is bounded by the
    window, never unbounded.

    All methods are thread-safe: faults arrive from the decode worker
    thread and the watchdog, probes from their own threads, admission
    reads from the event loop.
    """

    def __init__(self, core=None, threshold: int = 3, window_s: float = 30.0,
                 probe_backoff_s: float = 1.0,
                 probe_backoff_max_s: float = 30.0,
                 escalate_after: int = 3):
        self.core = core
        self.threshold = max(1, int(threshold))
        self.window_s = float(window_s)
        self.probe_backoff_s = float(probe_backoff_s)
        self.probe_backoff_max_s = float(probe_backoff_max_s)
        self.escalate_after = max(1, int(escalate_after))
        #: fleet/supervisor escalation hook: called once per quarantine
        #: episode as ``cb(model, state_dict)`` when ``escalate_after``
        #: consecutive probes failed (we cannot restart a wedged device
        #: from inside the process — the supervisor can)
        self.escalation_cb = None
        self._lock = threading.Lock()
        # cumulative counters -> nv_device_fault_total{model,kind} /
        # nv_device_recovered_sequences_total{model}
        self._faults: Dict[Tuple[str, str], int] = {}
        self._recovered: Dict[str, int] = {}
        self._aborted: Dict[str, int] = {}
        # sliding K-in-window detector, per model
        self._recent: Dict[str, List[float]] = {}
        # model -> {"since", "reason", "backoff_s", "probe_at",
        #           "probes_failed", "escalated"}
        self._quarantined: Dict[str, Dict[str, Any]] = {}
        self._probes: Dict[str, Any] = {}
        self._probing: Set[str] = set()
        # every model that ever faulted keeps a 0/1 gauge row, so the
        # un-quarantine flip is visible on the metrics surface
        self._ever: Set[str] = set()

    # -- fault intake --------------------------------------------------

    def record_fault(self, model: str, kind: str, reason: str = "",
                     force_quarantine: bool = False) -> bool:
        """One device fault for ``model`` (``kind`` labels the metric:
        ``prefill``/``step``/``rebuild``/``tick_stall``).  Returns True
        when this fault tripped (or re-affirmed) quarantine."""
        now = time.monotonic()
        with self._lock:
            self._ever.add(model)
            key = (model, kind)
            self._faults[key] = self._faults.get(key, 0) + 1
            recent = self._recent.setdefault(model, [])
            recent.append(now)
            cutoff = now - self.window_s
            while recent and recent[0] < cutoff:
                recent.pop(0)
            trip = force_quarantine or len(recent) >= self.threshold
        if trip:
            self.quarantine(model, reason or f"{kind} fault")
        return trip

    def record_recovered(self, model: str, n: int = 1) -> None:
        """``n`` in-flight generations re-admitted bit-identically after
        a device fault (nv_device_recovered_sequences_total)."""
        with self._lock:
            self._ever.add(model)
            self._recovered[model] = self._recovered.get(model, 0) + int(n)

    def record_aborted(self, model: str, n: int = 1) -> None:
        """``n`` generations whose recovery budget ran out (they got the
        typed 500 the pre-containment worker handed everyone)."""
        with self._lock:
            self._ever.add(model)
            self._aborted[model] = self._aborted.get(model, 0) + int(n)

    # -- quarantine state machine --------------------------------------

    def quarantine(self, model: str, reason: str = "") -> None:
        """Flip ``model`` to quarantined (idempotent: a fault while
        already quarantined only refreshes the reason)."""
        now = time.monotonic()
        with self._lock:
            self._ever.add(model)
            state = self._quarantined.get(model)
            if state is not None:
                state["reason"] = reason or state["reason"]
                return
            self._quarantined[model] = {
                "since": now,
                "reason": reason,
                "backoff_s": self.probe_backoff_s,
                "probe_at": now + self.probe_backoff_s,
                "probes_failed": 0,
                "escalated": False,
            }
        core = self.core
        if core is not None:
            log_off_loop(core.log, "error",
                         f"model '{model}' quarantined: {reason}")
            # every quarantine ships a postmortem bundle: the operator
            # gets the thread dump + subsystem snapshots from the moment
            # the device went bad, not a reconstruction
            core.incidents.trigger(
                "device_fault",
                reason=f"model '{model}' quarantined: {reason}",
                context={"model": model, "reason": reason})

    def unquarantine(self, model: str) -> None:
        with self._lock:
            if self._quarantined.pop(model, None) is None:
                return
            # a fresh fault after release starts a fresh window — stale
            # pre-quarantine faults must not instantly re-trip
            self._recent.pop(model, None)
        core = self.core
        if core is not None:
            log_off_loop(core.log, "warning",
                         f"model '{model}' un-quarantined")

    def is_quarantined(self, model: str) -> bool:
        with self._lock:
            return model in self._quarantined

    def retry_in(self, model: str) -> float:
        """Pushback horizon for a quarantine refusal: the time until the
        next probe could release the model (floored at 50 ms so the
        client never busy-loops)."""
        now = time.monotonic()
        with self._lock:
            state = self._quarantined.get(model)
            if state is None:
                return 0.05
            return max(0.05, state["probe_at"] - now)

    # -- probing -------------------------------------------------------

    def register_probe(self, model: str, cb) -> None:
        """``cb() -> bool`` issues one real probe dispatch (the decode
        worker registers a tiny tick against its rebuilt cache); True
        un-quarantines."""
        with self._lock:
            self._probes[model] = cb

    def maybe_probe(self, now: Optional[float] = None) -> None:
        """Run due probes (called periodically — the FleetController's
        evaluate loop drives it when autoscaling is on; the quarantine
        drill tests call it directly).  Probes run on their own daemon
        threads: a probe IS a device dispatch and must never block the
        caller's loop."""
        now = time.monotonic() if now is None else now
        due: List[Tuple[str, Any]] = []
        with self._lock:
            for model, state in self._quarantined.items():
                if now < state["probe_at"] or model in self._probing:
                    continue
                cb = self._probes.get(model)
                if cb is None:
                    # no probe wired: optimistic timed release (see class
                    # docstring — the K-in-window detector bounds flap)
                    due.append((model, None))
                else:
                    self._probing.add(model)
                    due.append((model, cb))
        for model, cb in due:
            if cb is None:
                self.unquarantine(model)
                continue
            threading.Thread(
                target=self._run_probe, args=(model, cb),
                daemon=True, name=f"tc-tpu-fault-probe-{model}").start()

    def _run_probe(self, model: str, cb) -> None:
        try:
            ok = bool(cb())
        except Exception:  # noqa: BLE001 — a raising probe is a failed probe
            ok = False
        finally:
            with self._lock:
                self._probing.discard(model)
        self.note_probe_result(model, ok)

    def note_probe_result(self, model: str, ok: bool) -> None:
        if ok:
            self.unquarantine(model)
            return
        escalate = None
        with self._lock:
            state = self._quarantined.get(model)
            if state is None:
                return
            state["probes_failed"] += 1
            state["backoff_s"] = min(self.probe_backoff_max_s,
                                     state["backoff_s"] * 2.0)
            state["probe_at"] = time.monotonic() + state["backoff_s"]
            if (state["probes_failed"] >= self.escalate_after
                    and not state["escalated"]):
                state["escalated"] = True
                escalate = dict(state)
        if escalate is not None:
            core = self.core
            if core is not None:
                log_off_loop(
                    core.log, "error",
                    f"model '{model}' still quarantined after "
                    f"{escalate['probes_failed']} failed probes; "
                    "escalating to supervisor")
            cb = self.escalation_cb
            if cb is not None:
                try:
                    cb(model, escalate)
                except Exception:  # noqa: BLE001 — escalation must not kill probing
                    pass

    # -- surfaces ------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        now = time.monotonic()
        with self._lock:
            return {
                "faults": {f"{m}/{k}": v
                           for (m, k), v in sorted(self._faults.items())},
                "recovered": dict(self._recovered),
                "aborted": dict(self._aborted),
                "quarantined": {
                    m: {"since_s": round(now - s["since"], 3),
                        "reason": s["reason"],
                        "backoff_s": s["backoff_s"],
                        "probes_failed": s["probes_failed"],
                        "escalated": s["escalated"]}
                    for m, s in sorted(self._quarantined.items())},
            }

    def metric_rows(self) -> Dict[str, List[Tuple[Dict[str, str], float]]]:
        """Rows for metrics.collect_families — the nv_device_fault_total /
        nv_device_recovered_sequences_total / nv_device_quarantine
        families."""
        with self._lock:
            fault = [({"model": m, "kind": k}, float(v))
                     for (m, k), v in sorted(self._faults.items())]
            recovered = [({"model": m}, float(v))
                         for m, v in sorted(self._recovered.items())]
            aborted = [({"model": m}, float(v))
                       for m, v in sorted(self._aborted.items())]
            quarantine = [({"model": m},
                           1.0 if m in self._quarantined else 0.0)
                          for m in sorted(self._ever)]
        return {"device_fault": fault, "device_recovered": recovered,
                "device_aborted": aborted, "device_quarantine": quarantine}


def _batch_count(inputs: Dict[str, Any]) -> int:
    """Rows of a request or a step, off the first input's shape (read as
    an attribute: ``np.asarray`` on a device-resident input would sync it)."""
    for v in inputs.values():
        shape = np.shape(v)
        return int(shape[0]) if shape else 1
    return 1


class InferenceCore:
    SERVER_NAME = "triton_client_tpu_harness"
    SERVER_VERSION = "2.0.0-tpu"
    EXTENSIONS = [
        "classification",
        "sequence",
        "model_repository",
        "model_repository(unload_dependents)",
        "schedule_policy",
        "model_configuration",
        "system_shared_memory",
        "cuda_shared_memory",
        "xla_shared_memory",
        "binary_tensor_data",
        "statistics",
        "trace",
        "logging",
    ]

    def __init__(self, registry: ModelRegistry):
        self.registry = registry
        self.system_shm = SystemShmRegistry()
        self.xla_shm = XlaShmRegistry()
        self.trace_settings: Dict[str, List[str]] = {
            k: list(v) for k, v in TRACE_DEFAULTS.items()
        }
        self.log_settings: Dict[str, Any] = {
            "log_file": "",
            "log_info": True,
            "log_warning": True,
            "log_error": True,
            "log_verbose_level": 0,
            "log_format": "default",
        }
        self.tracer = RequestTracer(self.trace_settings)
        self.log = ServerLog(self.log_settings)
        self.tracer.log = self.log
        self._batchers: Dict[str, _DynamicBatcher] = {}
        self._inline_profiles: Dict[str, _InlineProfile] = {}
        self.response_cache = _ResponseCache()
        # server wire fast path (server/wire.py): per-(model, output-set)
        # compiled response templates, one cache per frontend protocol.
        # Keys carry the registry generation, so a reload can never stamp
        # through a stale skeleton; retire_name_caches drops entries
        # eagerly on reload/unload.
        from .wire import ResponseTemplateCache

        self.http_wire_templates = ResponseTemplateCache()
        self.grpc_wire_templates = ResponseTemplateCache()
        # always-on per-request recording + tail-latency auto-capture;
        # the tracer hands every armed context's completion to it
        self.flight_recorder = FlightRecorder()
        self.tracer.flight_recorder = self.flight_recorder
        # device/scheduler observability (server/device_stats.py): compute
        # windows (duty cycle / live MFU), XLA compile events, host<->device
        # transfers, and batcher tick profiles — the nv_tpu_* family
        self.device_stats = DeviceStatsCollector()
        # the xla-shm staging paths record their H2D/D2H DMAs into it
        self.xla_shm.device_stats = self.device_stats
        # SLO burn-rate engine: objectives from --slo / model-config
        # parameters (slo.p99_ms, slo.availability); the flight recorder
        # feeds every completed request and pins SLO-bad ones on breach
        self.slo = SloEngine()
        self.slo.resolver = self._slo_from_config
        self.flight_recorder.slo_engine = self.slo
        # per-(model, tenant) cost attribution (server/costs.py): device-
        # time slot-shares, XLA-measured FLOPs, generated tokens, KV
        # byte-seconds — the nv_cost_* families and /v2/debug/costs
        self.cost_ledger = CostLedger()
        self.live = True
        # readiness gate: /v2/health/ready (and gRPC ServerReady) report
        # not-ready until startup warmup finished and no model is mid-load
        self.startup_complete = False
        # -- resilience layer ------------------------------------------
        # admission control: False once a graceful drain began — new
        # requests are refused (503/UNAVAILABLE) while in-flight ones run
        # to completion
        self.accepting = True
        # per-model bounded queue: a model's pending requests beyond its
        # limit are shed with 429/RESOURCE_EXHAUSTED + Retry-After instead
        # of queueing unboundedly.  Resolution order: the runtime override
        # in ``queue_limits``, the model config's ``max_queue_size``
        # parameter, then this default (0 = unbounded).
        self.default_max_queue_size = 0
        self.queue_limits: Dict[str, int] = {}
        # base pushback horizon handed to shed clients (Retry-After header
        # / retry-after-ms gRPC trailing metadata); the actual horizon is
        # depth-proportional — QosManager.pushback_s scales it with the
        # shed tier's queue depth
        self.shed_retry_after_s = 0.25
        # multi-tenant QoS policy: priority tiers, per-tenant token
        # buckets, preemptible best-effort lane (server/qos.py).  The
        # default config is inert for priority-0 anonymous traffic.
        self.qos = QosManager()
        # byte-accounted memory admission (server/memory.py): queued +
        # in-flight request/response bytes per model/tenant against
        # --mem-budget-bytes, plus the HBM-headroom gate for generation
        # slot admission.  Unconfigured (budget 0) it only tracks.
        self.memory = MemoryGovernor()
        # always-on host self-observation (server/profiler.py): stack
        # sampler + event-loop lag probes + GC pause accounting — the
        # nv_host_* families and /v2/debug/profile.  Constructed inert;
        # warmup_models() starts the sampler thread.
        from .profiler import HostProfiler

        self.profiler = HostProfiler()
        # pauses as events: the flight recorder lays the kept ones beside
        # a pinned request's REQUEST span, and every one is charged to
        # the ``pause`` entry of the models it held requests of
        self.flight_recorder.pauses_between = self.profiler.pauses_between
        self._pause_charges: collections.deque = collections.deque()
        self.profiler.on_pause = self._charge_pause
        # automatic postmortems (server/incident.py): trigger-driven
        # bundle directories (profile window + thread dump + every
        # subsystem snapshot).  The flight recorder feeds its SLO pins
        # and capture storms in; chaos and the fleet watcher feed theirs.
        from .incident import IncidentRecorder

        self.incidents = IncidentRecorder(self)
        self.flight_recorder.incidents = self.incidents
        # optional fault injector (server/chaos.py; --chaos CLI flags)
        self.chaos = None
        # device-fault containment: fault accounting + per-model
        # quarantine state machine (the decode worker reports dispatch
        # faults; admission and readiness consult it; the fleet
        # controller drives its probe schedule)
        self.device_faults = DeviceFaultManager(self)
        # closed-loop fleet controller (server/fleet.py): per-model
        # instance autoscaling + rolling version updates.  None = open
        # loop (the nv_fleet_instances / serving-version gauges still
        # render from the batchers and registry directly).
        self.fleet = None
        # counters backing nv_inference_rejected_total /
        # nv_inference_deadline_exceeded_total (bumped on the event loop /
        # under the GIL, same discipline as the response-cache counters)
        self.rejected_by_model: Dict[str, int] = {}
        self.deadline_exceeded_by_model: Dict[str, int] = {}

    def _slo_from_config(self, name: str) -> Optional[SloObjective]:
        """Resolve a model's SLO from its config parameters (``slo.p99_ms``
        required, ``slo.availability`` optional, default 0.999).  None —
        no SLO, the engine ignores the model — on absence or junk; the
        ``--slo`` CLI sets explicit objectives that win over this."""
        try:
            model = self.registry.get(name)
        except InferError:
            return None
        params = model.config.parameters
        if "slo.p99_ms" not in params:
            return None
        try:
            p99_ms = float(params["slo.p99_ms"].string_value)
        except ValueError:
            return None
        if p99_ms <= 0:
            return None
        availability = 0.999
        if "slo.availability" in params:
            try:
                a = float(params["slo.availability"].string_value)
                if 0.0 < a < 1.0:
                    availability = a
            except ValueError:
                pass
        return SloObjective(p99_ms=p99_ms, availability=availability)

    def ready(self) -> bool:
        """Server-level readiness: up, past startup warmup, and no model
        currently loading/warming (Triton semantics: ready means "will
        serve an inference now", not "the frontends answered")."""
        return (self.live and self.accepting and self.startup_complete
                and not self.registry.any_loading())

    def model_ready(self, name: str, version: str = "") -> bool:
        """Model-level readiness for both protocols: registry-ready AND
        not quarantined after device faults.  Server-level ``ready()``
        stays unaffected — one bad model must not fail the whole
        replica's health check while its siblings serve."""
        return (self.registry.is_ready(name, version)
                and not self.device_faults.is_quarantined(name))

    # -- resilience ----------------------------------------------------
    def count_deadline_exceeded(self, model_name: str) -> None:
        self.deadline_exceeded_by_model[model_name] = \
            self.deadline_exceeded_by_model.get(model_name, 0) + 1

    def max_queue_size(self, model: Model) -> int:
        """The model's admission bound (0 = unbounded)."""
        limit = self.queue_limits.get(model.name)
        if limit is not None:
            return int(limit)
        if "max_queue_size" in model.config.parameters:
            try:
                return int(model.config.parameters[
                    "max_queue_size"].string_value)
            except ValueError:
                pass
        return self.default_max_queue_size

    def _count_shed(self, model: Model, tenant: str, tier: int) -> None:
        self.rejected_by_model[model.name] = \
            self.rejected_by_model.get(model.name, 0) + 1
        self.qos.count_rejected(model.name, tenant, tier)

    def _tier_depth(self, model: Model, tier: int) -> int:
        """The shed tier's backlog for pushback scaling: its batcher lane
        depth when the model batches, else the model's pending gauge."""
        b = self._batchers.get(f"{model.name}@{model.served_version}")
        if b is not None and b._queue.qsize():
            return b._queue.depth(tier)
        return model.stats.pending_count

    def _admit(self, model: Model, request: InferRequest) -> None:
        """Admission control at request entry: refuse during drain, rate-
        limit per tenant, and shed by QoS tier when the model's pending
        queue is at that tier's bound — load the server cannot serve in
        time is cheaper to reject now than to time out later (Tail at
        Scale), and under overload the best-effort lane absorbs the
        shedding so tier 0 keeps its latency.

        Tier resolution happens here (priority -> tier, tenant default)
        so every downstream consumer — batcher lanes, flight records,
        metrics labels — sees the same classification."""
        if not self.accepting:
            err = InferError("server is shutting down", http_status=503,
                             retry_after_s=self.shed_retry_after_s)
            err.refusal_reason = "drain"
            raise err
        if self.device_faults.is_quarantined(model.name):
            # typed retryable refusal with a probe-horizon pushback: the
            # 'quarantined' marker is what the client resilience layer
            # classifies on (is_quarantine_error) to retry on ANOTHER
            # replica rather than hammering this one
            err = InferError(
                f"model '{model.name}' is quarantined after repeated "
                "device faults; retry on another replica",
                http_status=503,
                retry_after_s=self.device_faults.retry_in(model.name))
            err.refusal_reason = "quarantine"
            raise err
        qos = self.qos
        request.tier = qos.tier_of(request.priority)
        if not request.tenant:
            request.tenant = DEFAULT_TENANT
        qos.count_request(request.tenant, request.tier)
        retry_in = qos.admit_tenant(request.tenant)
        if retry_in is not None:
            self._count_shed(model, request.tenant, request.tier)
            # the bucket's own horizon (1-tokens)/rate IS the pushback —
            # it says exactly when a token frees up; flooring it at the
            # queue-shed base would make fast-refilling tenants wait
            # longer than the limiter requires
            err = InferError(
                f"tenant '{request.tenant}' is over its rate limit for "
                f"model '{model.name}'; retry later",
                http_status=429, retry_after_s=retry_in)
            err.refusal_reason = "rate_limit"
            raise err
        # byte-accounted admission (server/memory.py): the arrival's wire
        # bytes must fit its tier's share of the live host budget, or it
        # sheds here — tier-aware (best effort first) and largest-first
        # (a giant bounces where a small request still fits).  Admission
        # RESERVES the bytes; every exit below that refuses the request
        # must release them (the success paths release in _infer_on /
        # infer_stream when the envelope completes).
        verdict = self.memory.try_admit(
            model.name, request.tenant, request.tier, request.wire_bytes,
            qos=qos, base_pushback_s=self.shed_retry_after_s)
        if verdict is not None:
            retry_in, permanent = verdict
            self._count_shed(model, request.tenant, request.tier)
            if permanent:
                # the payload alone exceeds this tier's configured budget
                # share — no amount of waiting admits it, so answer the
                # client's NON-retryable oversize class (413) instead of
                # inviting a doomed 429 retry loop that re-uploads the
                # giant N times
                err = InferError(
                    f"request of {request.wire_bytes} bytes to model "
                    f"'{model.name}' exceeds the tier-{request.tier} "
                    "share of the server's memory budget "
                    "(--mem-budget-bytes) and can never be admitted; "
                    "reduce the payload or use shared memory",
                    http_status=413)
            else:
                err = InferError(
                    f"request of {request.wire_bytes} bytes to model "
                    f"'{model.name}' exceeds the server's memory budget "
                    f"for tier {request.tier}; retry later",
                    http_status=429, retry_after_s=retry_in)
            err.shed_reason = "memory"
            raise err
        limit = self.max_queue_size(model)
        if limit <= 0:
            return
        if model.stats.pending_count < qos.tier_limit(request.tier, limit):
            return
        # over this tier's threshold.  A non-best-effort arrival at a FULL
        # queue (not merely its own threshold — while free slots remain,
        # shedding the arrival is cheaper than evicting admitted work) may
        # still enter by preempting the newest queued item from the LOWEST
        # lane strictly below it (best effort drains first); the victim
        # gets the same 429 + pushback a front-door shed produces, and the
        # slot transfers.
        if (request.tier < qos.best_effort_tier
                and model.stats.pending_count >= limit):
            b = self._batchers.get(f"{model.name}@{model.served_version}")
            victim = (b._queue.preempt_lower(request.tier)
                      if b is not None else None)
            if victim is not None:
                v_tenant, v_tier = victim[6]
                self._count_shed(model, v_tenant or DEFAULT_TENANT, v_tier)
                fut = victim[2]
                if not fut.done():
                    fut.set_exception(InferError(
                        f"request to model '{model.name}' preempted by "
                        f"higher-priority traffic (tier {v_tier}); retry "
                        "later", http_status=429,
                        retry_after_s=qos.pushback_s(
                            self.shed_retry_after_s,
                            self._tier_depth(model, v_tier), limit)))
                return
        # refused on queue depth AFTER the byte reservation above went
        # through — hand the bytes back before raising
        self.memory.release(model.name, request.tenant, request.wire_bytes)
        self._count_shed(model, request.tenant, request.tier)
        err = InferError(
            f"request queue for model '{model.name}' is full for tier "
            f"{request.tier} ({model.stats.pending_count} pending, tier "
            f"limit {qos.tier_limit(request.tier, limit)}); retry later",
            http_status=429,
            retry_after_s=qos.pushback_s(
                self.shed_retry_after_s,
                self._tier_depth(model, request.tier), limit))
        err.refusal_reason = "queue_full"
        raise err

    def _admit_traced(self, model: Model, request: InferRequest) -> None:
        """Admission with refusal tracing: a shed never reaches the traced
        inference path, so without this a refused request with a propagated
        ``traceparent`` would simply vanish from the journey — the client
        records a failed attempt and no server record explains why.  The
        refusal record (tracer.record_refusal) is zero-cost when tracing is
        off and carries ``shed_reason`` + the propagated trace context."""
        try:
            self._admit(model, request)
        except InferError as e:
            # refusal_reason covers every admission refusal; shed_reason
            # stays a memory-governor-only attribute (its pre-existing
            # contract: None distinguishes a queue shed from a memory shed)
            self.tracer.record_refusal(
                model.name,
                shed_reason=(getattr(e, "refusal_reason", "")
                             or getattr(e, "shed_reason", "") or ""),
                status=e.http_status,
                tenant=request.tenant,
                protocol=request.protocol,
                client_request_id=request.client_request_id,
                traceparent=request.traceparent)
            raise

    def _check_deadline(self, model: Model, request: InferRequest) -> None:
        """Drop an already-expired request before any compute (proper v2
        "deadline exceeded" error; the span tree shows no COMPUTE child)."""
        if request.expired():
            self.count_deadline_exceeded(model.name)
            raise InferError(
                f"request to model '{model.name}' exceeded its deadline "
                "before execution", http_status=504)

    async def _apply_chaos(self, model: Model, trace) -> None:
        """Run the fault injector's verdict for this request.  The flight
        record carries the chaos marker so the recorder pins injected
        faults as outliers and triton-top labels them."""
        fault = self.chaos.decide(model.name)
        if fault is None:
            return
        if trace is not None and trace.flight is not None:
            trace.flight.chaos = fault.kind
        if fault.kind == "latency":
            await asyncio.sleep(fault.latency_s)
            return
        if fault.kind == "mem_pressure":
            # budget squeeze, not a request failure: the drawing request
            # proceeds (flight-stamped chaos=mem_pressure), but the live
            # byte budget shrinks for the fault's window — arrivals behind
            # it shed tier-aware until the pressure lifts on its own
            self.memory.inject_pressure(
                fault.pressure_factor, fault.latency_s)
            # a pressure window is exactly the moment shedding decisions
            # get interesting: bundle the governor's state for postmortem
            self.incidents.trigger(
                "chaos", reason=f"mem_pressure on {model.name} "
                f"(factor={fault.pressure_factor}, "
                f"window={fault.latency_s}s)")
            return
        if fault.kind == "abort":
            from .chaos import ChaosAbort

            raise ChaosAbort()
        if fault.kind == "worker_kill":
            # process/fleet-level fault: the registered callback takes the
            # worker down (a CLI worker hard-exits; a harness drill kills
            # its replica through the replica supervisor).  When the
            # callback returns — or none is wired — the request itself
            # fails like a severed connection, the signature a crashing
            # worker actually produces on the wire.
            from .chaos import ChaosAbort

            # bundle BEFORE the callback: a CLI worker's cb is
            # os._exit(70), and a bundle thread racing process death
            # loses — the capture must at least begin with the process
            # state that is about to die (the supervisor-side
            # worker_crash trigger covers the post-restart view)
            self.incidents.trigger(
                "chaos", reason=f"worker_kill on {model.name}")
            cb = self.chaos.worker_kill_cb
            if cb is not None:
                cb()
            raise ChaosAbort("chaos: injected worker kill")
        raise InferError(f"chaos: injected {fault.status} error",
                         http_status=fault.status)

    # ------------------------------------------------------------------
    async def infer(self, request: InferRequest) -> InferResponse:
        """Single request/response inference (HTTP infer, gRPC ModelInfer)."""
        model = self.registry.get(request.model_name, request.model_version)
        if model.decoupled:
            raise InferError(
                f"doesn't support models with decoupled transaction policy",
                http_status=400,
            )
        self._admit_traced(model, request)
        return await self._infer_on(model, request)

    async def _infer_on(self, model: Model, request: InferRequest) -> InferResponse:
        model.stats.inc_pending()
        # the governor's ledger entry for this request: wire bytes were
        # reserved at _admit; response bytes join when the response is
        # built, and the whole entry releases when the envelope completes
        # (the frontend serialize path aliases the counted arrays — the
        # PR 10 zero-copy contract — rather than copying them)
        held = request.wire_bytes
        try:
            resp = await self._infer_traced_entry(model, request)
            out_bytes = sum(
                o.data.nbytes for o in resp.outputs if o.data is not None)
            if out_bytes:
                self.memory.add(model.name, request.tenant, out_bytes)
                held += out_bytes
        finally:
            model.stats.dec_pending()
            self.memory.release(model.name, request.tenant, held)
        # the frontend charges the ``request`` entry (handler entry ->
        # response built) with the rows ``ModelStats.record`` counted
        shape = request.inputs[0].shape if request.inputs else ()
        resp.stats = model.stats
        resp.rows = int(shape[0]) if len(shape) else 1
        if request.client_request_id:
            # echo the propagated correlation id so the client can join its
            # telemetry with the server trace (HTTP also echoes the header)
            resp.parameters.setdefault(
                "triton_request_id", request.client_request_id)
        return resp

    async def _infer_traced_entry(
        self, model: Model, request: InferRequest
    ) -> InferResponse:
        from .trace import reset_current_trace, set_current_trace

        trace = self._arm_trace(
            model, request, request.client_request_id,
            self.tracer.maybe_start, self.tracer.start_shadow,
            batched=model.max_batch_size > 0)
        if trace is None:
            return await self._infer_traced(model, request, None)
        trace.ts("REQUEST_START", request.arrival_ns)
        trace.ts("QUEUE_START", request.arrival_ns)
        # the root opens at the frontend's wire-receive time when stamped
        # (arrival_ns is construction time, mid-decode — the DECODE child
        # must nest inside the root envelope)
        root_start = request.arrival_ns
        if request.decode_start_ns:
            root_start = min(root_start, request.decode_start_ns)
        trace.begin_root(root_start)
        if request.decode_end_ns:
            trace.add_span("DECODE", request.decode_start_ns,
                           request.decode_end_ns)
        # visible to synchronous helpers deep in this task (shm staging
        # transfers, request-scoped log lines) without threading a parameter
        token = set_current_trace(trace)
        try:
            resp = await self._infer_traced(model, request, trace)
        except BaseException as e:
            # errors close and emit here — no response carries the handoff
            reason = getattr(e, "shed_reason", None)
            if reason and trace.flight is not None:
                # memory sheds inside the envelope (HBM gating, budget
                # pressure mid-queue) are tellable from queue-depth sheds
                trace.flight.shed_reason = reason
            trace.mark_failed(e)
            await trace.emit_async()
            raise
        finally:
            reset_current_trace(token)
        if trace.flight is not None:
            trace.flight.bytes_out = sum(
                o.data.nbytes for o in resp.outputs if o.data is not None)
        if request.trace_handoff:
            # the frontend owns finalization: it records SERIALIZE /
            # NETWORK_WRITE spans, then closes the envelope and emits
            resp.trace = trace
        else:
            await trace.emit_async()
        return resp

    async def _infer_traced(
        self, model: Model, request: InferRequest, trace
    ) -> InferResponse:
        # deadline gate at dequeue: an expired request is rejected with
        # zero compute (no COMPUTE span ever opens); chaos runs inside the
        # traced envelope so injected faults land in the flight record
        self._check_deadline(model, request)
        if self.chaos is not None:
            await self._apply_chaos(model, trace)
            # an injected latency fault may have outlived the deadline —
            # re-gate so the no-COMPUTE invariant survives chaos too (the
            # batched path re-checks on its own via _drop_if_expired)
            self._check_deadline(model, request)
        inputs = self._resolve_inputs(model, request)
        params = dict(request.parameters)
        cache_key = None
        if (model.config.HasField("response_cache")
                and model.config.response_cache.enable
                and not isinstance(model, EnsembleModel)
                and not request.sequence_id
                and not any(i.shm is not None for i in request.inputs)
                and not any(o.shm is not None for o in request.outputs)):
            cache_key = _ResponseCache.key(
                model, self.registry.generation(model.name), request, inputs)
            if cache_key is not None:
                cached = self.response_cache.get(cache_key)
                if cached is not None:
                    # cache hits still count in statistics/metrics (Triton
                    # behavior) — zero compute, real queue time
                    model.stats.record_answered(
                        _batch_count(cached) or 1,
                        time.monotonic_ns() - request.arrival_ns, 0, ok=True)
                    if trace is not None:
                        now = time.monotonic_ns()
                        trace.ts("CACHE_HIT", now)
                        trace.add_span("QUEUE", request.arrival_ns, now)
                    return self._build_response(model, request, dict(cached))
        if isinstance(model, EnsembleModel):
            t0 = time.monotonic_ns()
            queue_ns = t0 - request.arrival_ns
            if trace is not None:
                trace.ts("COMPUTE_START", t0)
                trace.add_span("QUEUE", request.arrival_ns, t0)
            try:
                outputs = await self._run_ensemble(
                    model, inputs, params,
                    tenant=request.tenant, tier=request.tier)
            except Exception:
                model.stats.record_answered(_batch_count(inputs) or 1, queue_ns, 0, ok=False)
                raise
            compute_ns = time.monotonic_ns() - t0
            if trace is not None:
                trace.ts("COMPUTE_END", t0 + compute_ns)
                trace.add_span("COMPUTE", t0, t0 + compute_ns)
            model.stats.record_answered(
                _batch_count(inputs) or 1, queue_ns, compute_ns, ok=True)
        elif self._use_batcher(model, request):
            # the batch's step record carries this request as a member:
            # _book writes its QUEUE / BATCH_ASSEMBLY / COMPUTE spans
            outputs = await self._batcher(model).submit(
                inputs, params, trace=trace,
                deadline_ns=request.deadline_ns,
                tenant=request.tenant, tier=request.tier)
        else:
            # Outputs bound to slot-backed (in-process) xla-shm regions stay
            # device-resident — zero-copy handoff into the region.  Staging
            # (cross-process) regions and wire outputs resolve D2H on the
            # worker so _build_response never touches the device.
            keep_device = {
                o.name for o in request.outputs
                if o.shm is not None
                and self.xla_shm.is_slot_backed(o.shm.region_name)
            }
            if model.device_loop and request.tenant:
                # the decode worker charges per fused tick: the tenant rides
                # the parameters copy to label this request's slot
                params["_cost_tenant"] = request.tenant
            step = self._lone_step(model, inputs, "direct", request.tenant,
                                   trace, request.arrival_ns)
            if trace is not None:
                trace.ts("COMPUTE_START", step.t_assembled)
            try:
                outputs = await self._run_model(
                    model, inputs, params, keep_device, step)
            except InferError:
                raise
            except Exception as e:
                raise InferError(f"inference failed: {e}", http_status=500)
            if trace is not None:
                trace.ts("COMPUTE_END", step.t_done)
        if cache_key is not None:
            self.response_cache.put(cache_key, dict(outputs),
                                    ttl_s=_model_cache_ttl(model))
        return self._build_response(model, request, outputs)

    def _arm_trace(self, model: Model, request: InferRequest, rid: str,
                   start, shadow, batched: bool):
        """Shared trace-arming policy for unary AND streaming envelopes:
        a sampled context from ``start``, else a shadow one from
        ``shadow`` when the flight recorder / an SLO objective needs the
        span tree anyway, else None when nothing watches.  One
        implementation so a future arming-policy change (a new pin
        trigger, a recorder gate) cannot silently diverge per path."""
        trace = start(model.name, request.model_version or "1",
                      client_request_id=rid, traceparent=request.traceparent)
        recorder = self.flight_recorder
        # SLO observation rides the flight-record pipeline: a model with
        # an objective keeps records flowing even when the recorder itself
        # is disabled (complete() then skips the ring/watchdog but still
        # feeds the burn-rate windows and pins breaches) —
        # --no-flight-recorder must not silently kill --slo
        slo_watch = (recorder.slo_engine is not None
                     and recorder.slo_engine.objective_for(model.name)
                     is not None)
        if trace is None:
            if not (recorder.enabled or slo_watch):
                return None
            # flight recorder arming: the sampler skipped this request,
            # but the watchdog needs its span tree in case it lands slow
            # (and for streams, an SLO-breaching generation must land in
            # the recorder with its full lifecycle timeline)
            trace = shadow(model.name, request.model_version or "1",
                           client_request_id=rid,
                           traceparent=request.traceparent)
        if recorder.enabled or slo_watch:
            trace.flight = recorder.start(
                model.name, model.served_version, request, batched=batched)
        return trace

    def _start_stream_trace(self, model: Model, request: InferRequest):
        """Arm the streaming trace envelope for a decoupled request.  The
        per-request id joins on ``client_request_id`` like unary infer,
        falling back to the wire ``id`` (gRPC bidi streams stamp trace
        metadata once per stream but an id per request)."""
        return self._arm_trace(
            model, request, request.client_request_id or request.id,
            self.tracer.maybe_start_stream, self.tracer.start_stream_shadow,
            batched=False)

    async def infer_stream(self, request: InferRequest) -> AsyncIterator[InferResponse]:
        """Streaming inference: decoupled models yield 0..N responses then a
        final-flagged empty response; non-decoupled models yield exactly one
        (reference decoupled semantics: IsFinalResponse/IsNullResponse,
        common.h:488-563 and enable_empty_final_response,
        grpc/_client.py:1815-1929)."""
        model = self.registry.get(request.model_name, request.model_version)
        # admission gates EVERY stream entry (decoupled or not): the gRPC
        # bidi path reaches the core only through here, and a saturated or
        # draining server must refuse streamed requests like unary ones
        self._admit_traced(model, request)
        if not model.decoupled:
            yield await self._infer_on(model, request)
            return
        # streaming trace envelope: opened here, held across the whole
        # decoupled stream, emitted ONCE at close (drain, cancel, or error)
        trace = self._start_stream_trace(model, request)
        if trace is not None:
            trace.ts("REQUEST_START", request.arrival_ns)
            trace.ts("QUEUE_START", request.arrival_ns)
            # NO wire-decode span here, deliberately: in STREAM records
            # "DECODE" is the generation stage (first token -> last token,
            # models/decode.py) — the stream frontends never stamp
            # decode_*_ns, and a frontend that grows wire-decode timing
            # must pick a different span name for it
            trace.begin_root(request.arrival_ns)
        try:
            # the resilience gates apply to decoupled streams too: an
            # expired deadline is dropped before the producer ever starts,
            # and chaos exercises the stream error path
            self._check_deadline(model, request)
            if self.chaos is not None:
                await self._apply_chaos(model, trace)
                self._check_deadline(model, request)
            # pending gauge covers in-flight streams too, so graceful
            # drain waits for them and admission sees their occupancy
            model.stats.inc_pending()
            agen = self._infer_stream_decoupled(model, request, trace)
            try:
                async for resp in agen:
                    yield resp
            finally:
                # explicit aclose: the inner generator's GeneratorExit
                # handler (consumer-disconnect accounting, producer stop)
                # must run deterministically, not at GC time
                await agen.aclose()
                model.stats.dec_pending()
        except BaseException as e:
            if trace is not None:
                if isinstance(e, (GeneratorExit, asyncio.CancelledError)):
                    # consumer-initiated close (disconnect, stop sequence
                    # satisfied): the trace record says "cancelled" with
                    # its partial timeline, but the flight/SLO outcome
                    # stays ok — the request was served as far as the
                    # client wanted, and counting walk-aways as failures
                    # would poison burn rates and fleet actions
                    trace.mark_cancelled()
                else:
                    # real errors close the envelope as FAILED — the
                    # record still emits below with its partial timeline
                    reason = getattr(e, "shed_reason", None)
                    if reason and trace.flight is not None:
                        trace.flight.shed_reason = reason
                    trace.mark_failed(e)
            raise
        finally:
            try:
                if trace is not None:
                    # synchronous emit, deliberately: ONE append per
                    # stream (not per request, so the unary path's
                    # executor hop buys nothing here), and cancel-path
                    # finalization often runs under task cancellation
                    # (consumer disconnect) where an awaited hop would
                    # itself be cancelled and lose the record.
                    # Everything from the GeneratorExit injection to this
                    # append is synchronous — a disconnect can never
                    # strand a half-finalized stream trace.  On cancel
                    # the record carries whatever the decode worker had
                    # recorded by now (partial timeline; a still-running
                    # worker may not have closed DECODE yet).
                    trace.emit()
            finally:
                # _admit reserved the request's wire bytes; a stream
                # holds them for its whole lifetime (streamed response
                # chunks are not individually accounted).  Inner finally:
                # an exception escaping emit (recorder/SLO pipeline — the
                # file append itself swallows OSError) must not leak the
                # reservation from the governor's ledger forever.
                self.memory.release(
                    model.name, request.tenant, request.wire_bytes)

    async def _infer_stream_decoupled(
        self, model: Model, request: InferRequest, trace=None
    ) -> AsyncIterator[InferResponse]:
        from .trace import reset_current_trace, set_current_trace

        inputs = self._resolve_inputs(model, request)
        params = dict(request.parameters)
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()
        _SENTINEL = object()
        consumer_gone = threading.Event()
        # a decoupled model never passes through _run_model: a device loop
        # (llama_generate -> the decode worker) gets its services here.  The
        # tenant rides the (copied) parameters dict, and the worker reports
        # the stream's device-time back through it (read below)
        self._wire(model)
        if model.device_loop and request.tenant:
            params["_cost_tenant"] = request.tenant
        # current-trace contextvar set AROUND the whole stream (and reset
        # in the finally): shm staging transfers, request-scoped server-log
        # lines, and the decode worker's lifecycle spans all key off
        # current_trace() — before this, streams always saw None there
        token = set_current_trace(trace) if trace is not None else None
        try:
            sync_gen = model.execute_decoupled(inputs, params)

            def _produce():
                try:
                    try:
                        for out in sync_gen:
                            loop.call_soon_threadsafe(queue.put_nowait, out)
                            if consumer_gone.is_set():
                                break
                    finally:
                        # close() raises GeneratorExit inside the model's
                        # generator so it can cancel device work (e.g. free a
                        # self-feeding decode slot) on consumer disconnect
                        sync_gen.close()
                except Exception as e:  # pragma: no cover - surfaced to stream
                    loop.call_soon_threadsafe(queue.put_nowait, e)
                finally:
                    loop.call_soon_threadsafe(queue.put_nowait, _SENTINEL)

            t0 = time.monotonic_ns()
            if trace is not None:
                # host-side queue stage of the stream lifecycle: wire
                # arrival until the producer (the model's generation
                # chain) starts executing
                trace.add_span("QUEUE", request.arrival_ns, t0)
            # run_in_executor does NOT propagate contextvars; copy the
            # context explicitly so current_trace() resolves inside the
            # producer thread (where the model generator actually runs)
            ctx = contextvars.copy_context()
            producer = loop.run_in_executor(None, ctx.run, _produce)
            count = 0
            try:
                while True:
                    item = await queue.get()
                    if item is _SENTINEL:
                        break
                    if isinstance(item, Exception):
                        model.stats.record_answered(1, 0, time.monotonic_ns() - t0, ok=False)
                        raise item if isinstance(item, InferError) else InferError(str(item), 500)
                    count += 1
                    resp = self._build_response(model, request, item)
                    resp.parameters["triton_final_response"] = False
                    if trace is not None:
                        # strided token timeline (FIRST_TOKEN / TOKEN[n]);
                        # the response carries the live context so the
                        # frontend can record its NETWORK_WRITE spans —
                        # emission stays owned by the stream envelope
                        trace.record_chunk()
                        resp.trace = trace
                    yield resp
            except GeneratorExit:
                # consumer closed the stream early (stop sequence, disconnect):
                # the request was served — it must not vanish from statistics
                model.stats.record_answered(1, 0, time.monotonic_ns() - t0, ok=True)
                raise
            finally:
                # reached on aclose()/GeneratorExit too: tell the producer the
                # consumer is gone so the model generator stops at its next token
                consumer_gone.set()
            await producer
            model.stats.record_answered(1, 0, time.monotonic_ns() - t0, ok=True)
        finally:
            if token is not None:
                reset_current_trace(token)
        final = InferResponse(
            model_name=model.name, model_version=model.served_version, id=request.id
        )
        final.parameters["triton_final_response"] = True
        # the generator wrote the stream's accumulated device-time back
        # into the shared params dict when it finished; surface it on the
        # final response so frontends (the OpenAI usage block) can report
        # real device microseconds without another debug round trip
        device_us = params.get("_cost_device_us")
        if device_us is not None:
            final.parameters["device_time_us"] = device_us
        # same backchannel for the prefix-cache outcome: how many prompt
        # tokens the decode worker restored from cached KV blocks instead
        # of recomputing (OpenAI usage's prompt_tokens_details.cached_tokens)
        cache_hit = params.get("_cache_hit_tokens")
        if cache_hit is not None:
            final.parameters["cache_hit_tokens"] = cache_hit
        yield final

    # ------------------------------------------------------------------
    @staticmethod
    def _model_batchable(model: Model) -> bool:
        return (
            model.max_batch_size > 0
            and model.config.HasField("dynamic_batching")
            and not model.is_sequence
        )

    def _use_batcher(self, model: Model, request: InferRequest) -> bool:
        return (
            self._model_batchable(model)
            and not request.sequence_id
            and not any(i.shm is not None for i in request.inputs)
            and not any(o.shm is not None for o in request.outputs)
        )

    async def _warmup_one(self, model: Model) -> int:
        """Run one model's configured warmup samples through the real
        execute path (off the event loop).  Warmup executions do not count
        toward inference statistics, but they do warm the XLA compile cache
        and the inline-execution profiles."""
        from .warmup import warmup_samples

        if isinstance(model, EnsembleModel):
            # ensembles are executed by the core; their members warm
            # individually
            return 0
        n = 0
        for _name, count, inputs in warmup_samples(model):
            for _ in range(count):
                rows = _batch_count(inputs) or 1
                await self._run_model(
                    model, dict(inputs), {}, set(), StepRecord(
                        model.name, model.served_version, None, "warmup",
                        rows, rows))
                n += 1
        return n

    async def warmup_models(self) -> Dict[str, int]:
        """Warm every ready model that declares ``model_warmup`` samples.

        A failing warmup unloads THAT model (Triton semantics: bad warmup
        fails the model, not the server) and reports it under
        ``"<name>:error"``; serving proceeds for everything else."""
        ran: Dict[str, Any] = {}
        for model in self.registry.all_version_models():
            if not model.config.model_warmup:
                continue
            if not self.registry.is_ready(model.name, model.served_version):
                continue  # a sibling version's failure unloaded the name
            key = (model.name if model.versions == ["1"]
                   else f"{model.name}/{model.served_version}")
            try:
                ran[key] = await self._warmup_one(model)
            except Exception as e:  # noqa: BLE001 — isolate per-model
                ran[f"{key}:error"] = str(e)
                # the startup path is where a tailing operator most needs
                # the reason a model came up absent; the append rides the
                # executor — a slow log disk must not stall the loop
                log_off_loop(
                    self.log.error,
                    f"model '{model.name}' unloaded: warmup failed: {e}")
                try:
                    self.registry.unload(model.name)
                except InferError:
                    pass
        # readiness flips only after every declared warmup ran: a probe
        # hitting /v2/health/ready during startup must not route traffic
        # at a server still paying XLA compilation
        self.startup_complete = True
        # host self-observation starts with serving, not construction:
        # unit tests building a bare core get no background threads
        self.profiler.start()
        self.incidents.start()
        return ran

    async def load_model(self, name: str, config_override=None,
                         files=None) -> None:
        """Repository-API load: registry swap off the event loop, then
        every fresh version's warmup samples (Triton runs warmup at every
        load, not just server start).  A failing warmup fails the load."""
        if self.chaos is not None:
            # control-plane fault injection (load_fail): deterministic
            # drills for the fleet layer's rollback/retry paths — a load
            # that fails before touching the registry, like a corrupt
            # artifact or an OOM'd initializer would
            self.chaos.maybe_fail_load(name)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, lambda: self.registry.load(
                name, config_override=config_override, files=files))
        self.retire_name_caches(name)
        warm = [m for m in self.registry.version_models(name)
                if m.config.model_warmup]
        if warm:
            # the name (and server readiness) reports LOADING for the
            # whole warmup window — a load is not done until the model
            # would serve its first request without compiling
            self.registry.set_state(name, "LOADING", "warming up")
            try:
                for model in warm:
                    await self._warmup_one(model)
            except Exception as e:  # noqa: BLE001 — surface as load failure
                try:
                    self.registry.unload(name)
                except InferError:
                    pass
                log_off_loop(self.log.error,
                             f"failed to load model '{name}': warmup "
                             f"failed: {e}")
                raise InferError(
                    f"failed to load '{name}': warmup failed: {e}",
                    http_status=400)
            finally:
                # NO exit path may strand the name in LOADING (a cancelled
                # handler, or the unload above racing a concurrent unload):
                # a stuck LOADING would hold the whole server not-ready
                # until restart.  The failure path's unload already moved
                # the state off LOADING; anything still LOADING here is a
                # loaded, serving-capable instance.
                if self.registry.get_state(name)[0] == "LOADING":
                    self.registry.set_state(name, "READY", "")
        log_off_loop(self.log.info, f"successfully loaded model '{name}'")

    def retire_name_caches(self, name: str) -> None:
        """Drop stale per-version batchers/inline-profiles for ``name``.

        The generation check in ``_batcher`` only runs when a key is
        re-accessed; a version dropped by a policy change on reload (or an
        unload) would otherwise keep its pump task and retired Model alive
        for the server's lifetime."""
        gen = self.registry.generation(name)
        prefix = f"{name}@"
        for key in [k for k in self._batchers if k.startswith(prefix)]:
            b = self._batchers[key]
            if b.generation != gen:
                self._batchers.pop(key)
                asyncio.ensure_future(self._retire_batcher(b))
        for key in [k for k in self._inline_profiles
                    if k.startswith(prefix)]:
            if self._inline_profiles[key].generation != gen:
                self._inline_profiles.pop(key)
        # a reloaded instance may declare different SLO parameters or
        # FLOPs; cumulative device-stat counters stay (Prometheus counters
        # must not go backwards on a reload)
        self.slo.invalidate(name)
        self.device_stats.forget_model(name)
        # compiled response templates froze the old instance's output
        # specs; the generation key already bars stale stamps — this
        # frees the entries without waiting for cap eviction
        self.http_wire_templates.retire(name)
        self.grpc_wire_templates.retire(name)

    def enable_otlp(self, endpoint: str, replica: str = "") -> None:
        """Wire an OTLP/HTTP span exporter onto the tracer (``serve
        --otlp-endpoint``): every emitted trace record — successes and
        refusals alike — is also encoded as proto-JSON ResourceSpans and
        POSTed to the collector by a background batcher that never blocks
        the serving path.  ``replica`` stamps this process's identity into
        the records first, so the collector (and the journey join) can
        tell which replica served which attempt.  The exporter shuts down
        with the tracer (core.shutdown -> tracer.shutdown)."""
        from ..otlp import OtlpExporter, encode_server_record

        if replica:
            self.tracer.replica = replica
        old, self.tracer.otlp = self.tracer.otlp, OtlpExporter(
            endpoint, "triton-tpu-server", encode_server_record,
            resource_attributes={"replica": replica} if replica else None)
        if old is not None:
            old.shutdown()

    async def shutdown(self, drain_s: float = 5.0) -> None:
        """Graceful drain, then teardown: stop accepting (new requests get
        503/UNAVAILABLE), wait up to ``drain_s`` for in-flight requests to
        finish, then cancel background batcher tasks and fail anything
        still queued so no handler is left awaiting a forever-pending
        future."""
        self.accepting = False
        if self.fleet is not None:
            # the control loop first: a scale/bake actuation mid-drain
            # would race the batcher teardown below
            await self.fleet.stop()
        deadline = time.monotonic() + max(0.0, drain_s)
        while time.monotonic() < deadline:
            in_flight = sum(m.stats.pending_count
                            for m in self.registry.all_version_models())
            if not in_flight:
                break
            await asyncio.sleep(0.02)
        self.tracer.shutdown()
        self.log.shutdown()
        # stop host observers off-loop: profiler.stop() joins its sampler
        # thread and incidents.stop() joins any in-flight bundle writer
        # (which may be mid profile-window) — neither belongs on the loop
        await asyncio.get_running_loop().run_in_executor(
            None, self._stop_observers)
        while self._batchers:
            _, b = self._batchers.popitem()
            await self._retire_batcher(b, reason="server is shutting down")

    def _stop_observers(self) -> None:
        self.profiler.stop()
        self.incidents.stop()

    def _batcher(self, model: Model) -> _DynamicBatcher:
        gen = self.registry.generation(model.name)
        key = f"{model.name}@{model.served_version}"  # versions never share
        b = self._batchers.get(key)
        if b is not None and b.generation != gen:
            # the model instance behind this name was swapped (reload /
            # config override): retire the old batcher — its queue drains
            # through the shutdown path so no request hangs — and build a
            # fresh one bound to the current instance
            self._batchers.pop(key)
            asyncio.ensure_future(self._retire_batcher(b))
            b = None
        if b is None:
            b = _DynamicBatcher(self, model)
            b.generation = gen
            self._batchers[key] = b
        return b

    async def _retire_batcher(
        self, b: _DynamicBatcher,
        reason: str = "model was reloaded while queued",
    ) -> None:
        """Cancel a batcher's pump task, let in-flight batches resolve, and
        fail anything still queued so no handler awaits forever."""
        if b._task is not None and not b._task.done():
            b._task.cancel()
            try:
                await b._task
            except (asyncio.CancelledError, Exception):
                pass
        if b._batch_tasks:
            await asyncio.gather(*list(b._batch_tasks),
                                 return_exceptions=True)
        while not b._queue.empty():
            fut = b._queue.get_nowait()[2]
            if not fut.done():
                fut.set_exception(InferError(reason, 503))

    async def drain_batcher(self, name: str, version: str,
                            timeout_s: float = 30.0) -> bool:
        """Gracefully drain ONE version's batcher: wait for its queue and
        in-flight batches to empty (queued work executes — a fleet scale
        or version-flip event must never drop admitted tier-0 requests),
        then retire the pump.  Only past ``timeout_s`` does retirement
        fail whatever is still queued (the 503 shutdown contract).
        Returns True when the drain completed cleanly."""
        key = f"{name}@{version}"
        b = self._batchers.get(key)
        if b is None:
            return True
        deadline = time.monotonic() + max(0.0, timeout_s)
        clean = True
        while not b.idle():
            if time.monotonic() >= deadline:
                clean = False
                break
            await asyncio.sleep(0.02)
        if self._batchers.get(key) is b:
            self._batchers.pop(key)
        await self._retire_batcher(
            b, reason=f"model '{name}' version {version} was drained")
        return clean

    @staticmethod
    def _host_placed(model: Model) -> bool:
        for grp in model.config.instance_group:
            return grp.kind == pb.ModelInstanceGroup.Kind.Value("KIND_CPU")
        return False

    @staticmethod
    def _lone_step(model: Model, inputs, path: str, tenant: str, trace=None,
                   enqueue_ns: int = 0) -> StepRecord:
        """The record of a step that is one request's alone: a batch of one
        member, nothing to assemble, run now."""
        rows = _batch_count(inputs) or 1
        now = time.monotonic_ns()
        return StepRecord(
            model.name, model.served_version, model.stats, path, rows, rows,
            members=(StepMember(rows, tenant, trace, enqueue_ns or now),),
            t_assembly=now, t_assembled=now)

    def _wire(self, model: Model) -> None:
        """Hand ``model`` each of the core's services it has a socket for
        (``attach_<service>``: an idempotent attribute stamp).  A device
        loop (``Model.device_loop``: the decode worker) has all five: it
        records its fused ticks, gates slot admission on HBM headroom,
        charges the ledger per tick, reports failed dispatches and consults
        the chaos injector, so it holds them before its first request."""
        for socket, service in (
                ("device_stats", self.device_stats),
                ("memory_governor", self.memory),
                ("cost_ledger", self.cost_ledger),
                ("device_faults", self.device_faults),
                ("chaos", self.chaos)):
            attach = getattr(model, "attach_" + socket, None)
            if attach is not None and service is not None:
                attach(service)

    async def _run_model(
        self, model: Model, inputs, params,
        keep_device: Optional[Set[str]], step: StepRecord,
    ) -> Dict[str, Any]:
        """Execute one step on a thread-pool worker so the event loop keeps
        serving, stamp ``step`` with what happened and when, and book it
        (``_book``): once, whether it succeeds or raises.  The caller builds
        the record and books nothing itself.

        ``keep_device`` names the outputs left device-resident (the zero-copy
        path for xla-shm-bound outputs; ``None`` keeps everything on device —
        ensemble intermediates).  All other outputs resolve D2H on the worker
        thread: ``copy_to_host_async`` prefetches every transfer so they
        overlap, then the blocking reads drain already-inflight copies.
        Nothing here may block the event loop on a device sync — one
        blocking read would hold every concurrent request behind it for
        as long as the device takes to drain.

        Exception: sub-millisecond host-placed models with pure wire IO run
        INLINE once their shape signature is warm (see ``_InlineProfile``) —
        for those the executor round trip dominates the compute."""
        loop = asyncio.get_running_loop()
        ds = self.device_stats
        self._wire(model)
        step.device_loop = model.device_loop
        prof = None
        if keep_device is not None and not keep_device \
                and self._host_placed(model):
            gen = self.registry.generation(model.name)
            prof_key = f"{model.name}@{model.served_version}"
            prof = self._inline_profiles.get(prof_key)
            if prof is None or prof.generation != gen:
                # reloaded instance: forget the old record so its first
                # execution (a potential XLA compile) never runs inline
                prof = _InlineProfile(generation=gen)
                self._inline_profiles[prof_key] = prof
        # dtype objects are hashable/comparable by equality — building
        # str(dtype) here cost ~100 us/request of pure overhead on the
        # profiled hot path (benchmarks/HOTPATH_PROFILE.md); sort by name
        # only (the other elements never tie-break)
        sig = tuple(sorted(
            ((n, getattr(v, "shape", None), getattr(v, "dtype", None))
             for n, v in inputs.items()), key=lambda t: t[0]))
        if ds.enabled:
            ds.declare_model(model.name, model.flops_per_element())
            if isinstance(model, JaxModel):
                # signature-analytic compile tracking: jax.jit compiles
                # once per input-shape signature (the invariant JaxModel
                # builds on), so a signature's first execution is the
                # jit-cache miss whose wall time paid XLA compilation.
                # Only XLA-backed models earn one — a python-backend model
                # never compiles, and fabricating misses would both invent
                # nv_tpu_compile events and drop its real compute from the
                # duty/MFU window
                step.signature = sig

        # the step's identifier on the profiler's clock: its spans share it
        step.seq = model.step_seq = model.step_seq + 1

        def _exec():
            step.t_exec = time.monotonic_ns()
            with annotation("step.dispatch", model=model.name, step=step.seq,
                            bucket=step.bucket, rows=step.rows):
                step.t_called = time.monotonic_ns()
                # a padded step's parameters say how many rows are real, for
                # a model whose host_post counts by row
                outputs = model.execute(
                    inputs, params if step.rows == step.bucket
                    else {**params, "real_batch": step.rows})
                step.t_returned = time.monotonic_ns()
            if step.signature is not None and not ds.signature_known(
                    model.name, step.signature):
                # XLA cost analysis, once per new signature: the execute
                # above warmed the jit cache, so the AOT lower+compile
                # here reuses the compilation where the backend caches it
                # and the extracted FLOPs/bytes are those of the program
                # this signature actually runs.  None (CPU stand-ins with
                # no analysis, untraceable fns) stays None — absent,
                # never fabricated.
                step.cost = model.analyze_cost(inputs, params)
            if keep_device is None:
                return outputs
            with annotation("step.device_wait", model=model.name,
                            step=step.seq, bucket=step.bucket,
                            rows=step.rows):
                drained = [n for n, v in outputs.items()
                           if n not in keep_device
                           and hasattr(v, "copy_to_host_async")]
                for n in drained:
                    outputs[n].copy_to_host_async()
                resolved = {n: (v if n in keep_device else np.asarray(v))
                            for n, v in outputs.items()}
                step.t_on_host = time.monotonic_ns()
            # the record's host points on the profiler's clock: a zero-length
            # event whose own start beside ``now`` is the clock pair that
            # maps every monotonic_ns point onto the trace's time line, the
            # batcher's awaits (which can carry no span) among them
            with annotation(
                    "step.record", model=model.name, step=step.seq,
                    bucket=step.bucket, rows=step.rows,
                    first_enqueue=step.members[0].enqueue_ns
                    if step.members else 0,
                    t_window_end=step.t_window_end,
                    t_assembly=step.t_assembly, t_assembled=step.t_assembled,
                    t_submit=step.t_submit, t_exec=step.t_exec,
                    t_called=step.t_called, t_returned=step.t_returned,
                    t_on_host=step.t_on_host, now=time.monotonic_ns()):
                pass
            step.d2h_count = len(drained)
            step.d2h_bytes = sum(resolved[n].nbytes for n in drained)
            return resolved

        def _exec_timed():
            t0 = time.perf_counter()
            try:
                return _exec()
            finally:
                # observed even on raise: a model failing slowly must
                # still demote off the event loop
                prof.observe(sig, time.perf_counter() - t0)

        try:
            if prof is not None and prof.allows(sig):
                outputs = _exec_timed()
            else:
                step.t_submit = time.monotonic_ns()
                outputs = await loop.run_in_executor(
                    None, _exec if prof is None else _exec_timed)
            step.ok = True
            return outputs
        finally:
            step.t_done = time.monotonic_ns()
            self._book(step)

    def _book(self, step: StepRecord) -> None:
        """Write one step into every book that keeps one: the batcher that
        formed it (what its one-ahead rule learns from finished steps;
        there the record's ``t_dry`` becomes known, which the statistics'
        dry-time account then splits), the members' traces (QUEUE,
        BATCH_ASSEMBLY, COMPUTE and D2H_TRANSFER spans, the ``tick`` and
        ``cost`` stamps, mirrored on their flight records), the model's
        statistics, the collector (compute window and compile event,
        read-back, the batcher's tick) and the cost ledger.  No
        other code writes an execution of ``_run_model``'s into any of
        them; a book that needs a new fact gets it from the record."""
        ran = step.t_returned > 0
        if step.batcher is not None:
            step.batcher._observe(step)
        for m in step.members:
            trace = m.trace
            if trace is None:
                continue
            trace.add_span("QUEUE", m.enqueue_ns, step.t_assembly)
            if step.formed and step.t_assembled:
                # concat + pad-to-bucket: the cost of riding a shared batch
                trace.add_span("BATCH_ASSEMBLY", step.t_assembly,
                               step.t_assembled)
            if ran:
                # every traced member of a batch carries the same window
                trace.add_span("COMPUTE", step.t_called, step.t_returned)
            if step.t_on_host:
                trace.add_span("D2H_TRANSFER", step.t_returned,
                               step.t_on_host)
        if step.stats is not None:
            step.stats.record(step)
        if not ran:
            return
        ds, cost, window_ns = self.device_stats, step.cost, step.window_ns
        if ds.enabled:
            # ``now`` is the record's own point: the duty-cycle window's
            # events keep the times they had when the worker booked them.
            # Pad rows are waste (nv_tpu_pad_waste_ratio): they count as
            # neither inferences nor MFU FLOPs
            ds.record_execute(step.model, step.rows, window_ns,
                              signature=step.signature,
                              now=step.t_returned / 1e9, cost=cost,
                              padded_batch=step.bucket)
            if cost is None and step.signature is not None:
                cost = ds.signature_cost(step.model, step.signature)
            if step.d2h_count:
                ds.record_transfer("d2h", step.d2h_bytes,
                                   count=step.d2h_count)
        flops, nbytes = (cost.flops, cost.bytes_accessed) \
            if cost is not None else (0.0, 0.0)
        tick = None
        if step.formed and step.ok and ds.enabled:
            # one tick record per batched execution: the bucket view
            # (nv_tpu_tick_* / pad-waste series, triton-top buckets)
            # is aggregated from exactly these
            ds.record_tick(
                step.model, bucket=step.bucket, batch=step.rows,
                padded=step.bucket, queue_depth=step.queue_depth,
                assembly_ns=step.assembly_ns, compute_ns=window_ns,
                requests=len(step.members), syncs=step.d2h_count,
                flops=flops, bytes_accessed=nbytes)
            # the tick shape rides the trace record and the flight record,
            # so a pinned outlier shows which bucket/occupancy it paid for
            tick = {
                "bucket": step.bucket, "batch": step.rows,
                "pad_fraction": (
                    round((step.bucket - step.rows) / step.bucket, 4)
                    if step.bucket else 0.0),
                "queue_depth": step.queue_depth,
                "assembly_us": round(step.assembly_ns / 1e3, 1),
                "requests": len(step.members),
            }
        # slot-share attribution: each member owns rows/step.rows of the
        # compute window and of the signature's measured FLOPs (a lone
        # request: all of it), so the shares sum to exactly the window the
        # collector recorded — conservation by construction.  A device
        # loop attributes per fused tick in its own worker: charging here
        # too would count its time twice.
        ledger = self.cost_ledger
        charged = ledger.enabled and not step.device_loop and step.rows > 0
        roofline = classify_roofline(flops, nbytes) if charged else None
        for m in step.members:
            stamp = None
            if charged:
                share = m.rows / step.rows
                dev_us = window_ns * share / 1e3
                ledger.charge(step.model, m.tenant, device_us=dev_us,
                              flops=flops * share)
                stamp = {"tenant": m.tenant, "device_us": round(dev_us, 1)}
                if flops:
                    stamp["flops"] = flops * share
                if roofline is not None:
                    stamp["roofline"] = roofline["verdict"]
            if m.trace is None:
                continue
            for holder in filter(None, (m.trace, m.trace.flight)):
                if tick is not None:
                    holder.tick = tick
                if stamp is not None:
                    holder.cost = stamp

    async def _run_ensemble(self, model: EnsembleModel, inputs, params,
                            tenant: str = "", tier: int = 0) -> Dict[str, Any]:
        """Execute the ensemble DAG: tensors flow between steps through
        input_map/output_map (reference ensemble behavior, §2.7).

        Steps are scheduled by data dependency, not config order: every step
        whose inputs are available runs concurrently with its siblings
        (parallel DAG branches actually parallelize).  Intermediate tensors
        stay device-resident between steps — except through dynamically
        batched members, whose merged batch resolves to host so concurrent
        requests can coalesce (cross-request batching on the device model
        outweighs the per-step host round trip under load); the ensemble's
        final outputs pay their D2H off the event loop."""
        pool: Dict[str, Any] = dict(inputs)
        remaining = list(model.config.ensemble_scheduling.step)
        while remaining:
            ready = [
                s for s in remaining
                if all(p in pool for p in s.input_map.values())
            ]
            if not ready:
                missing = sorted(
                    {p for s in remaining for p in s.input_map.values()}
                    - set(pool))
                raise InferError(
                    f"ensemble '{model.name}': tensor(s) {', '.join(missing)} "
                    "are never produced"
                )
            results = await asyncio.gather(
                *(self._run_ensemble_step(model, s, pool, params,
                                          tenant=tenant, tier=tier)
                  for s in ready))
            for step, outs in zip(ready, results):
                for member_output, pool_name in step.output_map.items():
                    if member_output not in outs:
                        raise InferError(
                            f"ensemble '{model.name}': step '{step.model_name}' "
                            f"did not produce '{member_output}'"
                        )
                    pool[pool_name] = outs[member_output]
            ready_ids = {id(s) for s in ready}
            remaining = [s for s in remaining if id(s) not in ready_ids]
        final_names = [o.name for o in model.config.output if o.name in pool]
        loop = asyncio.get_running_loop()

        def _resolve_final():
            for n in final_names:
                v = pool[n]
                if hasattr(v, "copy_to_host_async"):
                    v.copy_to_host_async()
            for n in final_names:
                pool[n] = np.asarray(pool[n])
            return pool

        return await loop.run_in_executor(None, _resolve_final)

    async def _run_ensemble_step(
        self, model: EnsembleModel, step, pool: Dict[str, Any], params,
        tenant: str = "", tier: int = 0
    ) -> Dict[str, Any]:
        member = self.registry.get(step.model_name)
        step_inputs = {
            member_input: pool[pool_name]
            for member_input, pool_name in step.input_map.items()
        }
        # Member executions from CONCURRENT ensemble requests coalesce
        # through the member's dynamic batcher (Triton semantics: ensemble
        # steps are ordinary requests to the member). Only host-resident
        # inputs qualify — the batcher merges with np.concatenate, which
        # would silently force a D2H sync on device-resident intermediates.
        use_batcher = self._model_batchable(member) and all(
            isinstance(v, np.ndarray) for v in step_inputs.values())
        if use_batcher:
            # Sequence-control params correlate the ENSEMBLE request on its
            # stream; a stateless member ignores them, and leaving them in
            # would put every sequence in its own param group, defeating
            # coalescing across concurrent streams. Strip exactly the three
            # reserved keys — user params (e.g. "sequence_length") must stay,
            # both for the member fn and for param-group isolation.
            member_params = {k: v for k, v in params.items()
                             if k not in ("sequence_id", "sequence_start",
                                          "sequence_end")}
            # the batcher records the member's stats for the merged batch;
            # the ensemble request's QoS identity rides along so member
            # work queues in the SAME tier lane the front door classified
            # (a best-effort ensemble must not jump the member's queue)
            return await self._batcher(member).submit(
                step_inputs, member_params, tenant=tenant, tier=tier)
        # keep_device=None: intermediates stay on the device between steps
        return await self._run_model(
            member, step_inputs, params, None,
            self._lone_step(member, step_inputs, "member", tenant))

    # ------------------------------------------------------------------
    def _resolve_inputs(self, model: Model, request: InferRequest) -> Dict[str, Any]:
        cfg_inputs = {i.name: i for i in model.config.input}
        batched = model.max_batch_size > 0
        resolved: Dict[str, Any] = {}
        for t in request.inputs:
            cfg = cfg_inputs.get(t.name)
            if cfg is None:
                raise InferError(
                    f"unexpected inference input '{t.name}' for model '{model.name}'"
                )
            expect_dt = pb_to_datatype(cfg.data_type)
            if t.datatype != expect_dt:
                raise InferError(
                    f"inference input '{t.name}' data-type is '{t.datatype}', but "
                    f"model '{model.name}' expects '{expect_dt}'"
                )
            self._check_shape(model, t, cfg, batched)
            if t.shm is not None:
                if t.shm.region_name in self.xla_shm.status(None):
                    arr = self.xla_shm.read(t.shm, t.datatype, t.shape)
                else:
                    arr = self.system_shm.read(t.shm, t.datatype, t.shape)
            else:
                arr = t.data
            resolved[t.name] = arr
        missing = [
            n
            for n, cfg in cfg_inputs.items()
            if n not in resolved and not cfg.optional
        ]
        if missing:
            raise InferError(
                f"expected {len(cfg_inputs)} inputs but got {len(resolved)} inputs "
                f"for model '{model.name}' (missing: {', '.join(missing)})"
            )
        # Requested-output validation happens here too so both paths share it.
        cfg_outputs = {o.name for o in model.config.output}
        for o in request.outputs:
            if o.name not in cfg_outputs:
                raise InferError(
                    f"unexpected inference output '{o.name}' for model '{model.name}'"
                )
        return resolved

    def _check_shape(self, model, t: InputTensor, cfg, batched: bool) -> None:
        dims = list(cfg.dims)
        shape = list(t.shape)
        check = shape[1:] if batched else shape
        if len(check) != len(dims):
            raise InferError(
                f"unexpected shape for input '{t.name}' for model '{model.name}': "
                f"expected rank {len(dims) + (1 if batched else 0)}, got {len(shape)}"
            )
        for got, want in zip(check, dims):
            if want != -1 and got != want:
                raise InferError(
                    f"unexpected shape for input '{t.name}' for model '{model.name}': "
                    f"expected {dims}, got {check}"
                )
        if batched and shape and shape[0] > model.max_batch_size:
            raise InferError(
                f"inference request batch-size must be <= {model.max_batch_size} "
                f"for '{model.name}'"
            )

    # ------------------------------------------------------------------
    def _build_response(
        self, model: Model, request: InferRequest, outputs: Dict[str, Any]
    ) -> InferResponse:
        requested = {o.name: o for o in request.outputs}
        resp = InferResponse(model_name=model.name, model_version=model.served_version, id=request.id)
        cfg_outputs = [o.name for o in model.config.output]
        names = list(requested) if requested else cfg_outputs
        for name in names:
            if name not in outputs:
                raise InferError(
                    f"model '{model.name}' did not produce output '{name}'"
                )
            value = outputs[name]
            spec = requested.get(name)
            if spec is not None and spec.class_count > 0:
                host = np.asarray(value)
                value = self._classify(model, name, host, spec.class_count)
            out_shm = spec.shm if spec is not None else None
            if out_shm is not None:
                # The frontend emits only shm params for these outputs — no
                # wire data, so never materialize host bytes here (for a
                # device-resident value that would be a blocking D2H on the
                # event loop, serializing every concurrent request).
                if out_shm.region_name in self.xla_shm.status(None):
                    self.xla_shm.write(out_shm, value)
                else:
                    self.system_shm.write(out_shm, np.asarray(value))
                dt = getattr(value, "dtype", None)
                if dt is None:
                    value = np.asarray(value)
                    dt = value.dtype
                resp.outputs.append(
                    OutputTensor(
                        name=name,
                        datatype=np_to_triton_dtype(np.dtype(dt)),
                        shape=tuple(value.shape),
                        data=None,
                        shm=out_shm,
                    )
                )
            else:
                host = np.asarray(value)
                resp.outputs.append(
                    OutputTensor(
                        name=name,
                        datatype=np_to_triton_dtype(host.dtype),
                        shape=tuple(host.shape),
                        data=host,
                    )
                )
        return resp

    def _classify(self, model: Model, name: str, arr: np.ndarray, k: int) -> np.ndarray:
        """Top-k classification strings "score:index[:label]" (reference
        image_client postprocess contract, image_client.py:195-217)."""
        labels = model.labels(name)
        batched = arr.ndim > 1
        rows = arr if batched else arr[None, :]
        k = min(k, rows.shape[-1])
        out = []
        for row in rows.astype(np.float32):
            idx = np.argsort(-row)[:k]
            for i in idx:
                s = f"{row[i]:f}:{i}"
                if labels and i < len(labels):
                    s += f":{labels[i]}"
                out.append(s.encode("utf-8"))
        shape = (rows.shape[0], k) if batched else (k,)
        return np.array(out, dtype=np.object_).reshape(shape)

    def qos_queue_depths(self) -> Dict[Tuple[str, int], int]:
        """Live batcher lane depths keyed ``(model, tier)`` — the
        ``nv_qos_queue_depth`` gauge.  Versions of one name sum (metrics
        are per model name, like the cache counters)."""
        out: Dict[Tuple[str, int], int] = {}
        for key, b in list(self._batchers.items()):
            name = key.rsplit("@", 1)[0]
            for tier, depth in enumerate(b._queue.depths()):
                out[(name, tier)] = out.get((name, tier), 0) + depth
        return out

    # ------------------------------------------------------------------
    def server_metadata(self) -> dict:
        return {
            "name": self.SERVER_NAME,
            "version": self.SERVER_VERSION,
            "extensions": list(self.EXTENSIONS),
        }

    def _charge_pause(self, ns: int) -> None:
        """``HostProfiler.on_pause``: charge a pause that just ended to
        every model with a request pending.  It may run inside the
        collector's hook, on a thread that holds a ``ModelStats.lock``, so
        it queues the charges and settles only those whose lock is free
        (``statistics()`` settles the rest), and does not wait for the
        registry either: a pause during a model load is charged to none."""
        for m in self.registry.all_version_models(blocking=False):
            if m.stats.pending_count > 0:
                self._pause_charges.append((m.stats, ns))
        self._settle_pause_charges(blocking=False)

    def _settle_pause_charges(self, blocking: bool) -> None:
        queue = self._pause_charges
        while True:
            try:
                stats, ns = queue.popleft()
            except IndexError:
                return
            if not stats.charge_pause(ns, blocking):
                queue.appendleft((stats, ns))
                return

    def statistics(self, name: Optional[str], version: str = "") -> List[dict]:
        self._settle_pause_charges(blocking=True)
        if name and version:
            models = [self.registry.get(name, version)]
        elif name:
            # unversioned name-scoped query reports EVERY served version
            # (Triton semantics) — not just the latest
            self.registry.get(name)  # unknown name -> 400
            models = self.registry.version_models(name)
        else:
            models = self.registry.all_version_models()
        out = []
        for m in models:
            s = m.stats
            s.settle_device_counters()
            with s.lock:
                out.append(
                    {
                        "name": m.name,
                        "version": m.served_version,
                        "last_inference": s.last_inference_ms,
                        "inference_count": s.inference_count,
                        "execution_count": s.execution_count,
                        "inference_stats": {
                            "success": {"count": s.success_count, "ns": s.success_ns},
                            "fail": {"count": s.fail_count, "ns": s.fail_ns},
                            "queue": {"count": s.queue_count, "ns": s.queue_ns},
                            "compute_input": {"count": s.infer_count, "ns": 0},
                            "compute_infer": {"count": s.infer_count, "ns": s.infer_ns},
                            "compute_output": {"count": s.infer_count, "ns": 0},
                            **s.extension_entries(),
                        },
                        "batch_stats": [],
                    }
                )
        return out
