"""In-process regression tests for the inference core's scheduling logic:
dynamic-batcher parameter grouping, parallel ensemble DAG execution with real
stats, and sequence-state idle eviction (VERDICT round-1 weak items 6/7)."""

import asyncio
import threading
import time

import numpy as np
import pytest

from triton_client_tpu.models import zoo
from triton_client_tpu.server.core import InferenceCore, InferError
from triton_client_tpu.server.model import (
    EnsembleModel,
    PyModel,
    make_config,
)
from triton_client_tpu.server.registry import ModelRegistry
from triton_client_tpu.server.types import InferRequest, InputTensor


def _run(coro):
    return asyncio.run(coro)


def _request(model, value, params=None):
    arr = np.asarray(value, dtype=np.float32)
    return InferRequest(
        model_name=model,
        inputs=[InputTensor("INPUT", "FP32", tuple(arr.shape), data=arr)],
        parameters=params or {},
    )


class TestBatcherParamGrouping:
    def _core(self):
        # A batched model whose output depends on a request parameter, so
        # merging requests across parameter values produces wrong results.
        cfg = make_config(
            "scaled",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            max_batch_size=8,
            preferred_batch_sizes=[8],
            max_queue_delay_us=20_000,
        )
        executions = []

        def fn(inputs, params):
            executions.append(dict(params))
            scale = float(params.get("scale", 1.0))
            return {"OUTPUT": inputs["INPUT"] * scale}

        registry = ModelRegistry()
        registry.register_model(PyModel(cfg, fn))
        return InferenceCore(registry), executions

    def test_differing_params_not_merged(self):
        core, executions = self._core()

        async def drive():
            reqs = [
                _request("scaled", np.ones((1, 4)), {"scale": 2.0}),
                _request("scaled", np.ones((1, 4)), {"scale": 3.0}),
                _request("scaled", np.ones((1, 4)), {"scale": 2.0}),
            ]
            resps = await asyncio.gather(*(core.infer(r) for r in reqs))
            await core.shutdown()
            return resps

        resps = _run(drive())
        got = [float(r.outputs[0].data.reshape(-1)[0]) for r in resps]
        assert got == [2.0, 3.0, 2.0]
        # each distinct parameter set got its own execution
        scales = sorted(e["scale"] for e in executions)
        assert scales == [2.0, 3.0]

    def test_same_params_do_merge(self):
        core, executions = self._core()

        async def drive():
            reqs = [_request("scaled", np.ones((1, 4)), {"scale": 5.0})
                    for _ in range(4)]
            resps = await asyncio.gather(*(core.infer(r) for r in reqs))
            await core.shutdown()
            return resps

        resps = _run(drive())
        assert all(
            float(r.outputs[0].data.reshape(-1)[0]) == 5.0 for r in resps)
        assert len(executions) < 4  # concurrent identical requests coalesced

    def test_merge_never_exceeds_max_batch_size(self):
        # Multi-row requests whose counts don't divide max_batch_size: the
        # merge loop must carry the overflowing request into the next batch,
        # never execute a shape larger than the model's contract.
        cfg = make_config(
            "capped",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            max_batch_size=8,
            max_queue_delay_us=50_000,
        )
        execute_batches = []

        def fn(inputs, params):
            execute_batches.append(inputs["INPUT"].shape[0])
            return {"OUTPUT": inputs["INPUT"] * 2.0}

        registry = ModelRegistry()
        registry.register_model(PyModel(cfg, fn))
        core = InferenceCore(registry)

        async def drive():
            reqs = [_request("capped", np.full((5, 4), float(i)))
                    for i in range(4)]
            resps = await asyncio.gather(*(core.infer(r) for r in reqs))
            await core.shutdown()
            return resps

        resps = _run(drive())
        for i, r in enumerate(resps):
            np.testing.assert_array_equal(
                r.outputs[0].data, np.full((5, 4), 2.0 * i, np.float32))
        assert sum(execute_batches) == 20
        assert max(execute_batches) <= 8, execute_batches


class _GatedRig:
    """A batched model whose every execution blocks on a gate of its own,
    so a test makes the device's backlog by hand.  Request ``v`` carries
    ``v`` in every row; ``executions`` holds the first column of what each
    execution was handed, pad rows (0.0) included, in dispatch order."""

    def __init__(self, buckets=(1, 2, 4, 8, 16, 32), max_bs=32,
                 delay_us=100_000):
        self.executions = []
        self._gates = {}
        self._all_open = False
        self._lock = threading.Lock()
        cfg = make_config(
            "gated",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            max_batch_size=max_bs,
            preferred_batch_sizes=list(buckets),
            max_queue_delay_us=delay_us,
        )
        registry = ModelRegistry()
        registry.register_model(PyModel(cfg, self._fn))
        self.core = InferenceCore(registry)

    def _gate(self, k):
        # caller holds the lock
        gate = self._gates.setdefault(k, threading.Event())
        if self._all_open:
            gate.set()
        return gate

    def _fn(self, inputs, params):
        with self._lock:
            gate = self._gate(len(self.executions))
            self.executions.append(inputs["INPUT"][:, 0].tolist())
        assert gate.wait(30)
        return {"OUTPUT": inputs["INPUT"]}

    def open(self, k=None):
        """Let execution ``k`` finish; every one, now and later, without."""
        with self._lock:
            if k is None:
                self._all_open = True
                for gate in self._gates.values():
                    gate.set()
            else:
                self._gate(k).set()

    @property
    def batcher(self):
        (b,) = self.core._batchers.values()
        return b

    def ahead(self):
        return len(self.batcher._batch_tasks) if self.core._batchers else 0

    def held(self, where):
        """Values of the requests the pump holds in ``_pending``/``_carry``."""
        return [float(item[0]["INPUT"][0, 0])
                for item in getattr(self.batcher, where)]

    def send(self, value, rows=1, priority=0, deadline_s=None):
        req = _request("gated", np.full((rows, 4), float(value)))
        req.priority = priority
        if deadline_s is not None:
            req.deadline_ns = time.monotonic_ns() + int(deadline_s * 1e9)
        return asyncio.ensure_future(self.core.infer(req))

    async def queued_together(self, *values):
        """One-row requests, every one queued before the pump may form a
        batch of any: it waits on its in-flight permits, held here until
        the last is in, so no case rests on the event loop's order."""
        b = self.core._batcher(self.core.registry.get("gated"))
        for _ in range(b.instances):
            await b._inflight.acquire()
        tasks = [self.send(v) for v in values]
        await self.until(
            lambda: b._queue.qsize() + len(b._carry) == len(values),
            "every request queued")
        for _ in range(b.instances):
            b._inflight.release()
        return tasks

    async def until(self, cond, what):
        for _ in range(3000):
            if cond():
                return
            await asyncio.sleep(0.002)
        raise AssertionError(f"never came: {what}")

    async def lead(self, n=1):
        """``n`` single-row batches in flight (values 100, 101, ...), each
        blocked on its gate.  With one ahead the second closes at bucket 1
        when its window ends."""
        tasks = []
        for k in range(n):
            tasks.append(self.send(100 + k))
            await self.until(lambda: self.ahead() == k + 1
                             and len(self.executions) == k + 1,
                             f"lead batch {k} in flight")
        return tasks

    async def finish(self, tasks):
        """Open every gate, gather the answers, stop the core."""
        self.open()
        got = await asyncio.gather(*tasks, return_exceptions=True)
        await self.core.shutdown()
        return got


def _real(execution):
    return [v for v in execution if v != 0.0]


async def _carry_21_behind_one():
    # 21 single rows behind one batch in flight: 16 run with no pad, the
    # other 5 lead the next batch in arrival order
    rig = _GatedRig()
    tasks = await rig.lead(1)
    tasks += [rig.send(v) for v in range(1, 22)]
    await rig.until(lambda: len(rig.executions) == 2, "the batch of 16")
    assert rig.executions[1] == [float(v) for v in range(1, 17)]
    await rig.until(lambda: rig.held("_pending") == [17., 18., 19., 20., 21.],
                    "the carried five lead the next batch")
    assert rig.ahead() == 2 and len(rig.executions) == 2  # and it stays open
    got = await rig.finish(tasks)
    assert not any(isinstance(g, Exception) for g in got)
    later = [v for e in rig.executions[2:] for v in _real(e)]
    assert later == [17., 18., 19., 20., 21.]


async def _idle_pads_at_the_windows_end():
    # nothing in flight: three waiting rows pad to 4 and go, as before
    rig = _GatedRig()
    rig.open()
    t0 = time.monotonic()
    got = await asyncio.gather(*await rig.queued_together(1, 2, 3))
    assert time.monotonic() - t0 >= 0.1  # the queue delay, from arrival
    await rig.core.shutdown()
    assert rig.executions == [[1., 2., 3., 0.]]
    assert [float(r.outputs[0].data[0, 0]) for r in got] == [1., 2., 3.]


async def _idle_row_that_fills_a_bucket_goes_at_once():
    # nothing in flight and buckets from 1: a lone row is a full bucket, and
    # waiting for a partner would only leave the chip idle
    rig = _GatedRig(delay_us=1_000_000)
    rig.open()
    t0 = time.monotonic()
    got = await rig.send(1)
    assert time.monotonic() - t0 < 0.25
    await rig.core.shutdown()
    assert rig.executions == [[1.]]
    assert got.outputs[0].data.shape == (1, 4)


async def _idle_row_under_the_first_bucket_waits_its_window():
    # buckets from 8: a pad row costs what a real one does, so a lone row
    # at an idle chip still collects its partners for the queue delay
    rig = _GatedRig(buckets=(8, 16, 32, 64), max_bs=64)
    rig.open()
    t0 = time.monotonic()
    await rig.send(1)
    assert time.monotonic() - t0 >= 0.1
    await rig.core.shutdown()
    assert rig.executions == [[1.] + [0.] * 7]


async def _batch_early_counts():
    # at an idle chip a lone row closes early (about its whole window not
    # waited); three rows at once fill no bucket and wait theirs; a [32]
    # request goes at once as the top bucket, which is no early close
    rig = _GatedRig(delay_us=500_000)
    rig.open()
    await rig.send(1)
    await asyncio.gather(*await rig.queued_together(1, 2, 3))
    await rig.send(7, rows=32)
    await rig.core.shutdown()
    assert [len(e) for e in rig.executions] == [1, 4, 32]
    (row,) = rig.core.statistics("gated")
    early = row["inference_stats"]["batch_early"]
    assert early["count"] == 1 and 400_000_000 <= early["ns"] <= 500_000_000
    assert row["inference_stats"]["batch_hold"] == {"count": 0, "ns": 0}


async def _no_bucket_small_enough_still_pads():
    # buckets start at 8: five rows under a backlog pad to 8, none carried
    rig = _GatedRig(buckets=(8, 16, 32, 64), max_bs=64)
    tasks = await rig.lead(1)
    tasks += [rig.send(v) for v in range(1, 6)]
    await rig.until(lambda: len(rig.executions) == 2, "the batch of five")
    assert rig.executions[1] == [1., 2., 3., 4., 5., 0., 0., 0.]
    assert not rig.batcher._carry and not rig.batcher._pending
    await rig.finish(tasks)


async def _three_row_requests_fewest_pad_rows():
    # no prefix of 3-row requests lands on a bucket: 15 of 18 rows pad to
    # 16 (one pad row; 18 would pad to 32), the sixth request is carried
    rig = _GatedRig()
    tasks = await rig.lead(1)
    tasks += [rig.send(v, rows=3) for v in range(1, 7)]
    await rig.until(lambda: len(rig.executions) == 2, "the batch of 15")
    assert rig.executions[1] == [float(v) for v in range(1, 6)
                                 for _ in range(3)] + [0.]
    await rig.until(lambda: rig.held("_pending") == [6.], "the sixth held")
    got = await rig.finish(tasks)
    assert got[-1].outputs[0].data.shape == (3, 4)


async def _top_bucket_request_passes_whole():
    # a client-made [32] batch is the top bucket: it goes at once, whole,
    # even behind two batches
    rig = _GatedRig(delay_us=20_000)
    tasks = await rig.lead(2)
    tasks.append(rig.send(7, rows=32))
    await rig.until(lambda: len(rig.executions) == 3, "the [32] request")
    assert rig.executions[2] == [7.] * 32 and rig.ahead() == 3
    await rig.finish(tasks)


async def _two_ahead_stays_open():
    # behind two batches an under-full batch waits and grows; with one
    # ahead it closes at the bucket it fills and carries the rest
    rig = _GatedRig(delay_us=20_000)
    tasks = await rig.lead(2)
    tasks += [rig.send(v) for v in (1, 2, 3)]
    await asyncio.sleep(0.1)  # five queue delays
    assert len(rig.executions) == 2 and rig.ahead() == 2
    rig.open(0)
    await rig.until(lambda: len(rig.executions) == 3, "the batch of two")
    assert rig.executions[2] == [1., 2.]
    await rig.until(lambda: rig.held("_pending") == [3.], "the third held")
    rig.open(1)
    await rig.until(lambda: len(rig.executions) == 4, "the third runs")
    assert rig.executions[3] == [3.]
    await rig.finish(tasks)


async def _carried_request_expires_with_zero_compute():
    rig = _GatedRig(delay_us=20_000)
    tasks = await rig.lead(1)
    tasks += [rig.send(v) for v in (1, 2, 3, 4)]
    tasks.append(rig.send(5, deadline_s=0.25))
    await rig.until(lambda: len(rig.executions) == 2, "the batch of four")
    assert rig.executions[1] == [1., 2., 3., 4.]
    await rig.until(lambda: rig.held("_pending") == [5.], "the fifth held")
    await asyncio.sleep(0.3)
    got = await rig.finish(tasks)
    assert isinstance(got[-1], InferError) and got[-1].http_status == 504
    assert not any(isinstance(g, Exception) for g in got[:-1])
    assert all(5. not in e for e in rig.executions)
    assert rig.core.deadline_exceeded_by_model == {"gated": 1}


async def _carried_request_at_shutdown_gets_503():
    rig = _GatedRig(delay_us=20_000)
    tasks = await rig.lead(1)
    tasks += [rig.send(v) for v in (1, 2, 3, 4, 5)]
    await rig.until(lambda: len(rig.executions) == 2
                    and rig.held("_pending") == [5.], "the fifth held")
    down = asyncio.ensure_future(rig.core.shutdown(drain_s=0.05))
    got = await asyncio.wait_for(asyncio.gather(
        tasks[-1], return_exceptions=True), 10)
    assert isinstance(got[0], InferError) and got[0].http_status == 503
    assert len(rig.executions) == 2  # the batches in flight still run
    rig.open()
    await down
    got = await asyncio.gather(*tasks[:-1])
    assert len(got) == 5


async def _tiers_keep_priority_across_a_carry():
    # held behind two batches, arrivals stay in the tiered queue: tier 0
    # leaves it first.  The carried tail keeps its place: a later tier-0
    # arrival does not pass it, since nothing goes back through the queue
    rig = _GatedRig(delay_us=20_000)
    tasks = await rig.lead(2)
    tasks.append(rig.send(1, priority=1))
    await rig.until(lambda: rig.held("_pending") == [1.], "the first taken")
    tasks += [rig.send(v, priority=1) for v in (2, 3)]
    tasks += [rig.send(v, priority=0) for v in (4, 5)]
    await rig.until(lambda: rig.batcher._queue.depths()[:2] == [2, 2],
                    "four left in the queue")
    rig.open(0)
    await rig.until(lambda: len(rig.executions) == 3, "the batch of four")
    assert rig.executions[2] == [1., 4., 5., 2.]
    await rig.until(lambda: rig.held("_pending") == [3.], "the tail held")
    tasks.append(rig.send(6, priority=0))
    await rig.until(lambda: rig.batcher._queue.qsize() == 1, "6 queued")
    rig.open(1)
    await rig.until(lambda: len(rig.executions) == 4, "the next batch")
    assert rig.executions[3] == [3., 6.]
    await rig.finish(tasks)


async def _batch_carry_counts():
    rig = _GatedRig(delay_us=50_000)
    tasks = await rig.lead(1)
    tasks += [rig.send(v) for v in range(1, 22)]
    await rig.until(lambda: len(rig.executions) == 2, "16 run, 5 carried")
    rig.open(0)
    await rig.until(lambda: len(rig.executions) == 3, "4 run, 1 carried")
    assert rig.executions[2] == [17., 18., 19., 20.]
    rig.open(1)
    await rig.until(lambda: len(rig.executions) == 4, "the last one")
    assert rig.executions[3] == [21.]
    await rig.finish(tasks)
    (row,) = rig.core.statistics("gated")
    ext = row["inference_stats"]
    assert ext["batch_carry"] == {"count": 2, "ns": 0}
    assert ext["batch_carry_rows"] == {"count": 5 + 1, "ns": 0}
    assert row["inference_count"] == 22 and row["execution_count"] == 4
    assert ext["bucket_rows"]["count"] == 22  # not one pad row


class _OnDevice:
    """An output still on ``_DeviceRig``'s device: reading it waits for the
    end of its step, as ``np.asarray`` of a dispatched ``jax.Array`` does."""

    def __init__(self, array, end):
        self._array, self._end = array, end

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self._end - time.monotonic()))
        return self._array


class _DeviceRig(_GatedRig):
    """The same model on a device of its own: ``execute`` returns after
    ``dispatch_s``, the device runs one step at a time in dispatch order,
    each for ``step_s(rows)`` seconds, and the outputs are on the host when
    their step ends.  Executions finish, so a bucket gets a service time on
    record; ``began`` and ``ends`` hold each execution's dispatch and its
    end on the device (``time.monotonic()``)."""

    def __init__(self, step_s, dispatch_s=0.0, buckets=(4, 8, 16), max_bs=16,
                 delay_us=20_000):
        super().__init__(buckets=buckets, max_bs=max_bs, delay_us=delay_us)
        self._step_s, self._dispatch_s = step_s, dispatch_s
        self._free_at = 0.0
        self.began, self.ends = [], []

    def _fn(self, inputs, params):
        now = time.monotonic()
        with self._lock:
            self.executions.append(inputs["INPUT"][:, 0].tolist())
            end = max(now, self._free_at) + self._step_s(len(inputs["INPUT"]))
            self._free_at = end
            self.began.append(now)
            self.ends.append(end)
        time.sleep(self._dispatch_s)
        return {"OUTPUT": _OnDevice(inputs["INPUT"], end)}

    async def teach(self, *rows):
        """One client-made batch of each size, alone: its bucket's service
        time is on record afterwards."""
        for n in rows:
            await self.send(99, rows=n)

    async def one_ahead(self, rows=8):
        """A batch of ``rows`` in flight (value 100); its index."""
        k = len(self.executions)
        task = self.send(100, rows=rows)
        await self.until(lambda: len(self.executions) == k + 1
                         and self.ahead() == 1, "the batch ahead in flight")
        return k, task

    def hold_counter(self):
        (row,) = self.core.statistics("gated")
        return row["inference_stats"]["batch_hold"]

    def early_counter(self):
        (row,) = self.core.statistics("gated")
        return row["inference_stats"]["batch_early"]


def _long(rows):
    return 0.6


async def _one_ahead_with_long_to_run_waits_for_the_fill():
    # 8 wait behind a batch with 0.6 s to run: they stay open past their
    # window (five of them) and go as 16 the moment the other 8 arrive,
    # while that batch is still running
    rig = _DeviceRig(_long)
    await rig.teach(8)
    k, ahead = await rig.one_ahead(8)
    tasks = [ahead] + [rig.send(v) for v in range(1, 9)]
    await asyncio.sleep(0.1)
    assert len(rig.executions) == k + 1 and rig.ahead() == 1
    tasks += [rig.send(v) for v in range(9, 17)]
    await rig.until(lambda: len(rig.executions) == k + 2, "the batch of 16")
    assert rig.executions[k + 1] == [float(v) for v in range(1, 17)]
    assert rig.began[k + 1] < rig.ends[k]
    got = await rig.finish(tasks)
    assert not any(isinstance(g, Exception) for g in got)


async def _the_fill_never_comes_closes_before_the_batch_ahead_ends():
    # six wait and no more come: they close at the bucket they fill before
    # the batch ahead ends, not after, and carry the rest
    rig = _DeviceRig(_long)
    await rig.teach(8)
    k, ahead = await rig.one_ahead(8)
    tasks = [ahead] + [rig.send(v) for v in range(1, 7)]
    await rig.until(lambda: len(rig.executions) == k + 2, "the batch of four")
    assert rig.executions[k + 1] == [1., 2., 3., 4.]
    assert rig.began[k + 1] < rig.ends[k]
    await rig.until(lambda: rig.held("_pending") == [5., 6.], "two carried")
    got = await rig.finish(tasks)
    assert not any(isinstance(g, Exception) for g in got)
    assert rig.hold_counter()["count"] == 1


async def _bucket_never_seen_closes_at_the_windows_end():
    # nothing on record for the bucket ahead: today's rule
    rig = _DeviceRig(_long)
    await rig.teach(4)
    k, ahead = await rig.one_ahead(8)
    tasks = [ahead] + [rig.send(v) for v in (1, 2, 3)]
    await rig.until(lambda: len(rig.executions) == k + 2, "the batch of three")
    assert rig.executions[k + 1] == [1., 2., 3., 0.]
    assert rig.began[k + 1] < rig.ends[k]
    await rig.finish(tasks)
    assert rig.hold_counter() == {"count": 0, "ns": 0}


async def _steps_shorter_than_the_lead_close_at_the_windows_end():
    # the host takes 40 ms to stand a batch on the device, so the lead is
    # four times that: a step of 120 ms is not waited for, and the window
    # of 400 ms still closes the batch behind it
    rig = _DeviceRig(lambda rows: 0.12, dispatch_s=0.04, delay_us=400_000)
    await rig.teach(8)
    k, ahead = await rig.one_ahead(8)
    t0 = time.monotonic()
    tasks = [ahead] + [rig.send(v) for v in (1, 2, 3)]
    await rig.until(lambda: len(rig.executions) == k + 2, "the batch of three")
    assert rig.executions[k + 1] == [1., 2., 3., 0.]
    # the batch ahead ended inside the window: from then on the chip is
    # idle, and three rows fill no bucket, so they wait it out
    assert rig.began[k + 1] - t0 >= 0.35
    await rig.finish(tasks)
    assert rig.hold_counter() == {"count": 0, "ns": 0}
    # only the two batches of 8 that filled a bucket at an idle chip
    assert rig.early_counter()["count"] == 2


async def _host_model_behind_one_closes_at_the_windows_end():
    # ``execute`` is the whole step (a host-placed model), so the lead is
    # four steps and the batch ahead is never waited for past the window:
    # three rows behind it close at their window's end, while it still runs
    rig = _DeviceRig(lambda rows: 0.3, dispatch_s=0.3, delay_us=100_000)
    await rig.teach(8)
    k, ahead = await rig.one_ahead(8)
    t0 = time.monotonic()
    tasks = [ahead] + [rig.send(v) for v in (1, 2, 3)]
    await rig.until(lambda: len(rig.executions) == k + 2, "the batch of three")
    assert rig.executions[k + 1] == [1., 2., 3., 0.]
    assert 0.09 <= rig.began[k + 1] - t0 < 0.25
    await rig.finish(tasks)
    assert rig.hold_counter() == {"count": 0, "ns": 0}


async def _held_behind_one_stays_in_the_tiered_queue():
    # held behind one batch, arrivals stay in the queue: tier 0 leaves it
    # first, and a deadline that passes there is a 504 with zero compute
    rig = _DeviceRig(_long)
    await rig.teach(8)
    k, ahead = await rig.one_ahead(8)
    tasks = [ahead, rig.send(1, priority=1)]
    await rig.until(lambda: rig.held("_pending") == [1.], "the first taken")
    tasks.append(rig.send(2, priority=1))
    tasks.append(rig.send(3, priority=1, deadline_s=0.15))
    tasks += [rig.send(v, priority=0) for v in (4, 5)]
    await rig.until(lambda: rig.batcher._queue.depths()[:2] == [2, 2],
                    "four left in the queue")
    await rig.until(lambda: len(rig.executions) == k + 2, "the batch of four")
    assert rig.executions[k + 1] == [1., 4., 5., 2.]
    assert rig.began[k + 1] < rig.ends[k]
    got = await rig.finish(tasks)
    assert isinstance(got[3], InferError) and got[3].http_status == 504
    assert not any(isinstance(g, Exception) for g in got[:3] + got[4:])
    assert all(3. not in e for e in rig.executions)
    assert rig.core.deadline_exceeded_by_model == {"gated": 1}


async def _held_behind_one_at_shutdown_gets_503():
    rig = _DeviceRig(_long)
    await rig.teach(8)
    k, ahead = await rig.one_ahead(8)
    tasks = [rig.send(v) for v in (1, 2, 3)]
    await asyncio.sleep(0.1)
    assert rig.held("_pending") == [1.] and rig.batcher._queue.qsize() == 2
    down = asyncio.ensure_future(rig.core.shutdown(drain_s=0.05))
    got = await asyncio.wait_for(asyncio.gather(
        *tasks, return_exceptions=True), 10)
    assert [g.http_status for g in got] == [503] * 3
    await down
    assert (await ahead).outputs[0].data.shape == (8, 4)
    assert len(rig.executions) == k + 1  # the batch in flight still ran


async def _batch_hold_counts():
    # one close waited for the batch ahead (a tenth of a second and more
    # past its window), the others did not
    rig = _DeviceRig(_long)
    await rig.teach(8)
    assert rig.hold_counter() == {"count": 0, "ns": 0}
    k, ahead = await rig.one_ahead(8)
    tasks = [ahead] + [rig.send(v) for v in range(1, 9)]
    await asyncio.sleep(0.12)
    tasks += [rig.send(v) for v in range(9, 17)]
    await rig.finish(tasks)
    (row,) = rig.core.statistics("gated")
    hold = row["inference_stats"]["batch_hold"]
    assert hold["count"] == 1 and hold["ns"] >= 100_000_000
    assert row["execution_count"] == 3 and row["inference_count"] == 32


async def _closed_loop_of_32_leaves_16_8_8():
    # 32 callers over a top bucket of 16, a step affine in its bucket at
    # sdar_30b_a3b.blockgen's ratio (412.8 : 624 ms), an answer 5 ms on the
    # wire: started as 16, 8, 8 (how the window's end alone keeps it), the
    # loop has only full batches once both buckets have a step on record
    rig = _DeviceRig(lambda rows: 0.082 if rows <= 8 else 0.124,
                     buckets=(8, 16), delay_us=5_000)
    stop = False

    async def caller(v):
        while not stop:
            await rig.core.infer(_request("gated", np.full((1, 4), float(v))))
            await asyncio.sleep(0.005)

    callers = [asyncio.ensure_future(caller(v)) for v in range(1, 17)]
    await rig.until(lambda: len(rig.executions) == 1, "the first 16")
    callers += [asyncio.ensure_future(caller(v)) for v in range(17, 25)]
    await rig.until(lambda: len(rig.executions) == 2, "8 behind them")
    callers += [asyncio.ensure_future(caller(v)) for v in range(25, 33)]
    await rig.until(lambda: len(rig.executions) >= 11, "eleven executions")
    ran = [len(_real(e)) for e in rig.executions[:11]]
    stop = True
    await asyncio.gather(*callers)
    await rig.core.shutdown()
    assert ran[:3] == [16, 8, 8] and ran[3:] == [16] * 8, ran


class TestBatcherBacklog:
    """At an idle chip a batch goes as soon as its rows fill a bucket, and
    is padded only where they fill none by its window's end; with a batch
    ahead it closes at its window's end, or past it while that batch
    outlasts the host's lead, at the bucket it fills, and carries the rest;
    with two ahead it stays open (``_DynamicBatcher``'s docstring)."""

    @pytest.mark.parametrize("scenario", [
        _carry_21_behind_one,
        _idle_pads_at_the_windows_end,
        _idle_row_that_fills_a_bucket_goes_at_once,
        _idle_row_under_the_first_bucket_waits_its_window,
        _batch_early_counts,
        _no_bucket_small_enough_still_pads,
        _three_row_requests_fewest_pad_rows,
        _top_bucket_request_passes_whole,
        _two_ahead_stays_open,
        _carried_request_expires_with_zero_compute,
        _carried_request_at_shutdown_gets_503,
        _tiers_keep_priority_across_a_carry,
        _batch_carry_counts,
        _one_ahead_with_long_to_run_waits_for_the_fill,
        _the_fill_never_comes_closes_before_the_batch_ahead_ends,
        _bucket_never_seen_closes_at_the_windows_end,
        _steps_shorter_than_the_lead_close_at_the_windows_end,
        _host_model_behind_one_closes_at_the_windows_end,
        _held_behind_one_stays_in_the_tiered_queue,
        _held_behind_one_at_shutdown_gets_503,
        _batch_hold_counts,
        _closed_loop_of_32_leaves_16_8_8,
    ], ids=lambda f: f.__name__.lstrip("_"))
    def test_batch_is_sized_against_the_backlog(self, scenario):
        _run(scenario())


class TestEnsembleDag:
    def _core(self, sleep_s=0.15):
        registry = ModelRegistry()
        calls = {}

        def make_branch(name):
            cfg = make_config(
                name,
                inputs=[("INPUT", "FP32", [4])],
                outputs=[("OUTPUT", "FP32", [4])],
            )

            def fn(inputs, params):
                calls[name] = time.monotonic()
                time.sleep(sleep_s)
                return {"OUTPUT": inputs["INPUT"] + 1.0}

            return PyModel(cfg, fn)

        registry.register_model(make_branch("branch_a"))
        registry.register_model(make_branch("branch_b"))

        join_cfg = make_config(
            "join",
            inputs=[("A", "FP32", [4]), ("B", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
        )
        registry.register_model(
            PyModel(join_cfg, lambda inputs, params: {
                "OUTPUT": inputs["A"] + inputs["B"]}))

        ens_cfg = make_config(
            "fanout_ensemble",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            platform="ensemble",
            backend="",
        )
        # deliberately list the join FIRST: scheduling must follow data
        # dependencies, not config order
        s = ens_cfg.ensemble_scheduling.step.add()
        s.model_name = "join"
        s.input_map["A"] = "a_out"
        s.input_map["B"] = "b_out"
        s.output_map["OUTPUT"] = "OUTPUT"
        for name, out in (("branch_a", "a_out"), ("branch_b", "b_out")):
            s = ens_cfg.ensemble_scheduling.step.add()
            s.model_name = name
            s.input_map["INPUT"] = "INPUT"
            s.output_map["OUTPUT"] = out
        registry.register_model(EnsembleModel(ens_cfg))
        return InferenceCore(registry), calls, registry

    def test_parallel_branches_and_dependency_order(self):
        core, calls, _ = self._core()
        resp = _run(core.infer(_request("fanout_ensemble", np.ones(4))))
        np.testing.assert_array_equal(
            resp.outputs[0].data, np.full(4, 4.0, np.float32))
        # the two independent branches started concurrently, not serially
        assert abs(calls["branch_a"] - calls["branch_b"]) < 0.1

    def test_ensemble_stats_are_real(self):
        core, _, registry = self._core()
        _run(core.infer(_request("fanout_ensemble", np.ones(4))))
        stats = registry.get("fanout_ensemble").stats
        assert stats.execution_count == 1
        assert stats.infer_ns > 0  # compute time recorded, not fabricated 0
        member = registry.get("branch_a").stats
        assert member.infer_ns > 0

    def test_member_steps_coalesce_through_dynamic_batcher(self):
        # Concurrent ensemble requests must batch their member executions
        # (Triton semantics: a step is an ordinary request to the member) —
        # even when each ensemble request carries a distinct sequence id
        # from a generation stream, since the member itself is stateless.
        registry = ModelRegistry()
        execute_batches = []
        cfg = make_config(
            "batched_member",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            max_batch_size=8,
            max_queue_delay_us=50_000,
        )

        def fn(inputs, params):
            x = np.asarray(inputs["INPUT"])
            execute_batches.append(x.shape[0])
            return {"OUTPUT": (x * 2).astype(np.float32)}

        registry.register_model(PyModel(cfg, fn))
        ens_cfg = make_config(
            "member_ens",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            max_batch_size=8,
            platform="ensemble",
            backend="",
        )
        s = ens_cfg.ensemble_scheduling.step.add()
        s.model_name = "batched_member"
        s.input_map["INPUT"] = "INPUT"
        s.output_map["OUTPUT"] = "OUTPUT"
        registry.register_model(EnsembleModel(ens_cfg))
        core = InferenceCore(registry)

        async def drive():
            reqs = []
            for i in range(8):
                arr = np.full((1, 4), float(i), np.float32)
                req = InferRequest(
                    model_name="member_ens",
                    inputs=[InputTensor("INPUT", "FP32", arr.shape, data=arr)],
                    parameters={"sequence_id": 1000 + i},
                )
                reqs.append(core.infer(req))
            return await asyncio.gather(*reqs)

        responses = _run(drive())
        for i, resp in enumerate(responses):
            np.testing.assert_array_equal(
                resp.outputs[0].data, np.full((1, 4), 2.0 * i, np.float32))
        # all 8 member executions coalesced into far fewer batches
        assert sum(execute_batches) == 8
        assert len(execute_batches) <= 2, execute_batches

    def test_unproducible_tensor_raises(self):
        registry = ModelRegistry()
        cfg = make_config(
            "bad_ens",
            inputs=[("INPUT", "FP32", [4])],
            outputs=[("OUTPUT", "FP32", [4])],
            platform="ensemble",
            backend="",
        )
        s = cfg.ensemble_scheduling.step.add()
        s.model_name = "whatever"
        s.input_map["X"] = "never_made"
        s.output_map["OUTPUT"] = "OUTPUT"
        registry.register_model(EnsembleModel(cfg))
        core = InferenceCore(registry)
        from triton_client_tpu.server.types import InferError

        with pytest.raises(InferError, match="never_made"):
            _run(core.infer(_request("bad_ens", np.ones(4))))


class TestStepIsBookedOnce:
    """One executed step is one ``StepRecord`` booked once by
    ``InferenceCore._book``, and the books it writes agree with each other
    about it: the statistics, the collector, the ledger, the traces."""

    @staticmethod
    def _core(fail=False):
        registry = ModelRegistry()

        def fn(inputs, params):
            if fail:
                raise RuntimeError("boom")
            return {"OUTPUT": inputs["INPUT"] * 2.0}

        io = dict(inputs=[("INPUT", "FP32", [4])],
                  outputs=[("OUTPUT", "FP32", [4])])
        registry.register_model(PyModel(
            make_config("plain", max_batch_size=8, **io), fn))
        registry.register_model(PyModel(
            make_config("batched", max_batch_size=8,
                        preferred_batch_sizes=[4, 8],
                        max_queue_delay_us=50_000, **io), fn))
        ens = make_config("ens", max_batch_size=8, platform="ensemble",
                          backend="", **io)
        step = ens.ensemble_scheduling.step.add()
        step.model_name = "plain"
        step.input_map["INPUT"] = "INPUT"
        step.output_map["OUTPUT"] = "OUTPUT"
        registry.register_model(EnsembleModel(ens))
        core = InferenceCore(registry)
        booked = []
        book = core._book
        core._book = lambda step: (booked.append(step), book(step))[1]
        return core, booked

    @staticmethod
    def _books(core, name):
        stats = core.registry.get(name).stats
        snap = core.device_stats.snapshot()
        compute = snap["models"].get(name, {})
        return {
            "bucket_rows": stats.bucket_rows,
            "infer_count": stats.infer_count,
            "fail_count": stats.fail_count,
            "dispatch_ns": stats.dispatch_ns,
            "dry_steps": max(stats.dry_no_request_count,
                             stats.dry_window_count, stats.dry_late_count,
                             stats.dry_host_count),
            "dry_ns": stats.dry_no_request_ns + stats.dry_window_ns
            + stats.dry_late_ns + stats.dry_host_ns,
            "inferences": compute.get("inferences", 0),
            "compute_us": compute.get("compute_ms_total", 0.0) * 1e3,
            "tick_padded": sum(b["padded_total"] for b in
                               snap["ticks"].get(name, {}).values()),
            "ledger_us": core.cost_ledger.totals(name)["device_us"],
        }

    @pytest.mark.parametrize("case", [
        "direct_request", "batched_group_of_three_with_a_pad_row",
        "ensemble_member_not_batchable", "execute_raises",
        "batched_after_an_earlier_step",
        "batched_execute_raises_after_an_earlier_step"])
    def test_books_agree_about_one_step(self, case):
        core, booked = self._core(fail="execute_raises" in case)
        target, ran, sent = {
            "direct_request": ("plain", "plain", [2]),
            "batched_group_of_three_with_a_pad_row":
                ("batched", "batched", [1, 1, 1]),
            "ensemble_member_not_batchable": ("ens", "plain", [2]),
            "execute_raises": ("plain", "plain", [2]),
            "batched_after_an_earlier_step": ("batched", "batched", [1, 2]),
            "batched_execute_raises_after_an_earlier_step":
                ("batched", "batched", [1, 2]),
        }[case]
        earlier = case.endswith("after_an_earlier_step")
        before = {}

        async def drive():
            if earlier:
                # the model's first step leaves the chip dry behind it
                await asyncio.gather(
                    core.infer(_request(target, np.ones((1, 4)))),
                    return_exceptions=True)
                booked.clear()
            before.update(self._books(core, ran))
            return await asyncio.gather(
                *(core.infer(_request(target, np.ones((rows, 4))))
                  for rows in sent), return_exceptions=True)

        answers = _run(drive())
        after = self._books(core, ran)
        delta = {k: after[k] - before[k] for k in after}
        (step,) = booked  # once, success or failure
        rows = sum(sent)
        assert (step.model, step.rows, len(step.members)) == \
            (ran, rows, len(sent))
        assert step.path == {"batched": "batch", "ens": "member"}.get(
            target, "direct")
        # only a formed step with steps before it can have found the chip dry
        assert (step.t_dry > 0) == earlier
        if "execute_raises" in case:
            # a failed step is counted as failed and books nothing else,
            # the dry time before it included
            # (the batcher hands its members the model's own exception)
            assert all(isinstance(a, RuntimeError if step.formed
                                  else InferError) for a in answers)
            assert not step.ok and delta == {
                **dict.fromkeys(delta, 0), "fail_count": rows}
            return
        assert step.ok and not any(
            isinstance(a, Exception) for a in answers)
        assert step.bucket == (4 if step.formed else rows)
        # the three stores of rows and of the compute window agree
        assert delta["bucket_rows"] == step.bucket
        assert delta["tick_padded"] == (step.bucket if step.formed else 0)
        assert delta["infer_count"] == delta["inferences"] == rows
        assert delta["dispatch_ns"] == step.window_ns * rows
        # the dry interval is booked whole, by the step, not by the row
        assert delta["dry_steps"] == earlier
        assert delta["dry_ns"] == sum(step.dry_ns) == (
            step.t_called - step.t_dry if earlier else 0)
        assert delta["compute_us"] == pytest.approx(
            step.window_ns / 1e3, abs=1.0)
        # the members' shares sum to the window the collector recorded
        assert delta["ledger_us"] == pytest.approx(
            step.window_ns / 1e3, abs=1e-3)
        for m in step.members:
            if step.path == "member":
                assert m.trace is None  # the ensemble's envelope is traced
                continue
            spans = {}
            for span in m.trace.spans:
                spans.setdefault(span.name, []).append(
                    (span.start_ns, span.end_ns))
            assert spans["COMPUTE"] == [(step.t_called, step.t_returned)]
            assert spans["QUEUE"] == [(m.enqueue_ns, step.t_assembly)]
            assert ("BATCH_ASSEMBLY" in spans) == step.formed
            assert m.trace.cost["device_us"] == pytest.approx(
                step.window_ns * m.rows / rows / 1e3, abs=0.1)
            assert (m.trace.tick is not None) == step.formed


class TestSequenceEviction:
    def test_idle_sequences_evicted(self):
        model = zoo.SequenceModel()
        model._idle_s = 0.05  # tiny TTL for the test
        inp = {"INPUT": np.array([1], np.int32)}
        model.execute(inp, {"sequence_id": 111, "sequence_start": True})
        model.execute(inp, {"sequence_id": 222, "sequence_start": True})
        assert set(model._state) == {111, 222}
        time.sleep(0.08)
        # any traffic triggers eviction of idle sequences
        model.execute(inp, {"sequence_id": 333, "sequence_start": True})
        assert 111 not in model._state and 222 not in model._state
        assert 333 in model._state

    def test_live_sequence_survives(self):
        model = zoo.SequenceModel()
        model._idle_s = 0.2
        inp = {"INPUT": np.array([5], np.int32)}
        model.execute(inp, {"sequence_id": 1, "sequence_start": True})
        for _ in range(3):
            time.sleep(0.05)
            model.execute(inp, {"sequence_id": 1})  # keepalive traffic
        out = model.execute(inp, {"sequence_id": 1, "sequence_end": True})
        assert int(out["OUTPUT"][0]) == 25  # 5 starts + 4 increments
        assert 1 not in model._state and 1 not in model._touched

    def test_end_clears_state(self):
        model = zoo.DynaSequenceModel()
        inp = {"INPUT": np.array([2], np.int32)}
        model.execute(
            inp, {"sequence_id": 7, "sequence_start": True})
        model.execute(inp, {"sequence_id": 7, "sequence_end": True})
        assert model._state == {} and model._touched == {}


class TestInlineFastPath:
    """Adaptive inline execution for sub-ms host models (core._InlineProfile)."""

    def test_first_signature_sample_excluded_from_ema(self):
        from triton_client_tpu.server.core import _InlineProfile

        prof = _InlineProfile()
        sig = (("INPUT0", (1, 16), "int32"),)
        prof.observe(sig, 1.5)  # first execution: may include XLA compile
        assert not prof.ema and not prof.allows(sig)
        prof.observe(sig, 0.0002)
        assert prof.allows(sig)

    def test_slow_model_demoted(self):
        from triton_client_tpu.server.core import _InlineProfile

        prof = _InlineProfile()
        sig = ("s",)
        prof.observe(sig, 0.0001)
        prof.observe(sig, 0.0001)
        assert prof.allows(sig)
        for _ in range(8):
            prof.observe(sig, 0.05)  # sustained slowness
        assert not prof.allows(sig)

    def test_unseen_signature_never_inline(self):
        from triton_client_tpu.server.core import _InlineProfile

        prof = _InlineProfile()
        prof.observe(("a",), 0.0001)
        prof.observe(("a",), 0.0001)
        assert prof.allows(("a",)) and not prof.allows(("b",))

    def test_per_signature_gating(self):
        # advisor scenario: a fast signature's EMA must not admit a new,
        # possibly slower signature inline
        from triton_client_tpu.server.core import _InlineProfile

        prof = _InlineProfile()
        fast = (("INPUT0", (1, 16), "int32"),)
        big = (("INPUT0", (512, 4096), "float32"),)
        prof.observe(fast, 0.0001)
        prof.observe(fast, 0.0001)
        assert prof.allows(fast) and not prof.allows(big)
        prof.observe(big, 0.5)   # first sample (compile) excluded
        prof.observe(big, 0.02)  # genuinely slow signature
        assert not prof.allows(big) and prof.allows(fast)

    def test_live_path_warms_to_inline(self, monkeypatch):
        import triton_client_tpu.http as httpclient
        from triton_client_tpu.server.core import _InlineProfile
        from triton_client_tpu.server.testing import ServerHarness
        from triton_client_tpu.server import ModelRegistry
        from triton_client_tpu.models import zoo as z

        # the mechanism (warm-after-repeat, off-loop first exec) is what this
        # test proves; the 1 ms budget itself is unit-tested above.  Under
        # full-suite CPU load a sub-ms model can exceed 1 ms wall time, so
        # widen the budget to keep the live assertion deterministic.
        monkeypatch.setattr(_InlineProfile, "MAX_INLINE_S", 0.5)
        registry = ModelRegistry()
        z.register_all(registry)
        with ServerHarness(registry) as h:
            with httpclient.InferenceServerClient(h.http_url) as client:
                a = np.ones((1, 16), np.int32)
                i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
                i0.set_data_from_numpy(a)
                i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
                i1.set_data_from_numpy(a)
                for _ in range(4):
                    res = client.infer("simple", [i0, i1])
                np.testing.assert_array_equal(res.as_numpy("OUTPUT0"), a + a)
            prof = h.core._inline_profiles.get("simple@1")
            assert prof is not None and prof.ema
            # host-placed sub-ms model must have earned the inline path
            # signatures carry the dtype OBJECT (str(dtype) per request was
            # a measured hot-path cost; benchmarks/HOTPATH_PROFILE.md)
            assert prof.allows(tuple(
                ("INPUT%d" % i, (1, 16), np.dtype(np.int32))
                for i in range(2)))


class TestReloadInvalidation:
    """Per-model caches must not survive a model reload (registry
    generation counter)."""

    def test_generation_bumps_on_load_unload(self):
        from triton_client_tpu.server import ModelRegistry

        registry = ModelRegistry()
        registry.register_model(zoo.make_simple())
        g0 = registry.generation("simple")
        registry.unload("simple")
        g1 = registry.generation("simple")
        registry.load("simple")
        g2 = registry.generation("simple")
        assert g0 < g1 < g2

    def test_inline_profile_dropped_on_reload(self):
        import triton_client_tpu.http as httpclient
        from triton_client_tpu.server import ModelRegistry
        from triton_client_tpu.server.testing import ServerHarness

        registry = ModelRegistry()
        zoo.register_all(registry)
        with ServerHarness(registry) as h:
            with httpclient.InferenceServerClient(h.http_url) as client:
                a = np.ones((1, 16), np.int32)
                i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
                i0.set_data_from_numpy(a)
                i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
                i1.set_data_from_numpy(a)
                for _ in range(3):
                    client.infer("simple", [i0, i1])
                warm = h.core._inline_profiles["simple@1"]
                assert warm.ema
                client.unload_model("simple")
                client.load_model("simple")
                res = client.infer("simple", [i0, i1])
                np.testing.assert_array_equal(res.as_numpy("OUTPUT0"), a + a)
                fresh = h.core._inline_profiles["simple@1"]
                # reloaded instance: old EMA forgotten, first exec off-loop
                assert fresh is not warm

    def test_batcher_retired_on_reload(self):
        import triton_client_tpu.http as httpclient
        from triton_client_tpu.server import ModelRegistry
        from triton_client_tpu.server.testing import ServerHarness

        registry = ModelRegistry()
        zoo.register_all(registry)
        with ServerHarness(registry) as h:
            with httpclient.InferenceServerClient(h.http_url) as client:
                x = np.ones((1, 512), np.float32)
                inp = httpclient.InferInput("INPUT", [1, 512], "FP32")
                inp.set_data_from_numpy(x)
                client.infer("dense_tpu", [inp])
                old = h.core._batchers.get("dense_tpu@1")
                assert old is not None
                client.unload_model("dense_tpu")
                client.load_model("dense_tpu")
                res = client.infer("dense_tpu", [inp])
                assert res.as_numpy("OUTPUT").shape == (1, 512)
                assert h.core._batchers.get("dense_tpu@1") is not old
