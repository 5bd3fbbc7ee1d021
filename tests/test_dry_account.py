"""When the chip runs dry before a formed step, the step's record says since
when (``t_dry``) and ``InferenceCore._book`` splits the interval by what the
host was doing: ``dry_no_request``, ``dry_window``, ``dry_late``,
``dry_host`` (a step each, not a row).  The same cut, made over a profiler
trace's device line with the ``step.record`` events' mapped points, is
``profiler.attribute_gaps``.  Nothing here bounds a duration measured on
this CPU: the cases assert which parts are non-zero and that sums match the
record.
"""

import collections
import threading
import time

import numpy as np
import pytest

import triton_client_tpu.grpc as grpcclient
from triton_client_tpu.server import ModelRegistry, PyModel, make_config
from triton_client_tpu.server.profiler import GAP_CAUSES, attribute_gaps
from triton_client_tpu.server.testing import ServerHarness
from triton_client_tpu.server.types import dry_split

DRY = ("dry_no_request", "dry_window", "dry_late", "dry_host")


# (t_dry, first_enqueue, t_window_end, t_assembly, t_called) ->
# (no_request, window, late, host)
@pytest.mark.parametrize("points, parts", [
    # dry before any request, then the whole window, a late close, the hop
    ((100, 150, 170, 175, 180), (50, 20, 5, 5)),
    # the request was waiting inside its window when the chip ran dry
    ((160, 150, 170, 175, 180), (0, 10, 5, 5)),
    # ... past the window: the batch should have closed already
    ((172, 150, 170, 175, 180), (0, 0, 3, 5)),
    # ... during the assembly and the hop
    ((177, 150, 170, 175, 180), (0, 0, 0, 3)),
    # a batch that filled closes inside its window: no part is late
    ((100, 150, 170, 160, 180), (50, 10, 0, 20)),
    # carried members, whose window ended long ago
    ((100, 20, 40, 175, 180), (0, 0, 75, 5)),
    # called while the step ahead still ran (t_dry 0): nothing ran dry
    ((0, 150, 170, 175, 180), (0, 0, 0, 0)),
    # the model's earlier steps ended at the very call, or after it
    ((180, 150, 170, 175, 180), (0, 0, 0, 0)),
    ((190, 150, 170, 175, 180), (0, 0, 0, 0)),
    # a batch that its last member filled closes as that member arrives
    ((100, 150, 170, 150, 152), (50, 0, 0, 2)),
], ids=["before_any_request", "inside_its_window", "past_the_window",
        "during_the_hop", "closed_inside_its_window", "carried_members",
        "step_ahead_still_ran", "ahead_ended_at_the_call",
        "ahead_ended_after_the_call", "closed_on_arrival"])
def test_every_dry_nanosecond_has_one_cause(points, parts):
    assert dry_split(*points) == parts
    t_dry, t_called = points[0], points[-1]
    assert sum(parts) == (max(0, t_called - t_dry) if t_dry else 0)
    assert min(parts) >= 0


class _Gated:
    """A host model whose execute says when it began, and sleeps until the
    gate opens that the test laid out for that call (none: not at all)."""

    def __init__(self):
        self.began = threading.Semaphore(0)
        self.gates = collections.deque()

    def __call__(self, inputs, params):
        gate = self.gates.popleft() if self.gates else None
        self.began.release()
        assert gate is None or gate.wait(timeout=30)
        return {"OUT": inputs["IN"]}


QUEUE_DELAY_NS = 20_000_000


@pytest.fixture()
def served():
    """A served host model behind a batcher with a 20 ms window, the steps
    its core booked, and a way to send one row."""
    fn = _Gated()
    registry = ModelRegistry()
    registry.register_model(PyModel(make_config(
        "sleeper", inputs=[("IN", "FP32", [4])],
        outputs=[("OUT", "FP32", [4])], max_batch_size=8,
        preferred_batch_sizes=[4, 8],
        max_queue_delay_us=QUEUE_DELAY_NS // 1000), fn))
    with ServerHarness(registry) as h:
        booked = []
        book = h.core._book
        h.core._book = lambda step: (book(step), booked.append(step))[0]

        def send():
            x = np.ones((1, 4), np.float32)
            with grpcclient.InferenceServerClient(h.grpc_url) as client:
                inp = grpcclient.InferInput("IN", [1, 4], "FP32")
                inp.set_data_from_numpy(x)
                client.infer("sleeper", [inp])

        yield h.core, fn, booked, send


def _dry(core):
    (row,) = core.statistics("sleeper")
    return {name: dict(row["inference_stats"][name]) for name in DRY}


def test_a_lone_request_after_a_quiet_spell_books_where_the_chip_waited(
        served):
    core, fn, booked, send = served
    send()  # the model's first step: no step before it left the chip dry
    assert booked[0].t_dry == 0
    assert all(e == {"count": 0, "ns": 0} for e in _dry(core).values())
    time.sleep(0.05)  # the quiet spell
    send()
    step = booked[1]
    assert step.formed and step.t_dry == booked[0].t_on_host
    after = _dry(core)
    # no request was there, then its window was open to the end, then the
    # batch crossed to the executor; the window's part is the queue delay
    # at most, however late the pump ran (that is dry_late's)
    assert after["dry_no_request"]["count"] == 1
    assert after["dry_no_request"]["ns"] == \
        step.members[0].enqueue_ns - step.t_dry > 0
    assert after["dry_window"]["count"] == 1
    assert 0 < after["dry_window"]["ns"] <= QUEUE_DELAY_NS
    assert after["dry_host"]["count"] == 1
    assert after["dry_late"]["count"] == (after["dry_late"]["ns"] > 0)
    assert sum(e["ns"] for e in after.values()) == \
        step.t_called - step.t_dry
    assert tuple(after[name]["ns"] for name in DRY) == step.dry_ns


def _two_pipelined_steps(served):
    """After the model's first step, two more, the second called while the
    first sleeps behind its gate: no time decides the order.  Returns the
    two senders' threads and gates, and the account before the pair."""
    core, fn, booked, send = served
    send()
    assert fn.began.acquire(timeout=10)
    gates = [threading.Event(), threading.Event()]
    fn.gates.extend(gates)
    pair = [threading.Thread(target=send) for _ in gates]
    for thread in pair:
        thread.start()
        assert fn.began.acquire(timeout=10)
    return pair, gates, _dry(core)


def _ended(thread, gate):
    gate.set()
    thread.join(timeout=10)
    assert not thread.is_alive()


def test_a_step_called_while_the_one_ahead_runs_books_no_dry_time(served):
    core, fn, booked, send = served
    pair, gates, before = _two_pipelined_steps(served)
    _ended(pair[0], gates[0])
    _ended(pair[1], gates[1])
    _, ahead, step = booked
    assert ahead.t_called < step.t_called < ahead.t_on_host
    assert step.ok and step.t_dry == 0 and step.dry_ns == (0, 0, 0, 0)
    after = _dry(core)
    # what the pair booked is the first one's dry time alone
    assert ahead.t_dry == booked[0].t_on_host
    for k, name in enumerate(DRY):
        assert after[name]["ns"] - before[name]["ns"] == ahead.dry_ns[k]
    assert sum(e["ns"] for e in after.values()) == sum(
        s.t_called - s.t_dry for s in booked if s.t_dry)


def test_a_step_that_ends_before_the_one_called_ahead_of_it_was_not_dry(
        served):
    """Host steps run side by side, and after a pause the threads of two
    read-backs wake in any order: the step observed first was called while
    the other ran, though none of the observed steps says so."""
    core, fn, booked, send = served
    pair, gates, before = _two_pipelined_steps(served)
    _ended(pair[1], gates[1])
    _, step = booked
    assert step.ok and step.t_called > booked[0].t_on_host
    assert step.t_dry == 0 and _dry(core) == before
    _ended(pair[0], gates[0])
    ahead = booked[2]
    assert ahead.t_called < step.t_called and ahead.t_on_host > step.t_on_host
    # nor does the one ahead book the spell before it: by the time it is
    # observed a later step has ended past its call (the account is a
    # lower bound)
    assert ahead.t_dry == 0 and _dry(core) == before


def _record(step, at, now, **points):
    return {"model": "m", "step": step, "at": at, "now": now, **points}


def test_a_gap_is_cut_at_each_point_of_the_step_that_follows_it():
    """The host's clock runs 1,000,000 ahead of the trace's.  One op, a gap
    of 100, then the op of a step whose five points all lie in the gap."""
    ops = {"/device:TPU:0": [("a", 0, 1000), ("b", 1100, 2000)]}
    records = [_record(1, at=2050, now=1_002_050, first_enqueue=1_001_010,
                       t_window_end=1_001_030, t_assembly=1_001_060,
                       t_called=1_001_090, t_on_host=1_002_040)]
    out = attribute_gaps(ops, records)
    assert out["by_cause"] == {"no_request": 10, "window": 20, "late": 30,
                               "host": 30, "dispatch": 10, "none": 0}
    (device,) = out["devices"].values()
    assert device["window_ns"] == 2000 and device["idle_ns"] == 100
    (gap,) = out["gaps"]
    assert (gap["ns"], gap["step"], gap["model"], gap["start"]) == \
        (100, 1, "m", 1000)
    assert gap["parts"] == dict(zip(GAP_CAUSES, (10, 20, 30, 30, 10)))


@pytest.mark.parametrize("gap_lo, parts", [
    (1000, (10, 20, 30, 30, 10)),   # before the request came
    (1020, (0, 10, 30, 30, 10)),    # inside its window
    (1040, (0, 0, 20, 30, 10)),     # past the window's end
    (1070, (0, 0, 0, 20, 10)),      # during assembly and the hop
    (1095, (0, 0, 0, 0, 5)),        # inside model.execute
], ids=["no_request", "window", "late", "host", "dispatch"])
def test_a_gap_that_opens_later_begins_with_a_later_cause(gap_lo, parts):
    ops = {"d": [("a", 0, gap_lo), ("b", 1100, 2000)]}
    records = [_record(1, at=0, now=0, first_enqueue=1010,
                       t_window_end=1030, t_assembly=1060, t_called=1090,
                       t_on_host=2040)]
    out = attribute_gaps(ops, records)
    assert tuple(out["by_cause"][c] for c in GAP_CAUSES) == parts
    assert sum(parts) == 1100 - gap_lo == out["devices"]["d"]["idle_ns"]


@pytest.mark.parametrize("points, parts", [
    # a direct request or an ensemble member: it waited from 1030 on
    (dict(first_enqueue=1030, t_assembly=1060), (30, 0, 0, 60, 10)),
    # a warm-up has no member either: nothing waited before it was built
    (dict(first_enqueue=0, t_assembly=1060), (60, 0, 0, 30, 10)),
], ids=["direct_request", "warm_up"])
def test_a_step_no_batcher_formed_has_no_window_and_no_late_close(
        points, parts):
    ops = {"d": [("a", 0, 1000), ("b", 1100, 2000)]}
    records = [_record(1, at=0, now=0, t_window_end=0, t_called=1090,
                       t_on_host=2040, **points)]
    out = attribute_gaps(ops, records)
    assert tuple(out["by_cause"][c] for c in GAP_CAUSES) == parts


def test_gaps_of_two_devices_pipelined_steps_and_a_gap_no_step_follows():
    records = [
        _record(1, at=0, now=0, first_enqueue=10, t_window_end=20,
                t_assembly=20, t_called=40, t_on_host=500),
        # called while step 1 ran: the bubble inside step 1's programs is
        # not its host's doing
        _record(2, at=0, now=0, first_enqueue=100, t_window_end=120,
                t_assembly=120, t_called=150, t_on_host=900),
    ]
    ops = {
        # step 1's two programs with a bubble between them, step 2's, and
        # one more op after every recorded step was on the host
        "/device:TPU:0": [("p", 50, 200), ("q", 230, 480), ("r", 520, 880),
                          ("s", 1000, 1100)],
        # overlapping and nested ops are one busy span
        "/device:TPU:1": [("p", 50, 400), ("in", 60, 70), ("q", 300, 480),
                          ("r", 520, 880)],
    }
    out = attribute_gaps(ops, records, window=(0, 1100))
    first, second = (out["devices"][d] for d in sorted(ops))
    # [0,50): step 1's time line; [200,230) and [480,520): step 2 was
    # called by then, so dispatch; [880,1000): no step follows
    assert first["by_cause"] == {"no_request": 10, "window": 10, "late": 0,
                                 "host": 20, "dispatch": 10 + 30 + 40,
                                 "none": 120}
    assert first["idle_ns"] == sum(first["by_cause"].values()) == 240
    # the second device ran nothing after 880: the window's tail is no
    # step's either
    assert second["by_cause"]["none"] == 220
    assert second["by_cause"]["dispatch"] == 10 + 40
    assert second["idle_ns"] == sum(second["by_cause"].values())
    assert out["by_cause"]["none"] == 340
    assert out["gaps"][0]["ns"] == 220 and out["gaps"][0]["parts"] is None
    # without a window a device's gaps are those between its own ops
    assert attribute_gaps(ops, records)["devices"]["/device:TPU:1"][
        "by_cause"]["none"] == 0


class _Log:
    def __init__(self):
        self.infos, self.errors = [], []

    def info(self, msg, request_id=""):
        self.infos.append(msg)

    def error(self, msg, request_id=""):
        self.errors.append(msg)


def test_a_profile_session_ends_with_its_gaps_by_cause(monkeypatch, tmp_path):
    """``trace_level=PROFILE`` going off writes ``gaps.json`` beside the
    profile and logs the totals; a reduction that fails is logged and the
    level is off all the same."""
    import json

    import jax

    from triton_client_tpu.server import trace as trace_mod

    tracer = trace_mod.RequestTracer(
        {k: list(v) for k, v in trace_mod.TRACE_DEFAULTS.items()})
    tracer.log = _Log()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    run = tmp_path / "t.jsonl.profile" / "plugins" / "profile" / "a_run"
    run.mkdir(parents=True)
    planted = {"profile": str(run / "host.xplane.pb"), **attribute_gaps(
        {"/device:TPU:0": [("a", 0, 1000), ("b", 1100, 2000)]},
        [_record(1, at=0, now=0, first_enqueue=1010, t_window_end=1030,
                 t_assembly=1060, t_called=1090, t_on_host=2040)])}
    asked = []
    monkeypatch.setattr(trace_mod, "device_gaps",
                        lambda d: (asked.append(d), planted)[1])
    on = {"trace_level": ["PROFILE"],
          "trace_file": [str(tmp_path / "t.jsonl")]}
    tracer.apply(on)
    tracer.apply({"trace_level": ["OFF"]})
    assert asked == [str(tmp_path / "t.jsonl.profile")]
    with open(run / "gaps.json") as f:
        assert json.load(f)["by_cause"] == planted["by_cause"]
    (said,) = tracer.log.infos
    assert "window 20.0" not in said and "window 0.0" in said  # ms
    assert all(cause in said for cause in GAP_CAUSES)
    assert str(run / "gaps.json") in said and not tracer.log.errors

    def boom(d):
        raise ValueError("a truncated profile")

    monkeypatch.setattr(trace_mod, "device_gaps", boom)
    tracer.apply(on)
    tracer.apply({"trace_level": ["OFF"]})
    assert tracer._profiling is False
    (said,) = tracer.log.errors
    assert "a truncated profile" in said and len(tracer.log.infos) == 1


def test_a_cpu_profile_has_no_device_line_to_account_for(tmp_path):
    import jax

    from triton_client_tpu.server.profiler import annotation, device_gaps

    with pytest.raises(FileNotFoundError):
        device_gaps(str(tmp_path))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with annotation("step.record", step=1, now=time.monotonic_ns()):
            pass
    finally:
        jax.profiler.stop_trace()
    assert device_gaps(str(tmp_path)) is None
