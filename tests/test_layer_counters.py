"""Counters at the layer boundaries of the served path (the statistics
extension), the per-batch spans on the profiler's clock, and pauses as
events.

The counters are charged per row where the work happens, whether or not a
request carries a TraceContext, from the timestamps the spans already take:
``request`` (frontends), ``queue_member`` / ``batch_assembly`` /
``bucket_rows`` (dynamic batcher; direct path), ``executor_wait`` /
``dispatch`` / ``device_wait`` (the step's ``StepRecord``, stamped by
``InferenceCore._run_model``, booked by ``InferenceCore._book``), ``pause``
(``HostProfiler`` through the core).
"""

import asyncio
import gc
import threading
import time

import numpy as np
import pytest
import requests

import triton_client_tpu.grpc as grpcclient
import triton_client_tpu.http as httpclient
from triton_client_tpu.server import (
    InferenceCore,
    InferError,
    InferRequest,
    ModelRegistry,
    PyModel,
    make_config,
)
from triton_client_tpu.server import profiler as profiler_mod
from triton_client_tpu.server.testing import ServerHarness
from triton_client_tpu.server.trace import TRACE_DEFAULTS, RequestTracer
from triton_client_tpu.server.types import InputTensor

V2 = ("success", "fail", "queue", "compute_input", "compute_infer",
      "compute_output")
PER_ROW = ("queue_member", "batch_assembly", "executor_wait", "dispatch",
           "device_wait")
# a formed step that found the chip out of the model's work, by cause: a
# step, not a row (tests/test_dry_account.py)
DRY = ("dry_no_request", "dry_window", "dry_late", "dry_host")
EXTENSION = ("request",) + PER_ROW + (
    "bucket_rows", "batch_carry", "batch_carry_rows", "batch_hold",
    "batch_early") + DRY + (
    "pause",
    # an expert layer's routing, counted on the device (test_latent_moe.py)
    "expert_rows", "expert_tokens", "expert_rows_busiest",
    # generation by diffusion over blocks (test_block_diffusion.py)
    "denoise_passes", "denoise_tokens", "experts_touched",
    # a stack run several times over one set of weights (test_looped.py)
    "loop_steps", "loop_tokens",
    # greedy generation through two kinds of state (test_hybrid_conv.py)
    "decode_steps", "decode_tokens",
    # learned sparse attention (test_sparse_latent.py)
    "dsa_queries", "dsa_pairs", "index_reused")


def _echo(sleep_s):
    def fn(inputs, params):
        time.sleep(sleep_s)
        return {"OUT": inputs["IN"]}

    return fn


def _registry():
    registry = ModelRegistry()
    for name, sleep_s, batching in (("batched", 0.002, True),
                                    ("direct", 0.002, False),
                                    ("slow", 0.15, False),
                                    ("idle", 0.0, False)):
        cfg = make_config(
            name, inputs=[("IN", "FP32", [4])], outputs=[("OUT", "FP32", [4])],
            max_batch_size=8,
            preferred_batch_sizes=[4, 8] if batching else None,
            max_queue_delay_us=50_000 if batching else 0)
        registry.register_model(PyModel(cfg, _echo(sleep_s)))
    return registry


@pytest.fixture(scope="module")
def server():
    with ServerHarness(_registry()) as h:
        yield h


def _entries(core, model):
    (row,) = core.statistics(model)
    flat = {"inference_count": row["inference_count"],
            "execution_count": row["execution_count"]}
    for name, entry in row["inference_stats"].items():
        flat[name + ".count"] = entry["count"]
        flat[name + ".ns"] = entry["ns"]
    return flat


def _delta(core, model, before):
    after = _entries(core, model)
    return {k: after[k] - before[k] for k in after}


def _send(server, protocol, model, rows):
    mod, url = ((grpcclient, server.grpc_url) if protocol == "grpc"
                else (httpclient, server.http_url))
    x = np.arange(rows * 4, dtype=np.float32).reshape(rows, 4)
    with mod.InferenceServerClient(url) as client:
        inp = mod.InferInput("IN", list(x.shape), "FP32")
        inp.set_data_from_numpy(x)
        out = client.infer(model, [inp]).as_numpy("OUT")
    np.testing.assert_array_equal(out, x)


@pytest.mark.parametrize("recorder_on", [True, False],
                         ids=["recorder_on", "recorder_off"])
@pytest.mark.parametrize("protocol", ["grpc", "http"])
@pytest.mark.parametrize("model", ["batched", "direct"])
def test_the_means_add_up_along_a_requests_path(server, model, protocol,
                                                recorder_on):
    """Every per-row entry counts the rows sent, and a request's mean time
    in the frontend covers the means of what happened inside it — with the
    flight recorder off too (no TraceContext on any request)."""
    core = server.core
    core.flight_recorder.configure(enabled=recorder_on)
    try:
        before = _entries(core, model)
        rows = [1, 2, 1, 1, 2, 1]
        threads = [threading.Thread(target=_send,
                                    args=(server, protocol, model, r))
                   for r in rows]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        d = _delta(core, model, before)
    finally:
        core.flight_recorder.configure(enabled=True)
    sent = sum(rows)
    assert d["inference_count"] == d["success.count"] == sent
    for name in ("request",) + PER_ROW:
        assert d[name + ".count"] == sent, name
    assert d["bucket_rows.count"] >= sent and d["bucket_rows.ns"] == 0
    mean = {name: d[name + ".ns"] / sent for name in ("request",) + PER_ROW}
    assert mean["request"] >= sum(mean[name] for name in PER_ROW)
    # the work was timed, not skipped: the model sleeps 2 ms an execution
    assert mean["dispatch"] >= 2e6
    if model == "direct":
        assert d["batch_assembly.ns"] == 0
        assert d["bucket_rows.count"] == sent
        assert d["queue_member.ns"] == d["queue.ns"]


def _request(model, rows):
    x = np.zeros((rows, 4), np.float32)
    return InferRequest(
        model_name=model,
        inputs=[InputTensor("IN", "FP32", tuple(x.shape), data=x)])


def _run_core(coro_fn):
    core = InferenceCore(_registry())

    async def go():
        try:
            return await coro_fn(core)
        finally:
            await core.shutdown()

    return asyncio.run(go())


def test_queue_member_is_each_members_own_wait():
    """Two members 20 ms apart in one batch: ``queue`` charges the first
    member's wait to both, ``queue_member`` each its own, so its mean is
    the first member's wait less 10 ms."""

    async def two(core):
        first = asyncio.ensure_future(core.infer(_request("batched", 1)))
        await asyncio.sleep(0.02)
        second = asyncio.ensure_future(core.infer(_request("batched", 1)))
        await asyncio.gather(first, second)
        return _entries(core, "batched")

    s = _run_core(two)
    assert s["execution_count"] == 1 and s["inference_count"] == 2
    first_wait = s["queue.ns"] / 2
    member_mean = s["queue_member.ns"] / 2
    assert first_wait >= 45e6  # the batcher's 50 ms delay, from the first
    assert member_mean == pytest.approx(first_wait - 10e6, abs=5e6)
    assert s["batch_assembly.count"] == 2 and s["batch_assembly.ns"] > 0


def test_bucket_rows_counts_the_rows_padded_on():
    """3 rows ride a bucket of 4: ``inference_count`` says 3,
    ``bucket_rows`` 4."""

    async def three(core):
        await core.infer(_request("batched", 3))
        return _entries(core, "batched")

    s = _run_core(three)
    assert s["inference_count"] == 3
    assert s["bucket_rows.count"] == 4 and s["bucket_rows.ns"] == 0


def test_grpc_statistics_keep_the_six_v2_entries(server):
    """The extension shows on the HTTP statistics JSON; the gRPC response
    is the v2 message, as before."""
    _send(server, "grpc", "direct", 1)
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        stats = client.get_inference_statistics("direct", as_json=True)
    (row,) = stats["model_stats"]
    assert sorted(row["inference_stats"]) == sorted(V2)
    with httpclient.InferenceServerClient(server.http_url) as client:
        (row,) = client.get_inference_statistics("direct")["model_stats"]
    assert list(row["inference_stats"]) == list(V2) + list(EXTENSION)
    assert row["inference_stats"]["request"]["count"] >= 1


def _plant_gc_pause(profiler, seconds):
    """The collector's hook, called as CPython calls it around a
    generation-2 collection that takes ``seconds``."""
    gc.disable()  # a real collection in between would take the start stamp
    try:
        profiler._on_gc("start", {"generation": 2})
        time.sleep(seconds)
        profiler._on_gc("stop", {"generation": 2})
    finally:
        gc.enable()


def test_a_pause_is_charged_where_requests_were_held_and_names_the_stall(
        server):
    """A planted 30 ms collection while ``slow`` has a request pending
    lands in ``slow``'s ``pause`` entry and on its pinned flight record,
    and not in a model with nothing pending."""
    core = server.core
    core.flight_recorder.configure(capture_slower_than="25", enabled=True)
    core.flight_recorder.reset()
    try:
        slow0, idle0 = _entries(core, "slow"), _entries(core, "idle")
        t = threading.Thread(target=_send, args=(server, "http", "slow", 1))
        t.start()
        deadline = time.monotonic() + 10
        while core.registry.get("slow").stats.pending_count == 0:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        _plant_gc_pause(core.profiler, 0.03)
        t.join(timeout=30)
        assert not t.is_alive()
        slow, idle = _delta(core, "slow", slow0), _delta(core, "idle", idle0)
        snap = requests.get(
            f"http://{server.http_url}/v2/debug/flight_recorder?model=slow",
            timeout=10).json()
    finally:
        core.flight_recorder.configure(capture_slower_than="p99")
        core.flight_recorder.reset()
    assert slow["pause.count"] >= 1 and slow["pause.ns"] >= 30e6
    assert idle["pause.count"] == 0 and idle["pause.ns"] == 0
    (pinned,) = snap["outliers"]
    assert pinned["capture_reason"] == "slow"
    planted = [p for p in pinned["pauses"] if p["kind"] == "gc2"
               and p["end_ns"] - p["start_ns"] >= 30e6]
    assert len(planted) == 1
    root = next(s for s in pinned["spans"] if s["parent"] is None)
    assert root["start_ns"] < planted[0]["start_ns"]
    assert planted[0]["end_ns"] < root["end_ns"]
    # the ring's unpinned records carry no pause list
    assert all("pauses" not in r for r in snap["recent"])


def test_the_collectors_hook_does_not_wait_for_the_registry():
    """A load holds the registry's lock for as long as the model takes to
    build; a collection on another thread meanwhile charges no model and
    returns at once."""

    async def during_a_load(core):
        stats = core.registry.get("idle").stats
        stats.inc_pending()
        held, release = threading.Event(), threading.Event()

        def load():
            with core.registry._lock:
                held.set()
                release.wait(10)

        t = threading.Thread(target=load)
        t.start()
        try:
            assert held.wait(5)
            t0 = time.monotonic()
            core._charge_pause(7_000_000)
            waited = time.monotonic() - t0
        finally:
            release.set()
            t.join(timeout=10)
        during = stats.pause_count
        core._charge_pause(7_000_000)
        stats.dec_pending()
        return waited, during, stats.pause_count, stats.pause_ns

    waited, during, after, ns = _run_core(during_a_load)
    assert waited < 1.0 and during == 0
    assert after == 1 and ns == 7_000_000


class _Log:
    def __init__(self):
        self.errors = []

    def error(self, msg, request_id=""):
        self.errors.append(msg)


def test_a_profiler_that_does_not_start_refuses_the_update(monkeypatch,
                                                           tmp_path):
    import jax

    def boom(*a, **kw):
        raise RuntimeError("only one profile session at a time")

    settings = {k: list(v) for k, v in TRACE_DEFAULTS.items()}
    tracer = RequestTracer(settings)
    tracer.log = _Log()
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(InferError) as ei:
        tracer.apply({"trace_level": ["TIMESTAMPS", "PROFILE"],
                      "trace_file": [str(tmp_path / "t.jsonl")]})
    assert ei.value.http_status == 503
    assert "only one profile session" in str(ei.value)
    assert settings["trace_level"] == TRACE_DEFAULTS["trace_level"]
    assert settings["trace_file"] == TRACE_DEFAULTS["trace_file"]
    assert tracer._profiling is False
    # a profiler that does not stop: the level goes off, and the log says so
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    tracer.apply({"trace_level": ["PROFILE"]})
    assert tracer._profiling is True
    tracer.apply({"trace_level": ["OFF"]})
    assert tracer._profiling is False and settings["trace_level"] == ["OFF"]
    assert len(tracer.log.errors) == 1 and "stop_trace" in tracer.log.errors[0]


@pytest.mark.parametrize("protocol", ["grpc", "http"])
def test_both_frontends_report_a_profiler_that_did_not_start(
        server, monkeypatch, protocol):
    import jax

    def boom(*a, **kw):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    mod, url = ((grpcclient, server.grpc_url) if protocol == "grpc"
                else (httpclient, server.http_url))
    with mod.InferenceServerClient(url) as client:
        with pytest.raises(Exception) as ei:
            client.update_trace_settings(
                settings={"trace_level": ["PROFILE"]})
        assert "did not start" in str(ei.value)
        level = client.get_trace_settings()
    if protocol == "grpc":
        level = {k: list(v.value) for k, v in level.settings.items()}
    assert level["trace_level"] == ["OFF"]


def test_annotations_outside_a_profiler_session_write_and_raise_nothing(
        monkeypatch):
    with profiler_mod.annotation("step.record", step=7, bucket=4, rows=3,
                                 t_called=1, now=2):
        pass
    # a process that never imported JAX is not made to
    monkeypatch.setattr(profiler_mod, "sys",
                        type("_Sys", (), {"modules": {}}))
    span = profiler_mod.annotation("step.dispatch", model="m")
    assert span is profiler_mod._NO_SPAN
    with span:
        pass


def test_the_per_batch_spans_are_on_the_profilers_clock(server, tmp_path):
    """Inside a profiler session the executor's sections are host events of
    the trace, with their arguments, and the step's record beside them
    carries every host point of the step and the clock pair that maps them
    onto the trace's clock."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the level the benchmark traces at
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _send(server, "grpc", "batched", 3)
        _plant_gc_pause(server.core.profiler, 0.006)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    seen = {}
    for plane in ProfileData.from_file(str(path)).planes:
        # one line a host thread; the lines share the process's name
        for thread, line in enumerate(plane.lines):
            for event in line.events:
                if event.name in ("batcher.assemble", "step.record",
                                  "step.dispatch", "step.device_wait",
                                  "host.gc"):
                    seen[event.name] = (
                        (plane.name, thread), dict(event.stats),
                        (event.start_ns, event.start_ns + event.duration_ns))
    assert set(seen) == {"step.record", "step.dispatch", "step.device_wait",
                         "host.gc"}
    record = seen["step.record"][1]
    assert (record["model"], record["bucket"], record["rows"]) == \
        ("batched", 4, 3)
    assert seen["step.dispatch"][1]["model"] == "batched"
    # the spans of one step share its identifier and the executor's thread
    assert record["step"] == seen["step.dispatch"][1]["step"] \
        == seen["step.device_wait"][1]["step"] > 0
    assert seen["step.dispatch"][0] == seen["step.device_wait"][0] \
        == seen["step.record"][0]
    # every host point of the step, in the order it passed them
    points = [record[k] for k in (
        "first_enqueue", "t_assembly", "t_assembled", "t_submit", "t_exec",
        "t_called", "t_returned", "t_on_host", "now")]
    assert points == sorted(points) and points[0] > 0
    assert record["t_window_end"] == record["first_enqueue"] + 50_000_000
    # the clock pair (the event's own start beside ``now``) puts the
    # record's t_called and t_returned inside the step's dispatch event.
    # ``now`` is read after ``t_on_host``, which is read inside the
    # device_wait event, and before the record event starts: the pair is
    # off by no more than lies between those two on the trace itself
    at = seen["step.record"][2][0]
    offset = at - record["now"]
    lo, hi = seen["step.dispatch"][2]
    slack = at - seen["step.device_wait"][2][0]
    assert slack >= 0
    assert lo - slack <= record["t_called"] + offset \
        <= record["t_returned"] + offset <= hi + slack
    assert record["t_on_host"] + offset <= at


def test_triton_top_names_the_longest_pause_on_an_outlier():
    from triton_client_tpu.tools import top

    outlier = {"seq": 7, "age_s": 0.5, "total_us": 80_000.0,
               "capture_reason": "slow", "outcome": "ok",
               "pauses": [{"kind": "gc0", "start_ns": 0, "end_ns": 6_000_000},
                          {"kind": "gc2", "start_ns": 7_000_000,
                           "end_ns": 58_400_000}]}
    assert top._outlier_brief(outlier)["pause"] == {"kind": "gc2",
                                                    "ms": 51.4}
    assert top._outlier_brief(dict(outlier, pauses=[]))["pause"] is None
