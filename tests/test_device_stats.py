"""Device & scheduler observability: the DeviceStatsCollector (duty
cycle, live MFU, compile events, transfers, batcher tick profiling), the
SLO burn-rate engine, breach-triggered flight-recorder pinning, the debug
surfaces on both protocols, and the console views.

Burn-rate math runs entirely on synthetic time (every SloEngine/"window"
API takes an explicit ``now``) — no wall-clock sleeps against quantiles
or windows anywhere in this file.
"""

import asyncio
import json
import time

import numpy as np
import pytest
import requests

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import triton_client_tpu.grpc as grpcclient  # noqa: E402
import triton_client_tpu.http as httpclient  # noqa: E402
from triton_client_tpu.models import zoo  # noqa: E402
from triton_client_tpu.server import (  # noqa: E402
    JaxModel,
    ModelRegistry,
    make_config,
)
from triton_client_tpu.server.device_stats import (  # noqa: E402
    DeviceStatsCollector,
    SLO_WINDOWS,
    SloEngine,
    SloObjective,
    parse_slo_spec,
)
from triton_client_tpu.server.flight_recorder import (  # noqa: E402
    FlightRecorder,
    parse_snapshot_limit,
)
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402
from triton_client_tpu.server.types import StepRecord  # noqa: E402
from triton_client_tpu.server.trace import (  # noqa: E402
    TRACE_DEFAULTS,
    RequestTracer,
)


# -- collector units ---------------------------------------------------------

class TestCollector:
    def test_duty_cycle_over_window(self):
        ds = DeviceStatsCollector(window_s=10.0)
        ds._started_s = 0.0
        # 2s of compute inside a 10s window -> 20% duty
        ds.record_execute("m", 1, int(2e9), now=50.0)
        assert ds.duty_cycle("m", now=55.0) == pytest.approx(0.2)
        # events age out of the window entirely
        assert ds.duty_cycle("m", now=100.0) == 0.0

    def test_duty_cycle_clamps_at_one(self):
        ds = DeviceStatsCollector(window_s=10.0)
        ds._started_s = 0.0
        for _ in range(4):  # 16s of (pipelined) compute in a 10s window
            ds.record_execute("m", 1, int(4e9), now=50.0)
        assert ds.duty_cycle("m", now=50.0) == 1.0

    def test_live_mfu_counts_declared_flops_only(self, monkeypatch):
        from triton_client_tpu.server import costs

        # the CPU has no peak-table row; stand in for a listed device
        monkeypatch.setattr(costs, "_local_device_kind",
                            lambda: "TPU v5 lite")
        ds = DeviceStatsCollector(window_s=60.0)
        ds._started_s = 0.0
        # no FLOPs declared: unknown, not 0%
        ds.record_execute("anon", 1, int(1e9), now=10.0)
        assert ds.live_mfu("anon", now=10.0) is None
        # declared: flops/compute_s/bf16 peak of the device kind
        peak = costs.device_peaks()["bf16_flops"]
        assert peak == 197e12
        ds.declare_model("m", peak / 4.0)  # per element
        ds.record_execute("m", 2, int(1e9), now=10.0)  # 2 elements in 1s
        assert ds.live_mfu("m", now=10.0) == pytest.approx(0.5)

    def test_first_signature_is_compile_and_leaves_the_window(self):
        ds = DeviceStatsCollector(window_s=60.0)
        ds._started_s = 0.0
        sig = (("X", (4, 4), "f32"),)
        ds.record_execute("m", 1, int(30e9), signature=sig, now=1.0)
        ds.record_execute("m", 1, int(1e9), signature=sig, now=2.0)
        ds.record_execute("m", 1, int(1e9), signature=sig, now=3.0)
        snap = ds.snapshot()["models"]["m"]
        assert snap["compile"]["count"] == 1
        assert snap["compile"]["jit_cache_misses"] == 1
        assert snap["compile"]["jit_cache_hits"] == 2
        assert snap["compile"]["total_ms"] == pytest.approx(30000.0)
        # the 30s compile execution is NOT 30s of useful compute
        assert ds.duty_cycle("m", now=3.0) < 0.1
        # a second shape = a second compile
        ds.record_execute("m", 1, int(5e9),
                          signature=(("X", (8, 4), "f32"),), now=4.0)
        assert ds.snapshot()["models"]["m"]["compile"]["count"] == 2

    def test_tick_aggregation_and_pad_waste(self):
        ds = DeviceStatsCollector()
        ds.record_tick("m", bucket=8, batch=5, padded=8, queue_depth=3,
                       assembly_ns=10_000, requests=5, syncs=1)
        ds.record_tick("m", bucket=8, batch=3, padded=8, queue_depth=1,
                       assembly_ns=30_000, requests=3, syncs=1)
        ds.record_tick("m", bucket=16, batch=16, padded=16, queue_depth=0,
                       assembly_ns=10_000, requests=16)
        snap = ds.snapshot()["ticks"]["m"]
        assert snap["8"]["ticks"] == 2
        assert snap["8"]["pad_waste"] == pytest.approx(0.5)
        assert snap["8"]["avg_batch"] == pytest.approx(4.0)
        assert snap["8"]["avg_assembly_us"] == pytest.approx(20.0)
        assert snap["8"]["avg_queue_depth"] == pytest.approx(2.0)
        assert snap["8"]["max_queue_depth"] == 3
        assert snap["8"]["syncs"] == 2
        assert snap["16"]["pad_waste"] == 0.0
        # cumulative fraction across buckets: (5+3+16)/(8+8+16)
        assert ds.pad_waste("m") == pytest.approx(1.0 - 24 / 32)

    def test_transfer_counters(self):
        ds = DeviceStatsCollector()
        ds.record_transfer("h2d", 1024)
        ds.record_transfer("d2h", 512, count=4)
        snap = ds.snapshot()["transfers"]
        assert snap["h2d"] == {"count": 1, "bytes": 1024}
        assert snap["d2h"] == {"count": 4, "bytes": 512}

    def test_disabled_collector_records_nothing(self):
        ds = DeviceStatsCollector()
        ds.enabled = False
        ds.record_execute("m", 1, int(1e9))
        ds.record_tick("m", 8, 4, 8, 0, 1000)
        ds.record_transfer("h2d", 64)
        snap = ds.snapshot()
        assert snap["models"] == {} and snap["ticks"] == {}
        assert snap["transfers"] == {}

    def test_metric_rows_cover_every_family_key(self, monkeypatch):
        from triton_client_tpu.server import costs

        # live_mfu needs a peak-table row; the CPU has none
        monkeypatch.setattr(costs, "_local_device_kind",
                            lambda: "TPU v5 lite")
        ds = DeviceStatsCollector(window_s=60.0)
        ds._started_s = 0.0
        ds.declare_model("m", 1e9)
        sig = (("X", (1,), "f32"),)
        ds.record_execute("m", 1, int(1e9), signature=sig, now=1.0)
        ds.record_execute("m", 1, int(1e9), signature=sig, now=2.0)
        ds.record_tick("m", 8, 4, 8, 2, 1000, syncs=1)
        ds.record_transfer("d2h", 64)
        rows = ds.metric_rows(now=5.0)
        for key in ("duty_cycle", "live_mfu", "compile_total", "compile_us",
                    "jit_hit", "jit_miss", "transfer_total",
                    "transfer_bytes", "tick_total", "tick_batch",
                    "tick_padded", "tick_assembly_us", "tick_queue_depth",
                    "tick_syncs", "tick_steps", "tick_uploads",
                    "pad_waste"):
            assert rows[key], key

    def test_tick_steps_and_upload_counters(self):
        """ISSUE 12 counters: steps fused per dispatch and host->device
        control uploads — the measurable form of the decode fast path."""
        ds = DeviceStatsCollector()
        # a batcher-style tick defaults to 1 step, 0 uploads
        ds.record_tick("m", bucket=8, batch=4, padded=8, queue_depth=0,
                       assembly_ns=1000, syncs=1)
        # a fused decode dispatch: 8 steps, one sync, no uploads
        ds.record_tick("m", bucket=8, batch=4, padded=8, queue_depth=0,
                       assembly_ns=1000, syncs=1, steps=8, uploads=0)
        # a dispatch carrying client-driven steps pays 2 uploads
        ds.record_tick("m", bucket=8, batch=1, padded=8, queue_depth=0,
                       assembly_ns=1000, syncs=1, steps=1, uploads=2)
        entry = ds.snapshot()["ticks"]["m"]["8"]
        assert entry["steps"] == 10
        assert entry["avg_steps_per_tick"] == pytest.approx(10 / 3, rel=0.01)
        assert entry["uploads"] == 2
        rows = ds.metric_rows(now=1.0)
        assert rows["tick_steps"] == [({"model": "m", "bucket": "8"}, 10)]
        assert rows["tick_uploads"] == [({"model": "m", "bucket": "8"}, 2)]

    def test_forget_model_drops_flops_and_signatures(self):
        ds = DeviceStatsCollector()
        ds.declare_model("m", 123.0)
        sig = (("X", (1,), "f32"),)
        ds.record_execute("m", 1, 1000, signature=sig, now=1.0)
        ds.forget_model("m")
        # the reloaded instance re-compiles: same signature counts again
        ds.record_execute("m", 1, 1000, signature=sig, now=2.0)
        assert ds.snapshot()["models"]["m"]["compile"]["count"] == 2


# -- SLO engine units (synthetic time, no sleeps) ----------------------------

def _fill(engine, model, n_good, n_bad, t0, obj_ms=10.0, spacing=1.0):
    for i in range(n_good):
        engine.observe(model, (obj_ms / 2) * 1000, True,
                       now=t0 + i * spacing)
    for i in range(n_bad):
        engine.observe(model, obj_ms * 2000, True,
                       now=t0 + (n_good + i) * spacing)


class TestSloEngine:
    def test_no_objective_means_no_observation(self):
        eng = SloEngine()
        assert eng.observe("m", 1e9, False, now=10.0) is False
        assert eng.burn_rate("m", 300.0, now=10.0) is None
        assert eng.snapshot(now=10.0)["models"] == {}

    def test_burn_rate_math(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=10.0, availability=0.99))
        # 90 good + 10 bad in the window: bad fraction 0.1, budget 0.01
        _fill(eng, "m", 90, 10, t0=1000.0)
        burn = eng.burn_rate("m", 300.0, now=1100.0)
        assert burn == pytest.approx(10.0, rel=1e-6)
        assert eng.budget_remaining("m", now=1100.0) == \
            pytest.approx(-9.0, rel=1e-6)

    def test_failure_counts_as_bad(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=10.0, availability=0.9))
        eng.observe("m", 1000.0, False, now=50.0)  # fast but failed
        assert eng.burn_rate("m", 300.0, now=50.0) == pytest.approx(10.0)

    def test_multi_window_gating(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=10.0,
                                            availability=0.999))
        # an hour-old burst only: the 5m window has no traffic -> no breach
        _fill(eng, "m", 0, 50, t0=100.0)
        assert eng.breached("m", now=100.0 + 3000.0) is False
        # fresh burst too: both windows burn -> breach
        _fill(eng, "m", 0, 50, t0=100.0 + 3000.0)
        assert eng.breached("m", now=100.0 + 3060.0) is True

    def test_healthy_model_never_breaches(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=10.0,
                                            availability=0.999))
        _fill(eng, "m", 200, 0, t0=100.0)
        assert eng.breached("m", now=400.0) is False
        assert eng.budget_remaining("m", now=400.0) == 1.0
        assert eng.observe("m", 1000.0, True, now=400.0) is False

    def test_window_pruning(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=10.0))
        _fill(eng, "m", 0, 10, t0=100.0)
        long_s = max(SLO_WINDOWS.values())
        # the burst has aged out of even the long window
        assert eng.burn_rate("m", long_s, now=100.0 + long_s + 60.0) is None

    def test_observe_pins_only_bad_requests_during_breach(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=10.0,
                                            availability=0.999))
        # every request bad: burn over both windows immediately
        assert eng.observe("m", 50_000.0, True, now=100.0) is True
        # a GOOD request during the breach is never pinned
        assert eng.observe("m", 100.0, True, now=101.0) is False
        assert eng.breach_pins == {"m": 1}

    def test_snapshot_shape(self):
        eng = SloEngine()
        eng.set_objective("m", SloObjective(p99_ms=5.0, availability=0.99))
        _fill(eng, "m", 9, 1, t0=100.0, obj_ms=5.0)
        snap = eng.snapshot(now=200.0)
        entry = snap["models"]["m"]
        assert entry["objective"] == {"p99_ms": 5.0, "availability": 0.99}
        assert set(entry["windows"]) == set(SLO_WINDOWS)
        assert entry["windows"]["5m"]["total"] == 10
        assert entry["windows"]["5m"]["bad"] == 1
        assert entry["windows"]["5m"]["burn_rate"] == pytest.approx(10.0)

    def test_resolver_cache_and_invalidate(self):
        calls = []

        def resolver(name):
            calls.append(name)
            return SloObjective(p99_ms=7.0)

        eng = SloEngine()
        eng.resolver = resolver
        assert eng.objective_for("m").p99_ms == 7.0
        assert eng.objective_for("m").p99_ms == 7.0
        assert calls == ["m"]  # cached
        eng.invalidate("m")
        eng.objective_for("m")
        assert calls == ["m", "m"]  # re-resolved after invalidate
        # explicit objective wins over the resolver
        eng.set_objective("m", SloObjective(p99_ms=3.0))
        assert eng.objective_for("m").p99_ms == 3.0

    @pytest.mark.parametrize("spec,ok", [
        ("m=100", True), ("m=100:0.99", True), ("m=1.5", True),
        ("m", False), ("=100", False), ("m=junk", False),
        ("m=-5", False), ("m=100:1.5", False), ("m=100:junk", False),
        ("m=0", False),
    ])
    def test_parse_slo_spec(self, spec, ok):
        if ok:
            name, obj = parse_slo_spec(spec)
            assert name == "m" and obj.p99_ms > 0
        else:
            with pytest.raises(ValueError):
                parse_slo_spec(spec)


# -- breach-triggered flight-recorder pinning (unit, synthetic spans) --------

def _complete_one(recorder, model="m", total_us=1000.0, outcome="ok"):
    tracer = RequestTracer({k: list(v) for k, v in TRACE_DEFAULTS.items()})
    trace = tracer.start_shadow(model, "1")
    from triton_client_tpu.server import InferRequest

    rec = recorder.start(model, "1", InferRequest(model_name=model))
    t0 = time.monotonic_ns()
    trace.begin_root(t0)
    trace._root.end(t0 + int(total_us * 1e3))
    rec.outcome = outcome
    recorder.complete(rec, trace)
    return rec


class TestBreachPinning:
    def test_slo_bad_requests_pinned_while_breaching(self):
        recorder = FlightRecorder(capacity=64, capture_slower_than="10000")
        engine = SloEngine()
        engine.set_objective("m", SloObjective(p99_ms=1.0,
                                               availability=0.999))
        recorder.slo_engine = engine
        # 2ms requests: over the 1ms SLO target (SLO-bad), far under the
        # 10s watchdog threshold (never "slow") -> the capture reason can
        # only be the burn-rate breach
        rec = _complete_one(recorder, total_us=2000.0)
        assert rec.capture_reason == "slo_breach"
        assert rec.spans  # full span tree pinned
        snap = recorder.snapshot()
        assert any(o["capture_reason"] == "slo_breach"
                   for o in snap["outliers"])
        assert engine.breach_pins["m"] >= 1

    def test_failure_reason_wins_over_slo(self):
        recorder = FlightRecorder(capacity=64, capture_slower_than="10000")
        engine = SloEngine()
        engine.set_objective("m", SloObjective(p99_ms=1.0))
        recorder.slo_engine = engine
        rec = _complete_one(recorder, total_us=2000.0, outcome="boom")
        assert rec.capture_reason == "failed"  # root cause preserved

    def test_no_engine_no_slo_capture(self):
        recorder = FlightRecorder(capacity=64, capture_slower_than="10000")
        rec = _complete_one(recorder, total_us=2000.0)
        assert rec.capture_reason is None


# -- snapshot-limit validation (shared by both wire surfaces) ----------------

class TestSnapshotLimit:
    @pytest.mark.parametrize("value,expect", [
        ("0", 0), ("17", 17), (5, 5), (0, 0),
    ])
    def test_valid(self, value, expect):
        assert parse_snapshot_limit(value) == expect

    @pytest.mark.parametrize("value", ["abc", "1.5", "", None, "-1", -3])
    def test_invalid_is_client_error(self, value):
        from triton_client_tpu.server import InferError

        with pytest.raises(InferError) as ei:
            parse_snapshot_limit(value)
        assert ei.value.http_status == 400


# -- end to end: server harness, both protocols, console views ---------------

#: A tiny FLOPs declaration so nv_tpu_live_mfu materializes once a test
#: stands a peak-table device in for the CPU (which has no row).
_FLOPS_PE = 1000.0


@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    zoo.register_all(registry)
    cfg = make_config(
        "batchy",
        inputs=[("X", "FP32", [4])],
        outputs=[("Y", "FP32", [4])],
        max_batch_size=8,
        preferred_batch_sizes=[4, 8],
        max_queue_delay_us=500,
        instance_kind="KIND_CPU",
        parameters={
            "flops_per_inference": str(_FLOPS_PE),
            # SLO from model-config parameters: 10s p99 — never breached
            # by this harness's healthy traffic
            "slo.p99_ms": "10000",
            "slo.availability": "0.99",
        },
    )
    registry.register_model(
        JaxModel(cfg, lambda X: {"Y": jnp.asarray(X) * 2}, jit=False))
    with ServerHarness(registry) as h:
        yield h


def _infer_batchy(server, n=1):
    with httpclient.InferenceServerClient(server.http_url) as c:
        for _ in range(n):
            x = np.ones((1, 4), np.float32)
            inp = httpclient.InferInput("X", [1, 4], "FP32")
            inp.set_data_from_numpy(x)
            c.infer("batchy", [inp])


class TestEndToEnd:
    def test_metrics_expose_device_and_slo_series(self, server,
                                                  monkeypatch):
        from triton_client_tpu.server import costs

        _infer_batchy(server, n=3)
        # the CPU has no peak-table row: no MFU gauge, never a default
        text = requests.get(
            f"http://{server.http_url}/metrics").text
        assert 'nv_tpu_duty_cycle{model="batchy"}' in text
        assert 'nv_tpu_live_mfu{model="batchy"}' not in text
        monkeypatch.setattr(costs, "_local_device_kind",
                            lambda: "TPU v5 lite")
        text = requests.get(
            f"http://{server.http_url}/metrics").text
        assert 'nv_tpu_live_mfu{model="batchy"}' in text
        assert 'nv_tpu_tick_total{model="batchy",bucket="4"}' in text
        assert 'nv_tpu_pad_waste_ratio{model="batchy",bucket="4"}' in text
        assert 'nv_tpu_jit_cache_miss_total{model="batchy"} 1' in text
        assert 'nv_slo_burn_rate{model="batchy",window="5m"}' in text
        assert 'nv_slo_budget_remaining{model="batchy"} 1.0' in text

    def test_debug_endpoint_both_protocols_agree(self, server):
        _infer_batchy(server)
        http_snap = requests.get(
            f"http://{server.http_url}/v2/debug/device_stats").json()
        assert "batchy" in http_snap["models"]
        assert http_snap["ticks"]["batchy"]["4"]["ticks"] >= 1
        assert http_snap["slo"]["models"]["batchy"]["breached"] is False
        with grpcclient.InferenceServerClient(server.grpc_url) as gc:
            grpc_snap = gc.get_device_stats()
        assert set(grpc_snap) == set(http_snap)
        assert grpc_snap["models"]["batchy"]["executions"] >= 1
        # model filter applies on both
        filtered = requests.get(
            f"http://{server.http_url}/v2/debug/device_stats",
            params={"model": "nope"}).json()
        assert filtered["models"] == {}

    def test_http_client_helper(self, server):
        _infer_batchy(server)
        with httpclient.InferenceServerClient(server.http_url) as c:
            snap = c.get_device_stats(model_name="batchy")
        assert list(snap["models"]) == ["batchy"]
        assert snap["models"]["batchy"]["compile"]["count"] >= 1

    def test_aio_client_helpers(self, server):
        import triton_client_tpu.grpc.aio as grpcaio
        import triton_client_tpu.http.aio as httpaio

        async def run():
            async with httpaio.InferenceServerClient(
                    server.http_url) as hc:
                h = await hc.get_device_stats()
            async with grpcaio.InferenceServerClient(
                    server.grpc_url) as gc:
                g = await gc.get_device_stats()
            return h, g

        h, g = asyncio.run(run())
        assert "batchy" in h["models"] and "batchy" in g["models"]

    def test_flight_recorder_limit_validation_http(self, server):
        base = f"http://{server.http_url}/v2/debug/flight_recorder"
        for bad in ("abc", "-1", "1.5", ""):
            r = requests.get(base, params={"limit": bad})
            assert r.status_code == 400, bad
            assert "limit" in r.json()["error"]
        assert requests.get(base, params={"limit": "2"}).status_code == 200

    def test_tick_record_rides_flight_records(self, server):
        _infer_batchy(server)
        snap = requests.get(
            f"http://{server.http_url}/v2/debug/flight_recorder",
            params={"model": "batchy"}).json()
        rec = snap["recent"][-1]
        tick = rec["tick"]
        assert tick is not None
        assert tick["bucket"] == 4
        assert tick["batch"] >= 1
        assert 0.0 <= tick["pad_fraction"] < 1.0

    def test_overload_drives_burn_rate_and_pins(self, server):
        # a synthetic "overload": an explicit sub-microsecond p99 target
        # makes every request SLO-bad, so both windows burn far over
        # threshold and the recorder pins with reason slo_breach — no
        # actual load generation, no wall-clock coupling
        server.core.slo.set_objective(
            "simple", SloObjective(p99_ms=0.0001, availability=0.999))
        try:
            with httpclient.InferenceServerClient(server.http_url) as c:
                a = np.ones((1, 16), np.int32)
                i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
                i0.set_data_from_numpy(a)
                i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
                i1.set_data_from_numpy(a)
                for _ in range(3):
                    c.infer("simple", [i0, i1])
            text = requests.get(f"http://{server.http_url}/metrics").text
            burn = [l for l in text.splitlines()
                    if l.startswith('nv_slo_burn_rate{model="simple"')]
            assert burn and all(
                float(l.rsplit(" ", 1)[1]) > 14.4 for l in burn)
            assert 'nv_slo_breach_total{model="simple"}' in text
            snap = requests.get(
                f"http://{server.http_url}/v2/debug/flight_recorder",
                params={"model": "simple"}).json()
            pinned = [o for o in snap["outliers"]
                      if o["capture_reason"] == "slo_breach"]
            assert pinned and pinned[-1]["spans"]
        finally:
            # drop the objective so later tests see healthy state
            server.core.slo._objectives.pop("simple", None)
            server.core.slo._windows.pop("simple", None)

    def test_triton_top_buckets_view(self, server, capsys):
        from triton_client_tpu.tools import top

        _infer_batchy(server)
        rc = top.main(["--url", server.http_url, "--once", "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        b = out["buckets"]["batchy"]["4"]
        assert b["ticks"] >= 1
        assert b["avg_batch"] is not None
        assert b["pad_pct"] is not None
        row = out["models"]["batchy"]
        assert row["duty_pct"] is not None
        assert row["burn_5m"] is not None  # SLO configured on batchy
        assert row["slo_breach"] is False
        # the text table renders the buckets section + burn column
        rc = top.main(["--url", server.http_url, "--once"])
        text = capsys.readouterr().out
        assert rc == 0
        assert "MODEL/BUCKET" in text
        assert "batchy@4" in text
        assert "BURN" in text

    def test_trace_summary_buckets_view(self, server, tmp_path):
        from triton_client_tpu.tools.trace_summary import (format_text,
                                                           summarize)

        # sampled traces carry the tick record end to end
        trace_file = str(tmp_path / "trace.json")
        with httpclient.InferenceServerClient(server.http_url) as c:
            c.update_trace_settings(settings={
                "trace_file": [trace_file],
                "trace_level": ["TIMESTAMPS"],
                "trace_rate": ["1"],
            })
            try:
                _infer_batchy(server, n=2)
            finally:
                c.update_trace_settings(
                    settings={"trace_level": ["OFF"]})
        records = [json.loads(l) for l in open(trace_file)
                   if l.strip()]
        ticked = [r for r in records if r.get("model_name") == "batchy"
                  and r.get("tick")]
        assert ticked, "no batchy trace carried a tick record"
        summary = summarize(records)
        buckets = summary["models"]["batchy"]["buckets"]
        assert buckets["4"]["records"] >= 1
        assert buckets["4"]["pad_waste_pct"] is not None
        text = format_text(summary)
        assert "bucket" in text and "pad%" in text


class TestMetricsSnapshotParity:
    def test_json_snapshot_matches_prometheus_families(self, server):
        """Every family on the text surface appears in the JSON snapshot
        with identical values — the anti-drift contract the registry
        lint in test_tools_import.py enforces structurally."""
        from triton_client_tpu.server.metrics import (render_prometheus,
                                                      snapshot)

        _infer_batchy(server)
        text = render_prometheus(server.core)
        snap = snapshot(server.core)
        text_families = {l.split(" ", 3)[2] for l in text.splitlines()
                         if l.startswith("# TYPE ")}
        assert text_families == set(snap)
        # spot-check a sample round trip
        ticks = snap["nv_tpu_tick_total"]["samples"]
        assert any(s["labels"] == {"model": "batchy", "bucket": "4"}
                   and s["value"] >= 1 for s in ticks)

    def test_rendered_output_well_formed_and_sample_parity(self, server):
        """The runtime half the static METRICS-DECL rule cannot see: the
        *rendered* text declares every family exactly once (one HELP, one
        TYPE), every sample line parses and belongs to a declared family,
        and the JSON snapshot agrees type-for-type with matching per-family
        series counts.  (The static rule checks the declaration literals;
        this checks what render_prometheus actually emits.)"""
        import re

        from triton_client_tpu.server.metrics import (render_prometheus,
                                                      snapshot)

        _infer_batchy(server)
        text = render_prometheus(server.core)
        helps, types, samples, kinds = {}, {}, {}, {}
        sample_re = re.compile(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{.*\})? (.+)$")
        for line in text.splitlines():
            if line.startswith("# HELP "):
                name = line.split(" ", 3)[2]
                helps[name] = helps.get(name, 0) + 1
            elif line.startswith("# TYPE "):
                _, _, name, kind = line.split(" ", 3)
                types[name] = types.get(name, 0) + 1
                kinds[name] = kind
            elif line.strip():
                m = sample_re.match(line)
                assert m, f"unparseable sample line: {line!r}"
                samples[m.group(1)] = samples.get(m.group(1), 0) + 1
        assert helps, "renderer emitted no families"
        for name, n in helps.items():
            assert n == 1, f"{name}: HELP declared {n} times"
        for name, n in types.items():
            assert n == 1, f"{name}: TYPE declared {n} times"
        assert set(helps) == set(types), "HELP/TYPE sets differ"
        orphans = set(samples) - set(helps)
        assert not orphans, f"series without declarations: {orphans}"
        snap = snapshot(server.core)
        assert set(snap) == set(helps)
        for name, entry in snap.items():
            assert entry["type"] == kinds[name], name
            # same number of series per family on both surfaces
            assert len(entry["samples"]) == samples.get(name, 0), name


# -- review regressions ------------------------------------------------------

class TestReviewRegressions:
    """Pinned-down review findings: pad-inflated MFU, fabricated compile
    events for python-backend models, SLO death under --no-flight-recorder,
    and the unlabeled burn-threshold gauge triton-top could not parse."""

    def test_padded_batch_counts_real_inferences_only(self, server):
        core = server.core
        model = core.registry.get("batchy")
        before = (core.device_stats.snapshot()["models"].get("batchy")
                  or {}).get("inferences", 0)
        x = np.ones((4, 4), np.float32)  # bucket-4 execution, 3 real rows
        asyncio.run(core._run_model(model, {"X": x}, {}, None, StepRecord(
            "batchy", "1", None, "batch", rows=3, bucket=4)))
        after = core.device_stats.snapshot()["models"]["batchy"]
        assert after["inferences"] - before == 3  # pad slot is not an inference

    def test_python_backend_model_never_fabricates_compiles(self, server):
        core = server.core
        model = core.registry.get("custom_identity_int32")
        for n in (3, 5, 7):  # three distinct input-shape signatures
            x = np.zeros((1, n), np.int32)
            asyncio.run(core._run_model(
                model, {"INPUT0": x}, {}, None, StepRecord(
                    model.name, "1", None, "direct", rows=1, bucket=1)))
        snap = core.device_stats.snapshot()["models"]["custom_identity_int32"]
        # a PyModel never touches XLA: no compile events, and every
        # execution's compute stays in the duty/MFU window
        assert snap["compile"]["count"] == 0
        assert snap["compile"]["jit_cache_hits"] == 0
        assert snap["executions"] >= 3

    def test_disabled_recorder_still_feeds_slo_and_pins(self):
        recorder = FlightRecorder(capacity=64, capture_slower_than="10000",
                                  enabled=False)
        engine = SloEngine()
        engine.set_objective("m", SloObjective(p99_ms=1.0))
        recorder.slo_engine = engine
        rec = _complete_one(recorder, total_us=2000.0)
        assert rec.capture_reason == "slo_breach"  # breach pinning survives
        assert engine.breach_pins["m"] >= 1
        snap = recorder.snapshot()
        assert snap["recorded_total"] == 0  # ring/watchdog stay off
        assert any(o["capture_reason"] == "slo_breach"
                   for o in snap["outliers"])
        # recorder-class captures (failed/slow/chaos) stay off while
        # disabled: a failure on an objective-less model records nothing
        rec2 = _complete_one(recorder, model="other", outcome="boom")
        assert rec2.capture_reason is None

    def test_slo_engine_survives_no_flight_recorder_e2e(self):
        registry = ModelRegistry()
        cfg = make_config(
            "slonly",
            inputs=[("X", "FP32", [4])],
            outputs=[("Y", "FP32", [4])],
            max_batch_size=8,
            # 1 us p99: every request is SLO-bad -> instant breach
            parameters={"slo.p99_ms": "0.001"},
        )
        registry.register_model(
            JaxModel(cfg, lambda X: {"Y": jnp.asarray(X) * 2}, jit=False))
        with ServerHarness(registry) as h:
            h.core.flight_recorder.configure(enabled=False)
            with httpclient.InferenceServerClient(h.http_url) as c:
                for _ in range(10):
                    inp = httpclient.InferInput("X", [1, 4], "FP32")
                    inp.set_data_from_numpy(np.ones((1, 4), np.float32))
                    c.infer("slonly", [inp])
            slo = requests.get(
                f"http://{h.http_url}/v2/debug/device_stats",
                timeout=5).json()["slo"]["models"]["slonly"]
            assert slo["windows"]["5m"]["total"] >= 10
            assert slo["breached"] is True
            rsnap = h.core.flight_recorder.snapshot()
            assert rsnap["recorded_total"] == 0
            assert any(o["capture_reason"] == "slo_breach"
                       for o in rsnap["outliers"])

    def test_parse_device_reads_unlabeled_burn_threshold(self):
        from triton_client_tpu.tools.top import parse_device

        text = ('nv_slo_burn_threshold 6.0\n'
                'nv_tpu_duty_cycle{model="m"} 0.5\n')
        out = parse_device(text)
        assert out["burn_threshold"] == 6.0  # label-less gauge must parse
        assert out["duty"]["m"] == 0.5

    def test_bucket_rows_compute_steps_and_uploads_per_tick(self):
        from triton_client_tpu.tools.top import bucket_rows

        cur = {"t": 10.0, "device": {"buckets": {
            ("m", "160"): {"ticks": 20.0, "batch": 40.0, "padded": 80.0,
                           "assembly_us": 2000.0, "queue_depth": 0.0,
                           "syncs": 20.0, "steps": 80.0, "uploads": 4.0},
        }}}
        prev = {"t": 0.0, "device": {"buckets": {
            ("m", "160"): {"ticks": 10.0, "batch": 20.0, "padded": 40.0,
                           "assembly_us": 1000.0, "queue_depth": 0.0,
                           "syncs": 10.0, "steps": 10.0, "uploads": 4.0},
        }}}
        row = bucket_rows(cur, prev)[("m", "160")]
        # 70 steps over 10 ticks in the delta window; uploads flat at 0
        assert row["steps_per_tick"] == pytest.approx(7.0)
        assert row["uploads_per_tick"] == pytest.approx(0.0)

    def test_buckets_view_sorts_numerically(self):
        from triton_client_tpu.tools.top import _bucket_lines, _buckets_json

        row = {"ticks_per_s": 1.0, "avg_batch": 1.0, "pad_pct": 0.0,
               "avg_assembly_us": 1.0, "avg_queue_depth": 0.0,
               "syncs_per_tick": 1.0}
        rows = {("m", b): dict(row) for b in ("128", "8", "16")}
        names = [l.split()[0] for l in _bucket_lines(rows)[2:]]
        assert names == ["m@8", "m@16", "m@128"]  # numeric, not lexicographic
        assert list(_buckets_json(rows)["m"]) == ["8", "16", "128"]
