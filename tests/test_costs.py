"""Cost observability (ISSUE 16): XLA cost-analysis acquisition, roofline
classification, and the per-tenant device-time attribution ledger.

Three layers:

* unit tests for ``server/costs.py`` (classification math, the AOT
  analysis probe on the CPU backend, ledger bookkeeping, and the
  server/cluster ``merge_cost_snapshots`` parity);
* an end-to-end MFU test proving every zoo model — specifically
  ``moe_tpu``, which declares no hand-counted flops — gets a live MFU
  from the measured XLA figure, and that "unavailable" stays honestly
  absent (never 0%) when acquisition is disabled;
* the conservation drill: a mixed-tenant generation run in BATCHED
  decode mode, where attributed device-time must sum to the decode
  worker's tick compute window (±5%) and the ledger's KV byte-seconds
  must reconcile exactly with the memory governor's own integrals.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from triton_client_tpu.server import costs  # noqa: E402
from triton_client_tpu.server.costs import (  # noqa: E402
    CostLedger,
    SignatureCost,
    analyze_jax_callable,
    classify_roofline,
    merge_cost_snapshots,
)


class TestClassifyRoofline:
    def test_verdict_against_explicit_ridge(self):
        # ridge = 100/10 = 10 flops/byte
        hi = classify_roofline(1000.0, 10.0, pf=100.0, pb=10.0)
        assert hi["verdict"] == "compute_bound"
        assert hi["arithmetic_intensity"] == 100.0
        assert hi["ridge_point"] == 10.0
        lo = classify_roofline(10.0, 10.0, pf=100.0, pb=10.0)
        assert lo["verdict"] == "memory_bound"

    def test_pct_of_peak_tracks_the_bound_resource(self):
        # compute_bound: achieved flops/s vs peak flops
        r = classify_roofline(50.0, 1.0, compute_s=1.0, pf=100.0, pb=10.0)
        assert r["verdict"] == "compute_bound"
        assert r["pct_of_peak"] == 50.0
        # memory_bound: achieved bytes/s vs peak bytes/s
        r = classify_roofline(1.0, 5.0, compute_s=1.0, pf=100.0, pb=10.0)
        assert r["verdict"] == "memory_bound"
        assert r["pct_of_peak"] == 50.0

    def test_unknown_axes_yield_none_not_zero(self):
        assert classify_roofline(0.0, 10.0, pf=1.0, pb=1.0) is None
        assert classify_roofline(10.0, 0.0, pf=1.0, pb=1.0) is None
        r = classify_roofline(10.0, 1.0, pf=1.0, pb=1.0)
        assert "pct_of_peak" not in r  # no compute window -> no pct


class TestDevicePeaks:
    """One table keyed by device_kind; a device it does not list gets no
    figure — never a default borrowed from another chip."""

    def test_known_kind(self):
        v5e = costs.device_peaks("TPU v5 lite")
        assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                       "hbm_bytes_per_s": 819e9}

    def test_unknown_kind_yields_none(self):
        assert costs.device_peaks("TPU v99 imaginary") is None

    def test_cpu_yields_no_row_and_no_verdict(self):
        # the test process runs on the CPU: no peaks, so no roofline
        # verdict from the defaults and no MFU from the collector
        assert jax.local_devices()[0].platform == "cpu"
        assert costs.device_peaks() is None
        assert classify_roofline(1e12, 1e9, compute_s=1.0) is None
        from triton_client_tpu.server.device_stats import \
            DeviceStatsCollector

        ds = DeviceStatsCollector(window_s=60.0)
        ds._started_s = 0.0
        ds.declare_model("m", 1e9)
        ds.record_execute("m", 2, int(1e9), now=10.0)
        assert ds.live_mfu("m", now=10.0) is None
        assert ds.metric_rows(now=10.0)["live_mfu"] == []

    def test_local_kind_resolves_through_the_table(self, monkeypatch):
        monkeypatch.setattr(costs, "_local_device_kind",
                            lambda: "TPU v5 lite")
        r = classify_roofline(197e12, 1e9, compute_s=2.0)
        # AI far above the 197e12/819e9 ridge: compute bound, and bf16
        # work divides by the bf16 peak — 197 TFLOP in 2 s is 50%
        assert r["verdict"] == "compute_bound"
        assert r["ridge_point"] == pytest.approx(197e12 / 819e9, rel=1e-4)
        assert r["pct_of_peak"] == pytest.approx(50.0)

    def test_env_overrides_are_gone(self, monkeypatch):
        # the table is the only source: a stray env value changes nothing
        monkeypatch.setenv("TRITON_TPU_PEAK_FLOPS", "1")
        monkeypatch.setenv("TRITON_TPU_PEAK_BYTES_PER_S", "junk")
        assert costs.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
        assert not hasattr(costs, "peak_bytes_per_s")

    def test_language_v5e_peak_is_the_bf16_row(self):
        from triton_client_tpu.models import language

        assert language.V5E_PEAK_FLOPS == 197e12
        with pytest.raises(ValueError, match="DEVICE_PEAKS"):
            # offline MFU on a device without a row is an error
            language.serving_mfu(100.0, language.BERT_LARGE, 384, 2)


class TestAnalyzeJaxCallable:
    def test_matmul_flops_measured_on_cpu_backend(self):
        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 4), jnp.float32)
        cost = analyze_jax_callable(lambda x, y: x @ y, a, b)
        assert cost is not None
        # XLA schedules 2*M*N*K flops for a matmul
        assert cost.flops == pytest.approx(2 * 8 * 16 * 4, rel=0.5)
        assert cost.bytes_accessed > 0

    def test_untraceable_fn_is_none_never_raises(self):
        def bad(x):
            raise RuntimeError("boom")

        assert analyze_jax_callable(bad, jnp.ones(3)) is None

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("TRITON_TPU_COST_ANALYSIS", "0")
        assert costs.analysis_enabled() is False
        assert analyze_jax_callable(lambda x: x + 1, jnp.ones(3)) is None

    def test_signature_cost_to_dict_shape(self):
        d = SignatureCost(flops=2.0, bytes_accessed=3.0).to_dict()
        assert set(d) == {"flops", "bytes_accessed", "argument_bytes",
                          "output_bytes", "temp_bytes",
                          "generated_code_bytes"}


class TestCostLedger:
    def test_charge_totals_and_anonymous_row(self):
        led = CostLedger(enabled=True)
        led.charge("m", "a", device_us=10.0, flops=100.0, tokens=2,
                   kv_byte_seconds=1.5)
        led.charge("m", "", device_us=5.0, tokens=1)
        t = led.totals("m")
        assert t["device_us"] == 15.0
        assert t["tokens"] == 3
        snap = led.snapshot("m")
        assert snap["enabled"] is True
        assert set(snap["models"]["m"]) == {"a", ""}

    def test_disabled_ledger_is_a_noop(self):
        led = CostLedger(enabled=False)
        led.charge("m", "a", device_us=10.0)
        assert led.totals() == {"device_us": 0.0, "flops": 0.0,
                                "tokens": 0, "kv_byte_seconds": 0.0}
        assert led.snapshot()["models"] == {}

    def test_overflow_folding_preserves_totals(self):
        led = CostLedger(enabled=True)
        led.MAX_TRACKED_TENANTS = 2
        for i in range(5):
            led.charge("m", f"t{i}", device_us=1.0)
        snap = led.snapshot("m")["models"]["m"]
        assert set(snap) == {"t0", "t1", CostLedger.OVERFLOW_TENANT}
        assert snap[CostLedger.OVERFLOW_TENANT]["device_us"] == 3.0
        assert led.totals("m")["device_us"] == 5.0

    def test_merge_cost_snapshots_server_and_cluster_parity(self):
        from triton_client_tpu.cluster._client import \
            merge_cost_snapshots as cluster_merge

        snaps = [
            {"enabled": True, "models": {
                "m": {"a": {"device_us": 10.0, "flops": 1.0,
                            "tokens": 2, "kv_byte_seconds": 0.5}}}},
            {"enabled": True, "models": {
                "m": {"a": {"device_us": 5.0, "flops": 2.0,
                            "tokens": 1, "kv_byte_seconds": 0.25},
                      "b": {"device_us": 1.0, "flops": 0.0,
                            "tokens": 0, "kv_byte_seconds": 0.0}}}},
            "not-a-snapshot",  # a malformed replica must not kill the merge
        ]
        merged = merge_cost_snapshots(snaps)
        assert merged == cluster_merge(snaps)
        row = merged["models"]["m"]["a"]
        assert row["device_us"] == 15.0
        assert row["tokens"] == 3
        assert row["kv_byte_seconds"] == 0.75
        assert "b" in merged["models"]["m"]


# -- end-to-end: measured MFU for every zoo model ---------------------------

@pytest.fixture(scope="module")
def server():
    from triton_client_tpu.models import zoo
    from triton_client_tpu.server import ModelRegistry
    from triton_client_tpu.server.testing import ServerHarness

    registry = ModelRegistry()
    zoo.register_all(registry)
    with ServerHarness(registry) as h:
        yield h


def _infer_moe(server):
    import triton_client_tpu.http as httpclient
    from triton_client_tpu.models.language import moe_seq_len

    with httpclient.InferenceServerClient(server.http_url) as c:
        s = moe_seq_len()
        t = httpclient.InferInput("TOKENS", [1, s], "INT32")
        t.set_data_from_numpy(np.ones((1, s), np.int32))
        c.infer("moe_tpu", [t])


class TestMoeMfuEndToEnd:
    def test_moe_tpu_gets_measured_mfu_on_cpu_standin(self, server,
                                                      monkeypatch):
        # moe_tpu declares NO flops_per_inference (hand-counting the
        # routed expert FFNs would be wrong) — before XLA acquisition it
        # had no MFU at all; now the measured figure is the source.
        # Two infers: the first is the compile sighting (excluded from
        # the MFU window), the second is steady-state compute.  The CPU
        # has no peak-table row, so it stands in for a listed device.
        monkeypatch.setattr(costs, "_local_device_kind",
                            lambda: "TPU v5 lite")
        _infer_moe(server)
        _infer_moe(server)
        snap = server.core.device_stats.snapshot(model="moe_tpu")
        entry = snap["models"]["moe_tpu"]
        assert entry["flops_source"] == "measured"
        assert entry["flops_per_element"] > 0
        assert entry["flops_declared"] is None
        assert entry["live_mfu"] is not None
        assert entry["live_mfu"] > 0

    def test_mfu_absent_not_zero_when_analysis_disabled(self):
        from triton_client_tpu.models.language import make_moe_tpu
        from triton_client_tpu.server import ModelRegistry
        from triton_client_tpu.server.testing import ServerHarness

        saved = os.environ.get("TRITON_TPU_COST_ANALYSIS")
        os.environ["TRITON_TPU_COST_ANALYSIS"] = "0"
        try:
            registry = ModelRegistry()
            registry.register_model(make_moe_tpu())
            with ServerHarness(registry) as h:
                _infer_moe(h)
                entry = h.core.device_stats.snapshot(
                    model="moe_tpu")["models"]["moe_tpu"]
                # no measured figure, no declared figure -> MFU is
                # honestly absent, never a fabricated 0%
                assert entry["flops_source"] is None
                assert entry["live_mfu"] is None
        finally:
            if saved is None:
                os.environ.pop("TRITON_TPU_COST_ANALYSIS", None)
            else:
                os.environ["TRITON_TPU_COST_ANALYSIS"] = saved


class TestDebugSurfacesUnary:
    """The costs debug surface over both protocols against direct-path
    (unary) attribution, which charges the whole execute window to the
    requesting tenant."""

    def test_http_grpc_and_clients_agree(self, server):
        import triton_client_tpu.grpc as grpcclient
        import triton_client_tpu.http as httpclient

        ledger = server.core.cost_ledger
        ledger.reset()
        with httpclient.InferenceServerClient(server.http_url) as c:
            a = np.ones((1, 16), np.int32)
            i0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
            i0.set_data_from_numpy(a)
            i1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
            i1.set_data_from_numpy(a)
            c.infer("simple", [i0, i1], tenant="acme")
        snap = ledger.snapshot("simple")
        row = snap["models"]["simple"]["acme"]
        assert row["device_us"] > 0
        # HTTP debug endpoint
        with urllib.request.urlopen(
                f"http://{server.http_url}/v2/debug/costs?model=simple",
                timeout=30) as r:
            http_snap = json.loads(r.read())
        assert http_snap["models"]["simple"]["acme"]["device_us"] == \
            row["device_us"]
        # client helpers over both protocols
        with httpclient.InferenceServerClient(server.http_url) as c:
            assert c.get_costs("simple") == http_snap
        with grpcclient.InferenceServerClient(server.grpc_url) as c:
            assert c.get_costs("simple") == http_snap
        ledger.reset()

    def test_cluster_client_merges_replicas(self, server):
        from triton_client_tpu.cluster import ClusterClient

        ledger = server.core.cost_ledger
        ledger.reset()
        ledger.charge("simple", "acme", device_us=100.0, tokens=4)
        with ClusterClient([server.http_url], protocol="http") as cc:
            merged = cc.get_costs("simple")
        assert merged["models"]["simple"]["acme"]["device_us"] == 100.0
        assert merged["models"]["simple"]["acme"]["tokens"] == 4
        ledger.reset()
