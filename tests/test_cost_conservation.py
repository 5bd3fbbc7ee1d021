"""Cost-attribution conservation contract (ISSUE 16).

Drives a real batched-decode server with mixed-tenant generation traffic
and pins the two invariants the cost ledger promises:

* **Device-time conservation** — the per-tenant slot-share charges for a
  decode model sum to the tick profiler's recorded compute windows
  (within 5%; both sides observe the same ``t_done - t_disp0`` clock).
* **KV byte-seconds reconciliation** — ``nv_cost_kv_byte_seconds_total``
  is charged with exactly what the memory governor's pin/unpin
  integrator returns, so the ledger and the governor's own
  ``kv_byte_seconds`` dict agree by construction.

Plus the rider on the OpenAI frontend: ``usage.device_time_us`` carries
the real attributed microseconds for the request's generations.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np  # noqa: F401  (jax presence gate below)
import pytest

jax = pytest.importorskip("jax")

# Batched decode mode must be set BEFORE the zoo registers (DecodeModel
# reads it at construction); 4 slots so concurrent tenants share ticks.
_ENV = {
    "TRITON_TPU_DECODE_MODE": "batched",
    "TRITON_TPU_DECODE_SLOTS": "4",
    # prefix/KV cache on: the shared-prefix drill pins cached-block
    # residency on the PINNING tenant (cache blocks only unpin at
    # eviction, so the slot-pin reconciliation tests are unaffected)
    "TRITON_TPU_KV_CACHE_BYTES": str(64 << 20),
}


@pytest.fixture(scope="module")
def _env():
    saved = {k: os.environ.get(k) for k in _ENV}
    os.environ.update(_ENV)
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@pytest.fixture(scope="module")
def server(_env):
    from triton_client_tpu.models import zoo
    from triton_client_tpu.server import ModelRegistry
    from triton_client_tpu.server.testing import ServerHarness

    registry = ModelRegistry()
    zoo.register_all(registry)
    with ServerHarness(registry) as h:
        yield h


def _stream(server, body, headers=None, timeout=300):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(
        f"http://{server.http_url}/v2/models/llama_generate/generate_stream",
        data=json.dumps(body).encode(), headers=h)
    frames = []
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            if line.startswith(b"data: "):
                frames.append(json.loads(line[len(b"data: "):]))
    return frames


def _decode_compute_us(core, model="llama_decode"):
    """Tick profiler's cumulative compute windows for ``model``, in us."""
    with core.device_stats._lock:
        return sum(bs.compute_ns_total
                   for (m, _b), bs in core.device_stats._buckets.items()
                   if m == model) / 1e3


def _governor_kv(core, model="llama_decode"):
    return {t: v for (m, t), v in core.memory.kv_byte_seconds.items()
            if m == model}


class TestConservation:
    def test_mixed_tenant_device_time_sums_to_tick_windows(self, server):
        core = server.core
        base_us = _decode_compute_us(core)
        base_rows = dict(core.cost_ledger.snapshot()["models"].get(
            "llama_decode", {}))

        def drive(tenant, i):
            _stream(server, {"text_input": f"conserve {tenant} {i}",
                             "max_tokens": 8},
                    headers={"triton-tenant": tenant})

        threads = [threading.Thread(target=drive, args=(t, i))
                   for t in ("acme", "globex") for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

        rows = core.cost_ledger.snapshot()["models"]["llama_decode"]

        def delta(tenant, key):
            prev = (base_rows.get(tenant) or {}).get(key, 0.0)
            return rows[tenant][key] - prev

        # every tenant that generated got charged real device time,
        # at least one token per stream
        for tenant in ("acme", "globex"):
            assert delta(tenant, "device_us") > 0.0, tenant
            assert delta(tenant, "tokens") >= 2, tenant

        # conservation: attributed slot-shares sum to the tick windows.
        # Both sides clock the same dispatch interval, so the 5% contract
        # tolerance only has to absorb float rounding here.
        attributed = sum(delta(t, "device_us") for t in rows)
        window = _decode_compute_us(core) - base_us
        assert window > 0.0
        assert attributed == pytest.approx(window, rel=0.05)

    def test_kv_byte_seconds_reconcile_with_governor(self, server):
        core = server.core
        base_gov = _governor_kv(core)
        base_rows = dict(core.cost_ledger.snapshot()["models"].get(
            "llama_decode", {}))

        for i, tenant in enumerate(("acme", "globex")):
            _stream(server, {"text_input": f"kv {tenant} {i}",
                             "max_tokens": 6},
                    headers={"triton-tenant": tenant})

        # slot release (the unpin) rides the resolver thread; give it a
        # beat to close the final pins before reconciling
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with core.memory._lock:
                open_pins = len(core.memory._kv_pins)
            if open_pins == 0:
                break
            time.sleep(0.02)

        gov = _governor_kv(core)
        rows = core.cost_ledger.snapshot()["models"]["llama_decode"]
        for tenant in ("acme", "globex"):
            gov_d = gov.get(tenant, 0.0) - base_gov.get(tenant, 0.0)
            led_d = (rows[tenant]["kv_byte_seconds"]
                     - (base_rows.get(tenant) or {}).get(
                         "kv_byte_seconds", 0.0))
            assert gov_d > 0.0, tenant
            # charged with exactly what kv_unpin integrated — equality
            # by construction, not a sampling tolerance; the ledger
            # snapshot rounds to 6 decimals (one rounding per side of the
            # delta)
            assert led_d == pytest.approx(gov_d, rel=1e-9, abs=1.1e-6), tenant


def _await_slot_unpins(core):
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        with core.memory._lock:
            if not core.memory._kv_pins:
                return
        time.sleep(0.02)


class TestSharedPrefixPinning:
    def test_pinning_tenant_charged_until_eviction_no_double_charge(
            self, server):
        """Cached-block byte-seconds charge the tenant whose prefill
        COMMITTED the block, from commit until eviction; tenants that
        merely hit the block are never charged for its residency — and
        the eviction charge is exactly the governor integrator's return
        (conservation), bracketed by wall-clock residency bounds."""
        from triton_client_tpu.server import kvcache

        core = server.core
        cache = kvcache.get("llama_decode")
        assert cache is not None, "KV cache must be live for this drill"

        # >64 prompt tokens: the first block is unique to this prompt
        # (shorter prompts left-pad with zeros and share a block)
        prompt = "shared prefix pinning drill " * 4
        pinned_before = cache.stats()["pinned_bytes"]
        t_pin_lo = time.monotonic()
        frames = _stream(server, {"text_input": prompt, "max_tokens": 4},
                         headers={"triton-tenant": "pinner"})
        t_pin_hi = time.monotonic()
        pinned_by_drill = cache.stats()["pinned_bytes"] - pinned_before
        assert pinned_by_drill > 0

        # two riders hit the pinner's block — free rides, bit-identical
        hits0 = cache.stats()["hits"]
        for _ in range(2):
            warm = _stream(server,
                           {"text_input": prompt, "max_tokens": 4},
                           headers={"triton-tenant": "rider"})
            assert ([f["text_output"] for f in warm]
                    == [f["text_output"] for f in frames])
        assert cache.stats()["hits"] - hits0 == 2

        # measurable residency, then settle the riders' slot unpins so
        # the eviction charge is the ONLY delta across clear()
        time.sleep(0.25)
        _await_slot_unpins(core)
        gov0 = _governor_kv(core)
        rows0 = core.cost_ledger.snapshot()["models"]["llama_decode"]
        t_evict_lo = time.monotonic()
        cache.clear()
        t_evict_hi = time.monotonic()

        gov1 = _governor_kv(core)
        rows1 = core.cost_ledger.snapshot()["models"]["llama_decode"]

        def led_delta(tenant):
            a = (rows0.get(tenant) or {}).get("kv_byte_seconds", 0.0)
            b = (rows1.get(tenant) or {}).get("kv_byte_seconds", 0.0)
            return b - a

        # hits are not double-charged: eviction bills the rider nothing
        assert led_delta("rider") == 0.0
        # the pinning tenant pays, with exactly the governor's integral
        pinner = led_delta("pinner")
        gov_d = gov1.get("pinner", 0.0) - gov0.get("pinner", 0.0)
        assert pinner > 0.0
        assert pinner == pytest.approx(gov_d, rel=1e-9)
        # conservation vs wall clock: bytes x residency brackets the
        # charge (the 5% contract tolerance absorbs clock skew)
        lo = pinned_by_drill * (t_evict_lo - t_pin_hi)
        hi = pinned_by_drill * (t_evict_hi - t_pin_lo)
        assert lo * 0.95 <= pinner <= hi * 1.05


class TestOpenAIUsageCost:
    def test_completions_usage_reports_device_time(self, server):
        body = json.dumps({"model": "llama_generate", "prompt": "usage?",
                           "max_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://{server.http_url}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            out = json.loads(resp.read())
        usage = out["usage"]
        assert usage["completion_tokens"] == 4
        assert usage["device_time_us"] > 0.0
