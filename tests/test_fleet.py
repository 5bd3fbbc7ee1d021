"""Closed-loop fleet operations (server/fleet.py + the core/registry/
chaos/supervisor integration).

Layers under test:

* unit — autoscale spec parsing, restart-policy backoff/storm math,
  supervisor state file round trip, the resizable batcher semaphore,
* policy — the controller's scale-out/scale-in decisions on synthetic
  signals (hysteresis, cooldowns, bounds; injectable ``now``, no sleeps),
* chaos — the new ``worker_kill`` / ``load_fail`` fault kinds are
  deterministic, stamped into flight records, and control/data-plane
  scoped,
* rolling updates — stage-warm-flip-bake: a staged version is invisible
  and not-ready until promoted, the flip is atomic under live c=8
  traffic with zero caller-visible errors, and a deliberately-bad new
  version auto-rolls-back within the bake window,
* self-healing supervisor — a SIGKILLed ``--frontends`` worker is
  restarted with backoff, mid-c8-run, with zero caller-visible errors
  and the restart visible in ``nv_fleet_worker_restart_total``,
* acceptance — the ISSUE 13 fleet drill: a 2-replica ClusterHarness
  under ~2x overload with ``RetryPolicy(3)`` clients takes a seeded
  ``worker_kill`` plus a concurrent rolling update with zero
  caller-visible errors, the autoscaler's scale-out brings tier-0 burn
  back under the threshold, and the restarted replica's rejoin shows in
  the restart counter and triton-top.
"""

import asyncio
import io
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from contextlib import redirect_stdout

import numpy as np
import pytest

import triton_client_tpu.http as httpclient
from triton_client_tpu._resilience import RetryPolicy
from triton_client_tpu.models import zoo
from triton_client_tpu.server import (InferenceCore, InferError,
                                      InferRequest, ModelRegistry, PyModel,
                                      make_config)
from triton_client_tpu.server.chaos import ChaosInjector
from triton_client_tpu.server.device_stats import SloObjective
from triton_client_tpu.server.fleet import (FLEET_STATE_ENV,
                                            FleetController, RestartPolicy,
                                            SupervisorState,
                                            collect_fleet_rows,
                                            parse_autoscale_spec,
                                            worker_restart_counts)
from triton_client_tpu.server.testing import (ClusterHarness,
                                              ReplicaSupervisor,
                                              ServerHarness, free_port)
from triton_client_tpu.server.types import InputTensor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- unit: spec parsing ------------------------------------------------------

class TestParseAutoscale:
    def test_full_and_partial_bounds(self):
        assert parse_autoscale_spec("m=2..6") == ("m", (2, 6))
        assert parse_autoscale_spec("m=..3") == ("m", (1, 3))
        assert parse_autoscale_spec("m=2..") == ("m", (2, 8))
        assert parse_autoscale_spec("m=..") == ("m", (1, 8))

    @pytest.mark.parametrize("bad", ["m", "m=", "=2..4", "m=4..2",
                                     "m=0..4", "m=a..b", "m=3"])
    def test_junk_fails_loudly(self, bad):
        with pytest.raises(ValueError):
            parse_autoscale_spec(bad)


# -- unit: restart policy ----------------------------------------------------

class TestRestartPolicy:
    def test_backoff_doubles_and_caps(self):
        p = RestartPolicy(base_delay_s=0.5, max_delay_s=2.0,
                          storm_limit=10, window_s=100.0)
        delays = [p.on_crash(now=float(i)) for i in range(5)]
        assert delays == [0.5, 1.0, 2.0, 2.0, 2.0]

    def test_storm_fails_fast(self):
        p = RestartPolicy(storm_limit=3, window_s=10.0)
        assert p.on_crash(now=0.0) is not None
        assert p.on_crash(now=1.0) is not None
        assert p.on_crash(now=2.0) is None  # 3rd crash inside the window

    def test_window_aging_resets_backoff_and_storm(self):
        p = RestartPolicy(base_delay_s=0.5, storm_limit=3, window_s=10.0)
        assert p.on_crash(now=0.0) == 0.5
        assert p.on_crash(now=1.0) == 1.0
        # the worker then stays up long past the window: old crashes age
        # out, so the next crash is a fresh first crash, not a storm
        assert p.on_crash(now=100.0) == 0.5
        assert p.recent_crashes(now=100.0) == 1

    def test_storm_limit_one_restores_fail_fast(self):
        p = RestartPolicy(storm_limit=1)
        assert p.on_crash(now=0.0) is None

    def test_storm_limit_validated(self):
        with pytest.raises(ValueError):
            RestartPolicy(storm_limit=0)


# -- unit: supervisor state file --------------------------------------------

class TestSupervisorState:
    def test_round_trip_and_env_read(self, tmp_path, monkeypatch):
        path = str(tmp_path / "fleet-state.json")
        state = SupervisorState(path)
        assert worker_restart_counts(path) == {}
        assert state.record_restart("0") == 1
        assert state.record_restart("0") == 2
        assert state.record_restart("1") == 1
        assert worker_restart_counts(path) == {"0": 2, "1": 1}
        # the env-var path feeds the metrics renderer on every worker
        monkeypatch.setenv(FLEET_STATE_ENV, path)
        assert worker_restart_counts() == {"0": 2, "1": 1}
        monkeypatch.delenv(FLEET_STATE_ENV)
        assert worker_restart_counts() == {}

    def test_cache_tracks_file_changes(self, tmp_path):
        path = str(tmp_path / "fleet-state.json")
        state = SupervisorState(path)
        state.record_restart("2")
        assert worker_restart_counts(path) == {"2": 1}
        # rewrite with a bumped mtime: the mtime-keyed cache must refresh
        time.sleep(0.01)
        state.record_restart("2")
        assert worker_restart_counts(path)["2"] == 2

    def test_junk_file_reads_empty(self, tmp_path):
        path = str(tmp_path / "junk.json")
        with open(path, "w") as f:
            f.write("{not json")
        assert worker_restart_counts(path) == {}


# -- unit: resizable batcher parallelism ------------------------------------

def _blocking_batch_model(name, gate, started, lock):
    """max_batch_size=1 dynamic-batching model whose executions block on
    ``gate``; ``started`` counts entries so tests observe the live
    concurrency the in-flight semaphore admits."""
    cfg = make_config(
        name,
        inputs=[("IN", "INT32", [-1])],
        outputs=[("OUT", "INT32", [-1])],
        max_batch_size=1,
        preferred_batch_sizes=[1],
    )

    def fn(inputs, params):
        with lock:
            started[0] += 1
        gate.wait(timeout=30)
        return {"OUT": inputs["IN"]}

    return PyModel(cfg, fn)


def _req(model, n=1, input_name="IN"):
    return InferRequest(
        model_name=model,
        inputs=[InputTensor(input_name, "INT32", (1, n),
                            data=np.ones((1, n), np.int32))])


async def _settle(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        await asyncio.sleep(0.005)


class TestBatcherInstances:
    def test_set_instances_resizes_live_concurrency(self):
        gate = threading.Event()
        started = [0]
        lock = threading.Lock()
        registry = ModelRegistry()
        registry.register_model(
            _blocking_batch_model("scaly", gate, started, lock))
        core = InferenceCore(registry)
        ctl = FleetController(core, bounds={"scaly": (1, 8)})
        core.fleet = ctl

        async def main():
            ctl.scale_to("scaly", 2)
            tasks = [asyncio.create_task(core.infer(_req("scaly")))
                     for _ in range(6)]
            # exactly 2 executions admitted (the in-flight semaphore)
            await _settle(lambda: started[0] == 2, msg="2 started")
            await asyncio.sleep(0.1)
            assert started[0] == 2
            b = core._batchers["scaly@1"]
            assert b.instances == 2
            # scale OUT applies to the live batcher immediately
            ctl.scale_to("scaly", 4, direction="out")
            await _settle(lambda: started[0] == 4, msg="4 started")
            assert b.instances == 4
            # scale IN never drops queued work and never interrupts
            # running batches: everything completes
            ctl.scale_to("scaly", 1, direction="in")
            assert b._shrink_debt == 3
            gate.set()
            results = await asyncio.gather(*tasks)
            assert len(results) == 6
            assert all(r.outputs[0].data is not None for r in results)
            # the debt settled as batches finished; the semaphore now
            # admits exactly 1 at a time
            gate.clear()
            started[0] = 0
            more = [asyncio.create_task(core.infer(_req("scaly")))
                    for _ in range(3)]
            await _settle(lambda: started[0] == 1, msg="1 started")
            await asyncio.sleep(0.1)
            assert started[0] == 1
            gate.set()
            await asyncio.gather(*more)
            assert ctl.scale_events == {("scaly", "out"): 1,
                                        ("scaly", "in"): 1}
            await core.shutdown(drain_s=0.2)

        asyncio.run(main())

    def test_new_batcher_inherits_scaled_target(self):
        gate = threading.Event()
        gate.set()
        registry = ModelRegistry()
        registry.register_model(
            _blocking_batch_model("scaly", gate, [0], threading.Lock()))
        core = InferenceCore(registry)
        ctl = FleetController(core, bounds={"scaly": (1, 8)})
        core.fleet = ctl
        ctl.scale_to("scaly", 6)

        async def main():
            await core.infer(_req("scaly"))
            assert core._batchers["scaly@1"].instances == 6

        asyncio.run(main())


# -- policy: the control loop on synthetic signals ---------------------------

class TestAutoscalerPolicy:
    def _controller(self, **kw):
        registry = ModelRegistry()
        registry.register_model(zoo.make_custom_identity_int32())
        core = InferenceCore(registry)
        kw.setdefault("bounds", {"custom_identity_int32": (1, 6)})
        kw.setdefault("scale_out_cooldown_s", 1.0)
        kw.setdefault("scale_in_cooldown_s", 2.0)
        kw.setdefault("idle_cycles", 3)
        ctl = FleetController(core, **kw)
        core.fleet = ctl
        # synthetic signals (no real traffic): tests overwrite these
        ctl.burn = lambda name, now=None: None
        ctl.duty = lambda name, now=None: None
        ctl.queue_depth = lambda name: 0
        return core, ctl

    MODEL = "custom_identity_int32"

    def test_burn_breach_scales_out_with_cooldown(self):
        core, ctl = self._controller()
        ctl.burn = lambda name, now=None: 20.0  # >= default 14.4
        ctl.evaluate(now=100.0)
        assert ctl.desired_instances(self.MODEL) == 5
        # inside the cooldown: no second actuation
        ctl.evaluate(now=100.5)
        assert ctl.desired_instances(self.MODEL) == 5
        ctl.evaluate(now=101.5)
        assert ctl.desired_instances(self.MODEL) == 6
        # at the max bound: stays
        ctl.evaluate(now=103.0)
        assert ctl.desired_instances(self.MODEL) == 6
        assert ctl.scale_events[(self.MODEL, "out")] == 2

    def test_backlog_scales_out_without_slo(self):
        core, ctl = self._controller(queue_high=2.0)
        ctl.queue_depth = lambda name: 100
        ctl.evaluate(now=10.0)
        assert ctl.desired_instances(self.MODEL) == 5

    def test_shallow_backlog_is_hysteresis_dead_band(self):
        core, ctl = self._controller(queue_high=4.0)
        # 4 instances * queue_high 4 = 16; a backlog of 10 is normal
        # pipelining, not pressure — and duty 0.5 is not idle either
        ctl.queue_depth = lambda name: 10
        ctl.duty = lambda name, now=None: 0.5
        for t in range(20):
            ctl.evaluate(now=float(t * 10))
        assert ctl.desired_instances(self.MODEL) == 4
        assert ctl.scale_events == {}

    def test_sustained_idle_scales_in(self):
        core, ctl = self._controller(idle_cycles=3)
        ctl.duty = lambda name, now=None: 0.0
        # two idle evaluations are not enough (streak), the third acts
        ctl.evaluate(now=10.0)
        ctl.evaluate(now=11.0)
        assert ctl.desired_instances(self.MODEL) == 4
        ctl.evaluate(now=12.0)
        assert ctl.desired_instances(self.MODEL) == 3
        # scale-in cooldown: the streak keeps satisfying but the next
        # step waits for the (longer) in-cooldown
        ctl.evaluate(now=12.5)
        assert ctl.desired_instances(self.MODEL) == 3
        ctl.evaluate(now=15.0)
        ctl.evaluate(now=18.0)
        ctl.evaluate(now=21.0)
        assert ctl.desired_instances(self.MODEL) == 1
        # floor: never below min
        for t in range(10):
            ctl.evaluate(now=30.0 + 3 * t)
        assert ctl.desired_instances(self.MODEL) == 1

    def test_pressure_resets_idle_streak(self):
        core, ctl = self._controller(idle_cycles=2,
                                     scale_out_cooldown_s=100.0)
        ctl.duty = lambda name, now=None: 0.0
        ctl.evaluate(now=10.0)  # idle streak 1
        ctl.burn = lambda name, now=None: 20.0
        ctl.evaluate(now=11.0)  # breach: streak resets (no out: seeded
        # desired already actuated? no — cooldown never hit, scales out)
        ctl.burn = lambda name, now=None: None
        ctl.evaluate(now=12.0)  # idle again: streak restarts at 1
        assert ctl._idle_streak[self.MODEL] == 1

    def test_config_parameter_bounds(self):
        registry = ModelRegistry()
        cfg_model = zoo.make_custom_identity_int32()
        cfg_model.config.parameters[
            "autoscale.min_instances"].string_value = "2"
        cfg_model.config.parameters[
            "autoscale.max_instances"].string_value = "3"
        registry.register_model(cfg_model)
        core = InferenceCore(registry)
        ctl = FleetController(core)
        core.fleet = ctl
        assert ctl.bounds_for(self.MODEL) == (2, 3)
        # initial desired clamps the static default into the envelope
        assert ctl.desired_instances(self.MODEL) == 3
        # explicit CLI bounds win over config parameters
        ctl.bounds[self.MODEL] = (1, 6)
        assert ctl.bounds_for(self.MODEL) == (1, 6)

    def test_unbounded_model_untouched(self):
        core, ctl = self._controller(bounds={})
        ctl.burn = lambda name, now=None: 100.0
        ctl.queue_depth = lambda name: 1000
        ctl.evaluate(now=10.0)
        assert ctl.desired_instances(self.MODEL) is None
        assert ctl.scale_events == {}


# -- chaos: fleet fault kinds ------------------------------------------------

class TestChaosFleetKinds:
    def test_worker_kill_is_data_plane_and_deterministic(self):
        a = ChaosInjector(rate=0.5, kinds=["worker_kill", "error"], seed=7)
        b = ChaosInjector(rate=0.5, kinds=["worker_kill", "error"], seed=7)
        seq_a = [getattr(a.decide("m"), "kind", None) for _ in range(50)]
        seq_b = [getattr(b.decide("m"), "kind", None) for _ in range(50)]
        assert seq_a == seq_b  # same seed, same fault sequence
        assert "worker_kill" in seq_a

    def test_load_fail_never_fires_per_request(self):
        inj = ChaosInjector(rate=1.0, kinds=["load_fail"], seed=3)
        assert all(inj.decide("m") is None for _ in range(20))
        with pytest.raises(InferError, match="injected load failure"):
            inj.maybe_fail_load("m")
        assert inj.injected_by_model == {"m": 1}

    def test_load_fail_respects_max_faults_and_model_filter(self):
        inj = ChaosInjector(rate=1.0, kinds=["load_fail"], seed=3,
                            max_faults=1, models=["target"])
        inj.maybe_fail_load("other")  # filtered: no raise
        with pytest.raises(InferError):
            inj.maybe_fail_load("target")
        inj.maybe_fail_load("target")  # budget spent: no raise

    def test_worker_kill_fires_callback_and_stamps_flight_record(self):
        registry = ModelRegistry()
        registry.register_model(zoo.make_custom_identity_int32())
        core = InferenceCore(registry)
        core.chaos = ChaosInjector(rate=1.0, kinds=["worker_kill"],
                                   seed=1, max_faults=1)
        killed = []
        core.chaos.worker_kill_cb = lambda: killed.append(True)

        async def main():
            with pytest.raises(InferError) as ei:
                await core.infer(_req("custom_identity_int32", 4))
            assert ei.value.http_status == 503
            assert "worker kill" in str(ei.value)

        asyncio.run(main())
        assert killed == [True]
        rec = core.flight_recorder.snapshot(
            model="custom_identity_int32")["recent"][-1]
        assert rec["chaos"] == "worker_kill"

    def test_load_fail_injected_into_core_load(self):
        registry = ModelRegistry()
        registry.register_model(zoo.make_custom_identity_int32())
        core = InferenceCore(registry)
        core.chaos = ChaosInjector(rate=1.0, kinds=["load_fail"], seed=1,
                                   max_faults=1)

        async def main():
            with pytest.raises(InferError, match="injected load failure"):
                await core.load_model("custom_identity_int32")
            # budget spent: the retry lands clean and the model serves
            await core.load_model("custom_identity_int32")
            resp = await core.infer(
                _req("custom_identity_int32", 4, input_name="INPUT0"))
            assert resp.outputs[0].data is not None

        asyncio.run(main())


# -- rolling updates ---------------------------------------------------------

def _versioned_identity(name, version_tag, fail=False, warmup=False):
    """Identity-plus-tag model so tests can see WHICH version answered;
    ``fail=True`` builds the deliberately-bad new version."""
    kw = {}
    if warmup:
        kw["warmup"] = [{"name": "w", "batch_size": 1,
                         "inputs": {"IN": ("INT32", [4], "zero")}}]
    cfg = make_config(
        name,
        inputs=[("IN", "INT32", [-1])],
        outputs=[("OUT", "INT32", [-1])],
        max_batch_size=8,
        preferred_batch_sizes=[4],
        max_queue_delay_us=200,
        **kw)

    def fn(inputs, params):
        if fail:
            raise RuntimeError("bad version")
        return {"OUT": inputs["IN"] + np.int32(version_tag)}

    return PyModel(cfg, fn)


MODEL = "verid"


class TestRollingUpdate:
    def _core(self):
        registry = ModelRegistry()
        registry.register_model(_versioned_identity(MODEL, 0))
        core = InferenceCore(registry)
        ctl = FleetController(core, bake_s=0.2, bake_min_samples=4)
        core.fleet = ctl
        return core, ctl

    def test_staged_version_invisible_and_not_ready(self):
        core, ctl = self._core()
        registry = core.registry
        registry.stage_version(MODEL, _versioned_identity(MODEL, 100), "2")
        # not ready, not routed, not indexed, server readiness unaffected
        assert not registry.is_ready(MODEL, "2")
        assert registry.get(MODEL).served_version == "1"
        assert registry.get(MODEL).versions == ["1"]
        assert all(e["version"] != "2" for e in registry.index())
        assert not registry.any_loading()
        with pytest.raises(InferError):
            registry.get(MODEL, "2")
        # double-stage and stage-over-served are refused
        with pytest.raises(InferError):
            registry.stage_version(MODEL, _versioned_identity(MODEL, 1),
                                   "2")
        with pytest.raises(InferError):
            registry.stage_version(MODEL, _versioned_identity(MODEL, 1),
                                   "1")

    def test_completed_update_flips_and_keeps_old_addressable(self):
        core, ctl = self._core()

        async def main():
            # traffic against v1 first so a batcher exists to drain
            r = await core.infer(_req(MODEL, 4))
            np.testing.assert_array_equal(
                r.outputs[0].data, np.ones((1, 4), np.int32))
            outcome = await ctl.rolling_update(
                MODEL, _versioned_identity(MODEL, 100, warmup=True),
                bake_s=0.1)
            assert outcome == "completed"
            # the old default's batcher was drained and retired by the
            # commit (checked BEFORE any explicit-v1 request re-creates
            # a fresh one)
            assert f"{MODEL}@1" not in core._batchers
            # unversioned traffic now reaches v2...
            r2 = await core.infer(_req(MODEL, 4))
            np.testing.assert_array_equal(
                r2.outputs[0].data, np.ones((1, 4), np.int32) + 100)
            # ...the old version stays served and explicitly addressable
            req_v1 = _req(MODEL, 4)
            req_v1.model_version = "1"
            r1 = await core.infer(req_v1)
            np.testing.assert_array_equal(
                r1.outputs[0].data, np.ones((1, 4), np.int32))
            assert core.registry.get(MODEL).served_version == "2"
            assert core.registry.get(MODEL).versions == ["1", "2"]
            await core.shutdown(drain_s=0.2)

        asyncio.run(main())
        assert ctl.update_events == {(MODEL, "completed"): 1}
        rows = collect_fleet_rows(core)
        assert ({"model": MODEL}, 2) in rows["serving_version"]

    def test_warmup_failure_aborts_without_flip(self):
        core, ctl = self._core()

        async def main():
            bad = _versioned_identity(MODEL, 100, fail=True, warmup=True)
            with pytest.raises(InferError, match="warmup"):
                await ctl.rolling_update(MODEL, bad)
            # nothing flipped, nothing staged left behind
            assert core.registry.get(MODEL).served_version == "1"
            assert core.registry.staged_version(MODEL, "2") is None
            r = await core.infer(_req(MODEL, 4))
            assert r.outputs[0].data is not None

        asyncio.run(main())
        assert ctl.update_events == {(MODEL, "warmup_failed"): 1}

    def test_bad_version_auto_rolls_back_within_bake_window(self):
        core, ctl = self._core()

        async def main():
            update = asyncio.create_task(ctl.rolling_update(
                MODEL, _versioned_identity(MODEL, 100, fail=True),
                bake_s=5.0))
            # live traffic during the bake: the bad version fails it,
            # which is exactly the signal the bake watches
            deadline = time.monotonic() + 10.0
            while not update.done():
                assert time.monotonic() < deadline, "no rollback"
                try:
                    await core.infer(_req(MODEL, 4))
                except Exception:  # noqa: BLE001 — the bad version fails
                    pass
                await asyncio.sleep(0.01)
            assert await update == "rolled_back"
            # the default is v1 again and serves cleanly
            assert core.registry.get(MODEL).served_version == "1"
            assert core.registry.get(MODEL).versions == ["1"]
            r = await core.infer(_req(MODEL, 4))
            np.testing.assert_array_equal(
                r.outputs[0].data, np.ones((1, 4), np.int32))
            await core.shutdown(drain_s=0.2)

        asyncio.run(main())
        assert ctl.update_events == {(MODEL, "rolled_back"): 1}

    def test_slo_breach_during_bake_rolls_back(self):
        """With an SLO objective, the bake verdict is the burn rate —
        a new version that answers successfully but far over the latency
        target still rolls back."""
        registry = ModelRegistry()
        registry.register_model(_versioned_identity(MODEL, 0))
        core = InferenceCore(registry)
        # availability 0.95 -> error budget 0.05 -> an all-bad window
        # burns at 20, clearing the 14.4 threshold (0.9 would cap burn
        # at 10 and make breach unreachable)
        core.slo.set_objective(MODEL, SloObjective(p99_ms=5.0,
                                                   availability=0.95))
        ctl = FleetController(core, bake_s=5.0)
        core.fleet = ctl
        slow_cfg_model = _versioned_identity(MODEL, 100)
        inner = slow_cfg_model._fn

        def slow_fn(inputs, params):
            time.sleep(0.05)  # 10x the 5ms objective: every request bad
            return inner(inputs, params)

        slow_cfg_model._fn = slow_fn

        async def main():
            update = asyncio.create_task(
                ctl.rolling_update(MODEL, slow_cfg_model, bake_s=5.0))
            deadline = time.monotonic() + 10.0
            while not update.done():
                assert time.monotonic() < deadline, "no rollback"
                try:
                    await core.infer(_req(MODEL, 4))
                except InferError:
                    pass
            assert await update == "rolled_back"
            await core.shutdown(drain_s=0.2)

        asyncio.run(main())

    def test_stop_cancels_in_flight_bake(self):
        """Controller (and core) shutdown cancels a mid-bake update —
        the bake coroutine must not wake later and demote/drain against
        a torn-down core.  The flip itself stays (valid registry
        state)."""
        core, ctl = self._core()

        async def main():
            update = asyncio.create_task(ctl.rolling_update(
                MODEL, _versioned_identity(MODEL, 100), bake_s=60.0))
            deadline = time.monotonic() + 5.0
            while core.registry.get(MODEL).served_version != "2":
                assert time.monotonic() < deadline, "flip never happened"
                await asyncio.sleep(0.01)
            await core.shutdown(drain_s=0.2)  # stops the fleet layer
            assert update.cancelled() or update.done()
            # no outcome was recorded for the aborted bake
            assert ctl.update_events == {}
            assert MODEL not in ctl._updating

        asyncio.run(main())

    def test_warmup_failure_unloads_staged_instance(self):
        core, ctl = self._core()
        bad = _versioned_identity(MODEL, 100, fail=True, warmup=True)
        unloaded = []
        bad.unload = lambda: unloaded.append(True)

        async def main():
            with pytest.raises(InferError, match="warmup"):
                await ctl.rolling_update(MODEL, bad)

        asyncio.run(main())
        # the partially-warmed instance was freed promptly, like every
        # other staged-cleanup path
        assert unloaded == [True]

    def test_concurrent_update_refused(self):
        core, ctl = self._core()

        async def main():
            gate = asyncio.Event()

            async def slow_warmup(model):
                await gate.wait()
                return 0

            core._warmup_one = slow_warmup
            first = asyncio.create_task(ctl.rolling_update(
                MODEL, _versioned_identity(MODEL, 100), bake_s=0.0))
            await asyncio.sleep(0.01)
            with pytest.raises(InferError) as ei:
                await ctl.rolling_update(
                    MODEL, _versioned_identity(MODEL, 200))
            assert ei.value.http_status == 409
            gate.set()
            assert await first == "completed"

        asyncio.run(main())


class TestRollingUpdateLiveTraffic:
    def test_atomic_flip_under_c8_zero_errors(self):
        """The flip happens under live c=8 wire traffic: every response
        is a valid v1 or v2 answer, zero caller-visible errors, and the
        stream ends on v2."""
        registry = ModelRegistry()
        registry.register_model(_versioned_identity(MODEL, 0))
        h = ServerHarness(registry)
        h.start()
        try:
            ctl = FleetController(h.core, bake_s=0.2, bake_min_samples=4)
            h.core.fleet = ctl
            x = np.arange(4, dtype=np.int32).reshape(1, 4)
            errors, tags = [], set()
            stop = threading.Event()

            def worker():
                try:
                    with httpclient.InferenceServerClient(h.http_url) as c:
                        i0 = httpclient.InferInput("IN", [1, 4], "INT32")
                        i0.set_data_from_numpy(x)
                        while not stop.is_set():
                            out = c.infer(MODEL, [i0]).as_numpy("OUT")
                            tag = int(out[0, 0] - x[0, 0])
                            if tag not in (0, 100):
                                raise AssertionError(
                                    f"mixed-version answer: {out}")
                            tags.add(tag)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(repr(e))

            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(8)]
            for t in threads:
                t.start()
            time.sleep(0.4)  # v1 serving under load
            fut = asyncio.run_coroutine_threadsafe(
                ctl.rolling_update(
                    MODEL, _versioned_identity(MODEL, 100, warmup=True),
                    bake_s=0.3),
                h._loop)
            assert fut.result(timeout=30) == "completed"
            time.sleep(0.4)  # v2 serving under load
            stop.set()
            for t in threads:
                t.join(timeout=20)
            assert not errors, errors
            assert tags == {0, 100}  # both versions answered, correctly
            # post-flip traffic is v2-only
            with httpclient.InferenceServerClient(h.http_url) as c:
                i0 = httpclient.InferInput("IN", [1, 4], "INT32")
                i0.set_data_from_numpy(x)
                out = c.infer(MODEL, [i0]).as_numpy("OUT")
                np.testing.assert_array_equal(out, x + 100)
        finally:
            h.stop()


# -- self-healing supervisor (CLI --frontends) -------------------------------

def _wait_ready(port, timeout=90.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v2/health/ready",
                    timeout=2) as r:
                if r.status == 200:
                    return True
        except Exception:
            pass
        time.sleep(0.5)
    return False


class TestSupervisorSelfHealing:
    """Regression for the PR 10 fail-fast: one dead worker used to drain
    every sibling; now it is restarted with backoff and the fleet keeps
    serving."""

    N_WORKERS = 2

    def test_worker_kill_mid_c8_run_zero_caller_errors(self):
        http_port, grpc_port, metrics_port = (free_port(), free_port(),
                                              free_port())
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "triton_client_tpu.server", "--zoo",
             "--host", "127.0.0.1",
             "--http-port", str(http_port),
             "--grpc-port", str(grpc_port),
             "--metrics-port", str(metrics_port),
             "--frontends", str(self.N_WORKERS),
             "--worker-restart-window", "8",
             "--drain-timeout", "3"],
            cwd=REPO_ROOT, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        pids = {}
        lines = []

        def read_stdout():
            for line in proc.stdout:
                lines.append(line)
                if line.startswith("frontend worker ") and "pid" in line:
                    parts = line.split()
                    pids[int(parts[2].rstrip(":"))] = int(parts[-1])

        reader = threading.Thread(target=read_stdout, daemon=True)
        reader.start()
        try:
            assert _wait_ready(http_port), \
                "supervisor fleet not ready: " + "".join(lines[-20:])
            deadline = time.time() + 10
            while len(pids) < self.N_WORKERS and time.time() < deadline:
                time.sleep(0.1)
            assert len(pids) >= self.N_WORKERS, lines

            x = np.arange(16, dtype=np.int32).reshape(1, 16)
            y = np.ones((1, 16), dtype=np.int32)
            policy = RetryPolicy(max_attempts=3, retry_infer=True,
                                 initial_backoff_s=0.02, seed=5)
            errors, counts = [], [0] * 8
            stop = threading.Event()

            def worker(idx):
                try:
                    with httpclient.InferenceServerClient(
                            f"127.0.0.1:{http_port}") as c:
                        i0 = httpclient.InferInput("INPUT0", [1, 16],
                                                   "INT32")
                        i0.set_data_from_numpy(x)
                        i1 = httpclient.InferInput("INPUT1", [1, 16],
                                                   "INT32")
                        i1.set_data_from_numpy(y)
                        while not stop.is_set():
                            r = c.infer("simple", [i0, i1],
                                        retry_policy=policy)
                            np.testing.assert_array_equal(
                                r.as_numpy("OUTPUT0"), x + y)
                            counts[idx] += 1
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(f"worker {idx}: {e!r}")

            threads = [threading.Thread(target=worker, args=(i,),
                                        daemon=True) for i in range(8)]
            for t in threads:
                t.start()
            time.sleep(1.0)
            # SIGKILL one worker mid-run: a genuine crash, no drain
            victim = pids[0]
            os.kill(victim, signal.SIGKILL)
            # traffic continues through the sibling while the supervisor
            # restarts the victim with backoff
            time.sleep(3.0)
            stop.set()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors
            assert sum(counts) > 0 and all(c > 0 for c in counts)
            # the supervisor must NOT have failed fast
            assert proc.poll() is None, "".join(lines[-20:])

            # the restart is visible in nv_fleet_worker_restart_total on
            # a worker metrics surface (restarted worker rebinds its
            # port; the sibling's port answers either way)
            def restart_total():
                total = 0.0
                for i in range(self.N_WORKERS):
                    try:
                        text = urllib.request.urlopen(
                            f"http://127.0.0.1:{metrics_port + i}/metrics",
                            timeout=5).read().decode()
                    except Exception:
                        continue
                    for line in text.splitlines():
                        if line.startswith("nv_fleet_worker_restart_total"):
                            total += float(line.rsplit(" ", 1)[1])
                return total

            deadline = time.time() + 30
            while restart_total() < 1 and time.time() < deadline:
                time.sleep(0.5)
            assert restart_total() >= 1, "".join(lines[-30:])

            # ...and in triton-top (the fleet header counter); the sibling
            # may have reported the restart before the restarted worker
            # has rebound the port triton-top polls
            deadline = time.time() + 30
            while True:
                buf = io.StringIO()
                with redirect_stdout(buf):
                    rc = top_main(["--url", f"127.0.0.1:{metrics_port}",
                                   "--once", "--json"])
                if rc == 0 or time.time() > deadline:
                    break
                time.sleep(0.5)
            assert rc == 0
            snap = json.loads(buf.getvalue())
            assert snap["worker_restarts"] >= 1
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=10)


def top_main(argv):
    from triton_client_tpu.tools import top

    return top.main(argv)


# -- acceptance: the ISSUE 13 fleet drill ------------------------------------

DRILL_MODEL = "scaly"
SERVICE_S = 0.03


def _drill_model():
    cfg = make_config(
        DRILL_MODEL,
        inputs=[("IN", "INT32", [-1])],
        outputs=[("OUT", "INT32", [-1])],
        max_batch_size=1,
        preferred_batch_sizes=[1],
    )

    def fn(inputs, params):
        time.sleep(SERVICE_S)
        return {"OUT": inputs["IN"]}

    return PyModel(cfg, fn)


class TestFleetDrill:
    """Seeded fleet drill: 2-replica ClusterHarness at ~2x overload with
    RetryPolicy(3) clients; a seeded ``worker_kill`` plus a concurrent
    rolling update produce ZERO caller-visible errors; the autoscaler's
    scale-out returns tier-0 burn under the threshold inside the
    recovery window; the restarted replica's rejoin is visible in
    ``nv_fleet_worker_restart_total`` and triton-top."""

    def test_drill(self, monkeypatch):
        from triton_client_tpu.cluster import ClusterClient

        controllers = {}

        def factory():
            r = ModelRegistry()
            r.register_model(_drill_model())
            return r

        def core_setup(h):
            core = h.core
            core.slo.set_objective(
                DRILL_MODEL, SloObjective(p99_ms=SERVICE_S * 2e3,
                                          availability=0.95))
            ctl = FleetController(
                core, interval_s=0.1,
                bounds={DRILL_MODEL: (1, 4)},
                queue_high=2.0, scale_out_cooldown_s=0.25,
                scale_in_cooldown_s=60.0)
            core.fleet = ctl
            ctl.scale_to(DRILL_MODEL, 1)  # start pinned at min capacity
            ctl.start_on(h._loop)
            controllers[id(core)] = ctl

        with ClusterHarness(factory, n=2, core_setup=core_setup) as ch:
            sup = ReplicaSupervisor(ch)
            monkeypatch.setenv(FLEET_STATE_ENV, sup.state.path)
            # seeded worker_kill on replica 1: exactly one draw, wired
            # to the replica supervisor (kill -> backoff -> restart)
            inj = ChaosInjector(rate=1.0, kinds=["worker_kill"], seed=42,
                                max_faults=1)
            inj.worker_kill_cb = lambda: sup.crash(1)
            policy = RetryPolicy(max_attempts=3, retry_infer=True,
                                 initial_backoff_s=0.02, seed=9)
            errors = []
            stop = threading.Event()
            x = np.ones((1, 4), dtype=np.int32)

            def flood():
                try:
                    with ClusterClient(ch.http_urls, protocol="http",
                                       policy="least_outstanding",
                                       retry_policy=policy) as c:
                        i0 = httpclient.InferInput("IN", [1, 4], "INT32")
                        i0.set_data_from_numpy(x)
                        while not stop.is_set():
                            r = c.infer(DRILL_MODEL, [i0], priority=0,
                                        retry_policy=policy)
                            np.testing.assert_array_equal(
                                r.as_numpy("OUT"), x)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(repr(e))

            threads = [threading.Thread(target=flood, daemon=True)
                       for _ in range(8)]
            for t in threads:
                t.start()
            try:
                # overload at pinned capacity: burn must breach
                core0 = ch.harnesses[0].core
                deadline = time.monotonic() + 15.0
                while time.monotonic() < deadline:
                    burn = core0.slo.burn_rate(DRILL_MODEL, 300.0)
                    if burn is not None \
                            and burn >= core0.slo.burn_threshold:
                        break
                    time.sleep(0.05)
                else:
                    raise AssertionError("overload never breached")

                # drop the seeded worker_kill on replica 1 mid-run
                ch.chaos(1, inj)
                kill_t = time.monotonic()

                # concurrent rolling update on replica 0, under traffic
                fut = asyncio.run_coroutine_threadsafe(
                    controllers[id(core0)].rolling_update(
                        DRILL_MODEL, _drill_model(), bake_s=0.3),
                    ch.harnesses[0]._loop)
                assert fut.result(timeout=30) == "completed"

                # recovery: scale-out returns burn under the threshold
                recovery_deadline = time.monotonic() + 25.0
                recovered_at = None
                while time.monotonic() < recovery_deadline:
                    burns = [h.core.slo.burn_rate(DRILL_MODEL, 300.0)
                             for h in ch.harnesses if h is not None]
                    if burns and all(
                            b is None or b < core0.slo.burn_threshold
                            for b in burns):
                        recovered_at = time.monotonic()
                        break
                    time.sleep(0.1)
                assert recovered_at is not None, \
                    "burn never returned under the threshold"
                sup.join(timeout=20)
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=30)
            assert not errors, errors

            # the autoscaler actuated OUT on the loaded replica
            out_events = sum(
                ctl.scale_events.get((DRILL_MODEL, "out"), 0)
                for ctl in controllers.values())
            assert out_events >= 1
            assert controllers[id(core0)].desired_instances(
                DRILL_MODEL) > 1

            # the kill became a healed restart, visible in the counter...
            assert sup.state.counts() == {"1": 1}
            assert ch.harnesses[1] is not None  # replica is back
            assert recovered_at - kill_t < 25.0

            # ...on every surviving replica's /metrics...
            text = urllib.request.urlopen(
                f"http://{ch.http_urls[0]}/metrics",
                timeout=5).read().decode()
            assert 'nv_fleet_worker_restart_total{worker="1"} 1' in text

            # ...and in triton-top's fleet view
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = top_main(["--url", ch.http_urls[0],
                               "--url", ch.http_urls[1],
                               "--once", "--json"])
            assert rc == 0
            snap = json.loads(buf.getvalue())
            # EXACTLY 1: both replicas export the same fleet-global
            # counter (shared state file) and the fleet view must dedup
            # per worker, not sum the endpoints
            assert snap["worker_restarts"] == 1
            assert snap["models"][DRILL_MODEL]["instances"] >= 2
            assert snap["models"][DRILL_MODEL]["version"] == 2


class TestTopRestartAggregation:
    def test_fleet_dedups_shared_counters_per_worker(self):
        """Every worker of one supervised fleet exports the SAME
        fleet-global restart counters (shared state file): the fleet
        aggregate must dedup per worker label, not sum endpoints."""
        from triton_client_tpu.tools.top import aggregate_restarts

        per_url = {"a:1": {"0": 1.0, "1": 2.0},
                   "b:1": {"0": 1.0, "1": 2.0}}
        assert aggregate_restarts(per_url) == 3
        # disjoint fleets behind one console still sum across workers
        assert aggregate_restarts({"a:1": {"0": 1.0},
                                   "b:1": {"9": 2.0}}) == 3
        assert aggregate_restarts({"a:1": {}, "b:1": None or {}}) == 0


# -- metrics rows ------------------------------------------------------------

class TestFleetMetricRows:
    def test_rows_without_controller(self):
        registry = ModelRegistry()
        registry.register_model(zoo.make_custom_identity_int32())
        core = InferenceCore(registry)
        rows = collect_fleet_rows(core)
        assert rows["serving_version"] == \
            [({"model": "custom_identity_int32"}, 1)]
        assert rows["scale"] == [] and rows["rolling_update"] == []

    def test_restart_rows_from_env(self, tmp_path, monkeypatch):
        path = str(tmp_path / "state.json")
        SupervisorState(path).record_restart("3")
        monkeypatch.setenv(FLEET_STATE_ENV, path)
        registry = ModelRegistry()
        registry.register_model(zoo.make_custom_identity_int32())
        core = InferenceCore(registry)
        rows = collect_fleet_rows(core)
        assert rows["worker_restart"] == [({"worker": "3"}, 1)]
