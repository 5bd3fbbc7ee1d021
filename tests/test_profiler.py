"""Host profiler (server/profiler.py): sampling, folding, windows,
loop-lag probe, GC accounting, capture windows, and metric-row shapes.

Everything here is hermetic and fast: the sampler is driven either by a
real (short-lived) thread at a high rate or by calling ``_sample_once``
directly so assertions are deterministic.
"""

import asyncio
import gc
import sys
import threading
import time

import pytest

from triton_client_tpu.server.profiler import (DEFAULT_PROFILE_HZ,
                                               PROBE_INTERVAL_S,
                                               PROFILE_HZ_ENV, HostProfiler,
                                               classify_thread, dump_threads,
                                               fold_stack,
                                               profile_hz_from_env)


# -- unit: role classification ----------------------------------------------

class TestClassifyThread:
    @pytest.mark.parametrize("name,role", [
        ("llama-decode-worker", "decode"),
        ("llama-readback", "readback"),
        ("llama-gen", "readback"),
        ("MainThread", "frontend"),
        ("tc-tpu-server", "frontend"),
        ("tc-tpu-server-2", "frontend"),
        ("asyncio_0", "batcher"),
        ("ThreadPoolExecutor-0_1", "batcher"),
        ("tc-tpu-host-profiler", "other"),
        ("random-thread", "other"),
    ])
    def test_roles(self, name, role):
        assert classify_thread(name) == role


# -- unit: stack folding -----------------------------------------------------

def _inner_frame():
    return sys._getframe()


class TestFoldStack:
    def test_root_first_basename_colon_func(self):
        folded = fold_stack(_inner_frame())
        frames = folded.split(";")
        # the leaf is the innermost call; the root is the runner
        assert frames[-1] == "test_profiler.py:_inner_frame"
        assert any(f.startswith("test_profiler.py:") for f in frames)
        for f in frames:
            assert ":" in f and ";" not in f

    def test_depth_limit_truncates(self):
        def deep(n):
            if n == 0:
                return sys._getframe()
            return deep(n - 1)

        folded = fold_stack(deep(100), limit=8)
        assert len(folded.split(";")) == 8


# -- unit: env parsing -------------------------------------------------------

class TestHzFromEnv:
    def test_default_when_unset(self, monkeypatch):
        monkeypatch.delenv(PROFILE_HZ_ENV, raising=False)
        assert profile_hz_from_env() == DEFAULT_PROFILE_HZ

    def test_zero_disables(self, monkeypatch):
        monkeypatch.setenv(PROFILE_HZ_ENV, "0")
        assert profile_hz_from_env() == 0.0

    def test_negative_clamps_to_zero(self, monkeypatch):
        monkeypatch.setenv(PROFILE_HZ_ENV, "-5")
        assert profile_hz_from_env() == 0.0

    def test_junk_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv(PROFILE_HZ_ENV, "banana")
        assert profile_hz_from_env() == DEFAULT_PROFILE_HZ


# -- sampler -----------------------------------------------------------------

class _Parked:
    """A thread parked in a recognizable function until released."""

    def __init__(self, name):
        self.gate = threading.Event()
        self.thread = threading.Thread(target=self._park, name=name,
                                       daemon=True)
        self.thread.start()

    def _park(self):
        self.gate.wait(timeout=30)

    def release(self):
        self.gate.set()
        self.thread.join(timeout=5)


class TestSampler:
    def test_disabled_profiler_starts_no_thread(self):
        p = HostProfiler(hz=0)
        assert not p.enabled
        p.start()
        try:
            assert p._thread is None
            # GC accounting is registered even with the sampler off
            assert p._on_gc in gc.callbacks
        finally:
            p.stop()
        assert p._on_gc not in gc.callbacks

    def test_live_sampler_attributes_roles(self):
        worker = _Parked("m-decode-worker")
        p = HostProfiler(hz=200.0)
        p.start()
        try:
            deadline = time.monotonic() + 5.0
            while (p._samples_by_role.get("decode", 0) < 3
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            p.stop()
            worker.release()
        assert p._samples_by_role.get("decode", 0) >= 3
        # collapsed output is flamegraph grammar: "role;frames N"
        text = p.collapsed(role="decode")
        assert text
        for line in text.strip().splitlines():
            stack, _, count = line.rpartition(" ")
            assert stack.startswith("decode;")
            assert int(count) >= 1
        # the sampler never samples itself
        assert "tc-tpu-host-profiler" not in p.collapsed()

    def test_double_start_and_stop_are_idempotent(self):
        p = HostProfiler(hz=100.0)
        p.start()
        p.start()
        p.stop()
        p.stop()
        assert p._thread is None

    def test_max_stacks_overflow_folds(self):
        worker = _Parked("overflow-park")
        try:
            p = HostProfiler(hz=0, max_stacks=1)
            # ≥2 live threads with distinct stacks, cap of 1: the second
            # distinct stack must fold into ~overflow, not grow the epoch
            p._sample_once()
            text = p.collapsed()
        finally:
            worker.release()
        assert "~overflow" in text

    def test_epoch_rotation_keeps_previous_window(self):
        p = HostProfiler(hz=0, window_s=0.05)
        p._sample_once()
        first = dict(p._epoch)
        assert first
        time.sleep(0.08)
        p._sample_once()  # rotates: first epoch becomes previous
        assert p._prev_epoch == first
        # collapsed() still covers both epochs
        assert p.collapsed().strip()

    def test_top_stacks_sorted_and_bounded(self):
        p = HostProfiler(hz=0)
        for _ in range(3):
            p._sample_once()
        top = p.top_stacks(n=2)
        assert len(top) <= 2
        counts = [c for _, _, c in top]
        assert counts == sorted(counts, reverse=True)


# -- capture windows ---------------------------------------------------------

class TestCaptureWindow:
    def test_inline_capture_when_sampler_off(self):
        # hz=0 deployments still get incident captures: the capture
        # samples inline on the calling thread
        worker = _Parked("cap-decode-worker")
        p = HostProfiler(hz=0)
        try:
            text = p.capture_window(duration_s=0.2, hz=50.0)
        finally:
            worker.release()
        assert "decode;" in text
        for line in text.strip().splitlines():
            _, _, count = line.rpartition(" ")
            assert int(count) >= 1

    def test_capture_rides_live_sampler_with_boost(self):
        worker = _Parked("cap2-decode-worker")
        p = HostProfiler(hz=5.0)
        p.start()
        try:
            text = p.capture_window(duration_s=0.4, hz=100.0)
        finally:
            p.stop()
            worker.release()
        # at a boosted 100 Hz over 0.4s a parked thread lands many
        # samples; at the base 5 Hz it could get at most ~2
        decode = sum(int(line.rpartition(" ")[2])
                     for line in text.strip().splitlines()
                     if line.startswith("decode;"))
        assert decode >= 5
        # the capture sink is deregistered afterwards
        assert p._captures == []


# -- loop-lag probe ----------------------------------------------------------

class TestLoopProbe:
    def _run_loop(self):
        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True)
        t.start()
        return loop, t

    def test_probe_measures_a_blocked_loop(self):
        loop, t = self._run_loop()
        p = HostProfiler(hz=0)
        try:
            p.install_loop_probe(loop, name="lp", interval_s=0.02)
            # block the loop: every scheduled callback (the probe
            # included) now runs late by up to the block length
            loop.call_soon_threadsafe(time.sleep, 0.15)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                lag = p.loop_lag().get("lp", {})
                if lag.get("max_us", 0.0) > 50_000:
                    break
                time.sleep(0.01)
            assert p.loop_lag()["lp"]["max_us"] > 50_000
            rows = p.metric_rows()["loop_lag"]
            assert rows and rows[0][0] == {"loop": "lp"}
        finally:
            p._stop.set()  # probe stops rescheduling
            loop.call_soon_threadsafe(loop.stop)
            t.join(timeout=5)
            loop.close()

    def test_duplicate_probe_name_is_single_probe(self):
        loop, t = self._run_loop()
        p = HostProfiler(hz=0)
        try:
            p.install_loop_probe(loop, name="dup", interval_s=0.02)
            p.install_loop_probe(loop, name="dup", interval_s=0.02)
            assert list(p._loops) == ["dup"]
        finally:
            p._stop.set()
            loop.call_soon_threadsafe(loop.stop)
            t.join(timeout=5)
            loop.close()


# -- GC accounting -----------------------------------------------------------

class TestGcAccounting:
    def test_collect_lands_in_generation_rows(self):
        p = HostProfiler(hz=0)
        p.start()
        try:
            # retry: a manual collect silently no-ops (no callbacks) when
            # another thread's collection is in flight — possible under a
            # full-suite run with leaked daemon threads
            deadline = time.monotonic() + 5.0
            rows: dict = {}
            while time.monotonic() < deadline:
                gc.collect()
                rows = {labels["generation"]: value
                        for labels, value in p.metric_rows()["gc_pause"]}
                if rows.get("2", 0.0) > 0.0:
                    break
                time.sleep(0.01)
        finally:
            p.stop()
        assert rows.get("2", 0.0) > 0.0
        snap = p.snapshot()
        assert snap["gc"]["2"]["collections"] >= 1
        assert snap["gc"]["2"]["pause_us_total"] > 0.0


# -- pauses as events --------------------------------------------------------

def _plant_gc(p, seconds, generation=2):
    """The collector's hook, called as CPython calls it."""
    gc.disable()  # a real collection in between would take the start stamp
    try:
        p._on_gc("start", {"generation": generation})
        time.sleep(seconds)
        p._on_gc("stop", {"generation": generation})
    finally:
        gc.enable()


class TestPauses:
    def test_the_probe_sees_every_stall_of_20_ms(self):
        assert PROBE_INTERVAL_S == 0.02

    def test_long_pauses_are_kept_and_every_pause_is_charged(self):
        p = HostProfiler(hz=0)
        charged = []
        p.on_pause = charged.append
        t0 = time.monotonic_ns()
        _plant_gc(p, 0.006)
        _plant_gc(p, 0.0, generation=0)
        t1 = time.monotonic_ns()
        assert len(charged) == 2 and charged[0] >= 6e6 > charged[1] > 0
        (kept,) = p.pauses_between(t0, t1)   # 5 ms or more: an event
        assert kept["kind"] == "gc2"
        assert t0 <= kept["start_ns"] < kept["end_ns"] <= t1
        assert kept["end_ns"] - kept["start_ns"] == charged[0]
        assert p.pauses_between(t1, t1 + 10**9) == []
        # the totals count both, kept or not
        gc_snap = p.snapshot()["gc"]
        assert gc_snap["2"]["collections"] == 1
        assert gc_snap["0"]["collections"] == 1
        assert gc_snap["2"]["pause_us_total"] == charged[0] / 1e3

    def test_one_store_feeds_the_rows_the_series_and_the_recorder(self):
        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True)
        t.start()
        p = HostProfiler(hz=0)
        charged = []
        p.on_pause = charged.append
        try:
            p.install_loop_probe(loop, name="lp", interval_s=0.02)
            time.sleep(0.05)  # the probe is running, and on time
            t0 = time.monotonic_ns()
            loop.call_soon_threadsafe(time.sleep, 0.08)
            deadline = time.monotonic() + 5.0
            while not charged and time.monotonic() < deadline:
                time.sleep(0.005)
            pauses = p.pauses_between(t0, time.monotonic_ns())
        finally:
            p._stop.set()
            loop.call_soon_threadsafe(loop.stop)
            t.join(timeout=5)
            loop.close()
        (pause,) = pauses
        assert pause["kind"] == "loop:lp"
        late_ns = pause["end_ns"] - pause["start_ns"]
        assert 50e6 <= late_ns <= 200e6 and charged == [late_ns]
        assert p.loop_lag()["lp"]["max_us"] == late_ns / 1e3
        (point,) = p.snapshot()["loop_lag"]["lp"]["series"]
        assert point == {"ts_mono": pause["end_ns"] / 1e9,
                         "lag_us": late_ns / 1e3}
        assert p.metric_rows()["loop_lag"] == [({"loop": "lp"},
                                                late_ns / 1e3)]

    def test_a_late_probe_is_charged_net_of_the_collections_before_it(self):
        """A collection stalls the loop too: the firing it made late is an
        event of its own, but its milliseconds are charged once."""
        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True)
        t.start()
        p = HostProfiler(hz=0)
        charged = []
        p.on_pause = charged.append
        try:
            p.install_loop_probe(loop, name="lp", interval_s=0.02)
            time.sleep(0.05)
            t0 = time.monotonic_ns()
            loop.call_soon_threadsafe(_plant_gc, p, 0.08)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and len(
                    p.pauses_between(t0, time.monotonic_ns())) < 2:
                time.sleep(0.005)
            time.sleep(0.05)  # a firing or two more, on time again
            pauses = p.pauses_between(t0, time.monotonic_ns())
        finally:
            p._stop.set()
            loop.call_soon_threadsafe(loop.stop)
            t.join(timeout=5)
            loop.close()
        assert sorted(x["kind"] for x in pauses) == ["gc2", "loop:lp"]
        late = next(x for x in pauses if x["kind"] == "loop:lp")
        assert late["end_ns"] - late["start_ns"] >= 50e6
        # the collection, and of the late firing at most what the loop
        # took to come round after it
        assert charged[0] >= 80e6 and sum(charged) <= charged[0] + 10e6


# -- output surfaces ---------------------------------------------------------

class TestSurfaces:
    def test_metric_rows_shape(self):
        p = HostProfiler(hz=0)
        p._sample_once()
        rows = p.metric_rows()
        assert set(rows) == {"loop_lag", "gc_pause", "samples"}
        for labels, value in rows["samples"]:
            assert set(labels) == {"role"}
            assert value >= 1.0

    def test_snapshot_shape(self):
        p = HostProfiler(hz=0, window_s=12.5)
        p._sample_once()
        snap = p.snapshot()
        assert snap["hz"] == 0.0 and snap["enabled"] is False
        assert snap["window_s"] == 12.5
        assert snap["distinct_stacks"] >= 1
        assert snap["top_stacks"]
        entry = snap["top_stacks"][0]
        assert set(entry) == {"role", "stack", "samples"}

    def test_dump_threads_names_roles_and_frames(self):
        worker = _Parked("dump-decode-worker")
        try:
            text = dump_threads()
        finally:
            worker.release()
        assert "MainThread" in text
        assert "[role=frontend]" in text
        assert "dump-decode-worker" in text and "[role=decode]" in text
        # frames come from traceback.format_stack: file + line refs
        assert 'File "' in text
