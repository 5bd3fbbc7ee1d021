"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process opens the chip: it starts the served path (``served.py``) with
the configuration's model, warms the shapes the cell's traffic uses, and owns
the profiler and the memory reading.  The load comes from a child that never
imports JAX (``loadgen.py``).  When the window has closed and the server has
stopped, the plain reference runs over a sample of the answered requests and
decides ``correct`` (``check.py``).  The last line of standard output is built
in ``result_line.build`` or not printed at all; any failure on the way exits
non-zero with the reason on standard error.
"""

from __future__ import annotations

import time

_T0 = time.time()

import argparse  # noqa: E402
import io  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench import check, result_line, trace_reduce  # noqa: E402
from chipbench.files import (HERE, ROOT, BenchmarkError, Cell,  # noqa: E402
                             load_json, load_module)

SCRATCH = os.path.join(ROOT, ".chipbench_run")
CHILD_MARGIN_S = 90.0


def place_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed place inside the
    checkout, unless the environment already placed it.  The program takes
    the variable over its own default, so both sides use one directory."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax  # reads the variable when it is first imported: here

    # every program of the window, however small, is found again by the
    # next run of the cell: nothing compiles twice in one checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def open_devices(chips: int, platform: str):
    """The devices this cell runs on, or ``BenchmarkError`` when JAX finds
    another platform or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchmarkError(
            f"JAX found platform {devices[0].platform!r}, the benchmark "
            f"runs on {platform!r} only")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


class MemoryWatch:
    """Samples every chip's ``memory_stats()`` while the window runs.

    PJRT counts live arrays in ``bytes_in_use`` and keeps the loaded
    programs' temporaries in a reservation of its own (``bytes_reserved``)
    that ``peak_bytes_in_use`` never includes, so at any moment the chip
    holds their sum.  Each sample adds the two as one call returned them;
    the largest sum is kept for each chip."""

    def __init__(self, devices, every_s: float = 0.25):
        self._devices = devices
        self._every_s = every_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self.largest = [0] * len(devices)
        self.samples = 0

    def _loop(self):
        while not self._stop.wait(self._every_s):
            for k, d in enumerate(self._devices):
                stats = d.memory_stats() or {}
                if "bytes_in_use" in stats and "bytes_reserved" in stats:
                    held = int(stats["bytes_in_use"]) \
                        + int(stats["bytes_reserved"])
                    self.largest[k] = max(self.largest[k], held)
            self.samples += 1

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join()


def memory_peak_bytes(devices, watch: MemoryWatch) -> int:
    """The most the fullest chip held: the larger of the allocator's own
    ``peak_bytes_in_use`` (live arrays alone) and the largest sampled
    ``bytes_in_use + bytes_reserved``.  Both go to standard error."""
    peaks = []
    for k, d in enumerate(devices):
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise BenchmarkError(f"{d}: memory_stats() has no "
                                 "peak_bytes_in_use")
        if not watch.samples or not watch.largest[k]:
            raise BenchmarkError(
                f"{d}: no sample of bytes_in_use + bytes_reserved was "
                "taken during the window")
        plain = int(stats["peak_bytes_in_use"])
        say(f"{d}: peak_bytes_in_use {plain}, largest of {watch.samples} "
            f"samples of bytes_in_use + bytes_reserved {watch.largest[k]}")
        peaks.append(max(plain, watch.largest[k]))
    return max(peaks)


def say(text: str) -> None:
    print(f"chipbench [{time.time() - _T0:7.2f}s] {text}", file=sys.stderr,
          flush=True)


def start_loadgen(cell: Cell, url: str, seed: int, seconds: float):
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), "--url", url,
         "--config", cell.config_file, "--traffic", cell.traffic_file,
         "--seed", str(seed), "--seconds", str(seconds)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)


def bring_up(cell: Cell, platform: str, control: bool):
    """Open the chip and start the served path with the cell's shapes warm.
    Returns ``(devices, served)``; the caller stops ``served``."""
    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.makedirs(SCRATCH)
    place_compile_cache()
    devices = open_devices(cell.chips, platform)[:cell.chips]
    say("chip open")

    from chipbench.served import Served

    served = Served(cell.config)
    try:
        served.start(cell.config["control"]["env"] if control else None)
        say("server up")
        served.warm(cell.traffic["warm_batches"])
        say(f"warmed batches {cell.traffic['warm_batches']}")
    except BaseException:
        served.stop()
        raise
    return devices, served


def traced_window(served, lead_s: float, length_s: float, trace_dir: str):
    """Trace ``length_s`` seconds of the running window.  Returns the
    statistics' change between the traced window's two ends, and the host
    times between which the profiler was at work (from the call that
    started it until it had written its file)."""
    import jax

    time.sleep(lead_s)
    began = time.time()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.raise_error_on_start_failure = True
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_NAME):
            before = served.statistics()
            time.sleep(length_s)
            after = served.statistics()
    finally:
        jax.profiler.stop_trace()
    return {k: after[k] - before[k] for k in after}, (began, time.time())


def drive_window(cell: Cell, served, seed: int, seconds: float,
                 traced: bool, t0: float, watch=None) -> dict:
    """One window of the cell's traffic against the warmed server: the load
    generator's arrays, the set-up time up to its first request and, traced,
    the statistics' change over the traced window."""
    import numpy as np

    traffic = cell.traffic
    child = start_loadgen(cell, served.grpc_url, seed, seconds)
    try:
        if child.stdout.readline().strip() != b"ready":
            raise BenchmarkError("the load generator did not come up")
        child.stdin.write(b"go\n")
        child.stdin.flush()
        out = {"setup_s": time.time() - t0}
        if watch is not None:
            watch.start()
        if traced:
            lead = min(float(traffic["trace_lead_s"]), seconds / 4)
            length = min(float(traffic["trace_seconds"]), seconds / 2)
            out["stats_delta"], out["profiled"] = traced_window(
                served, lead, length, os.path.join(SCRATCH, "trace"))
        try:
            blob, _ = child.communicate(timeout=seconds + CHILD_MARGIN_S)
        except subprocess.TimeoutExpired:
            raise BenchmarkError("the load generator did not finish") from None
        if child.returncode != 0:
            raise BenchmarkError(
                f"the load generator exited with {child.returncode}")
    finally:
        if watch is not None:
            watch.stop()
        if child.poll() is None:
            child.kill()
            child.wait()
    arrays = dict(np.load(io.BytesIO(blob)))
    outputs = [o["name"] for o in cell.config["served"]["outputs"]]
    out["answers"] = [{name: arrays.pop(f"answer.{k}.{name}")
                       for name in outputs}
                      for k in range(len(arrays["sample"]))]
    out.update(arrays)
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             platform: str = "tpu", root: str = ROOT,
             control: bool = False):
    """Returns ``(result line, compared)``; raises where no valid line can
    be printed.  ``control`` puts the configuration's lower-precision path
    in the program's place (the benchmark's own runs never do)."""
    cell = Cell(workload, root)
    cfg, traffic = cell.config, cell.traffic
    devices, served = bring_up(cell, platform, control)
    peaks = load_json(HERE, "peaks.json").get(devices[0].device_kind)
    try:
        if traced and peaks is None:
            raise BenchmarkError(
                "chipbench/peaks.json has no row for device kind "
                f"{devices[0].device_kind!r}")
        watch = MemoryWatch(devices)
        load = drive_window(cell, served, seed, seconds, traced, _T0, watch)
        memory_peak = memory_peak_bytes(devices, watch)
        say("window closed, every answer in")
    finally:
        served.stop()

    records = load["records"]
    never = int(load["never"][0])
    attempted = len(records) + never
    failed = int((records[:, 3] == 0).sum()) + never
    if str(load["first_error"][0]):
        print(f"chipbench: first failed request: {load['first_error'][0]}",
              file=sys.stderr)

    trace = None
    if traced:
        trace = trace_reduce.reduce_trace_dir(os.path.join(SCRATCH, "trace"))
        say("trace reduced")
    shutil.rmtree(SCRATCH, ignore_errors=True)

    reference = load_module("references", cfg["reference"]).Reference(cfg)
    compared = check.compare(cfg, traffic, seed, load["sample"],
                             load["answers"], reference)
    correct = check.verdict(compared, attempted, failed, never)
    say(f"reference compared over {len(load['sample'])} requests")

    import jax

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": jax.device_count(),
              "memory_peak_bytes": memory_peak}
    values = {}
    if traced:
        device["window_s"] = trace["window_s"]
        device["busy_s"] = trace["busy_s"]
        ctx = {"trace": trace, "stats_delta": load["stats_delta"],
               "records": records, "profiled": load["profiled"],
               "config": cfg,
               "traffic": traffic, "peaks": peaks, "chips": cell.chips,
               "flops_per_inference": load_module(
                   "flop_counts", cfg["flops"]).flops_per_inference(cfg)}
        for m in cell.per_layer:
            values[m["name"]] = load_module(
                "layer_metrics", m["name"]).read(ctx)
    else:
        run = {"records": records, "window": tuple(load["window"]),
               "request_batch": int(traffic["request_batch"]),
               "setup_s": load["setup_s"], "config": cfg, "traffic": traffic}
        for m in cell.end_to_end:
            values[m["name"]] = load_module(
                "end_to_end_metrics", m["name"]).read(run)
    line = result_line.build(
        cell.declared(traced), values, correct=correct, attempted=attempted,
        failed=failed, device=device, traced=traced, compared=compared,
        breakdown=trace["breakdown"] if traced else None, platform=platform)
    return line, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        line, compared = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace))
    except (BenchmarkError, result_line.ResultLineError,
            trace_reduce.TraceError) as e:
        print(f"chipbench: no result: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    for name, entry in compared.items():
        print(f"chipbench compared {name}: value {entry['value']} "
              f"limit {entry['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
