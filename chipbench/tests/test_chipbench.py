"""The benchmark's own tests.  None needs a chip; none describes a topology.

Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q`` from the
checkout's root.
"""

from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, result_line, trace_reduce  # noqa: E402
from chipbench.files import HERE, Cell, load_json, load_module  # noqa: E402
from chipbench.tests import tiny_models  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

E2E = [{"name": "infer_per_s", "unit": "infer/s"},
       {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "model_step.mfu_pct", "unit": "%"},
         {"name": "device.idle_pct", "unit": "%"}]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 2400000000}
COMPARED = {"logit_rel_l2": {"value": 0.01, "limit": 0.05}}


def _build(declared, values, traced, device=None, **kw):
    device = dict(DEVICE, **(device or {}))
    if traced:
        device.setdefault("window_s", 4.0)
        device.setdefault("busy_s", 3.9)
    args = dict(correct=True, attempted=10, failed=0, device=device,
                traced=traced, compared=COMPARED)
    args.update(kw)
    return result_line.build(declared, values, **args)


def test_result_line_good_in_both_modes():
    line = _build(E2E, {"infer_per_s": 350.5, "setup_s": 21.0}, False)
    obj = json.loads(line)
    assert list(obj) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert obj["metrics"]["infer_per_s"] == {"value": 350.5,
                                             "unit": "infer/s"}
    traced = _build(LAYER, {"model_step.mfu_pct": 44.0,
                            "device.idle_pct": 2.5}, True,
                    breakdown={"device_ops": [["fusion.1", 1.5]],
                               "idle_gaps": []})
    obj = json.loads(traced)
    assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
    assert list(obj)[-1] == "compared"


@pytest.mark.parametrize("case", [
    "missing_metric", "busy_zero", "busy_over_window", "extra_key",
    "unit_with_space", "mfu_over_100", "nan_value", "platform_cpu",
    "trace_keys_untraced", "no_compared"])
def test_result_line_refuses(case):
    values = {"model_step.mfu_pct": 44.0, "device.idle_pct": 2.5}
    declared, device, traced, kw = LAYER, {}, True, {}
    if case == "missing_metric":
        values.pop("device.idle_pct")
    elif case == "busy_zero":
        device = {"busy_s": 0.0}
    elif case == "busy_over_window":
        device = {"busy_s": 4.0000001}
    elif case == "extra_key":
        device = {"idle_s": 0.1}
    elif case == "unit_with_space":
        declared = [dict(LAYER[0], unit="per cent"), LAYER[1]]
    elif case == "mfu_over_100":
        values["model_step.mfu_pct"] = 100.5
    elif case == "nan_value":
        values["device.idle_pct"] = float("nan")
    elif case == "platform_cpu":
        device = {"platform": "cpu"}
    elif case == "trace_keys_untraced":
        traced, declared = False, E2E
        values = {"infer_per_s": 1.0, "setup_s": 1.0}
        device = {"busy_s": 1.0, "window_s": 2.0}
    elif case == "no_compared":
        kw = {"compared": {}}
    with pytest.raises((result_line.ResultLineError, ValueError)):
        _build(declared, values, traced, device, **kw)


def test_validate_refuses_a_line_with_a_key_of_its_own():
    line = _build(E2E, {"infer_per_s": 350.5, "setup_s": 21.0}, False)
    obj = json.loads(line)
    obj["versions"] = {"jax": "0.9.0"}
    with pytest.raises(result_line.ResultLineError):
        result_line.validate(json.dumps(obj), E2E, False)


def test_trace_reduce_unites_overlaps_and_clips_to_the_window():
    plane = "/device:TPU:0"
    ops = [("while", 100, 600),       # holds the next two
           ("fusion.1", 150, 300), ("fusion.2", 300, 500),
           ("a", 620, 700), ("b", 650, 720),  # overlap on one line
           ("copy.1", -50, 40),       # straddles the window's start
           ("fusion.3", 900, 1200),   # straddles its end
           ("fusion.4", 2000, 2100)]  # wholly outside
    out = trace_reduce.reduce({plane: ops}, (0, 1000),
                              {plane: [("jit_step", 100, 600),
                                       ("jit_step", 900, 1200)]})
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx((40 + 500 + 100 + 100) * 1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    ops_by_name = dict(out["breakdown"]["device_ops"])
    assert ops_by_name["while"] == pytest.approx((500 - 150 - 200) * 1e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(260e-9)
    # a line whose events cover the window many times over is still <= it
    dense = [("op", i, i + 500) for i in range(0, 1000, 10)]
    full = trace_reduce.reduce({plane: dense}, (0, 1000))
    assert full["busy_s"] == full["window_s"]


@pytest.mark.parametrize("lines", [{}, {"/device:TPU:0": []},
                                   {"/device:TPU:0": [("op", 5000, 6000)]}])
def test_trace_reduce_fails_loudly_on_an_empty_device_plane(lines):
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce(lines, (0, 1000))


def test_every_cell_names_files_that_exist():
    assert BENCH["paths"] == ["chipbench"]
    for cell_row in BENCH["workloads"]:
        cell = Cell(cell_row["name"])
        for key in ("name", "config", "traffic"):
            assert NAME.match(cell_row[key])
        assert len(cell_row["why"]) <= 200
        served = cell.config["served"]
        assert set(cell.traffic["warm_batches"]) <= set(
            served["batch_buckets"])
        load_module("references", cell.config["reference"]).Reference
        assert callable(load_module("comparators",
                                    cell.config["compare"]).compare)
        assert callable(load_module("request_makers",
                                    served["requests"]).make)
        load_module("generators", cell.traffic["generator"]).Generator
        load_module("flop_counts", cell.config["flops"]).flops_per_inference
        assert cell.config["limits"]["logit_rel_l2"] > 0
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end:
            assert callable(load_module("end_to_end_metrics",
                                        m["name"]).read)
        for m in cell.per_layer:
            assert callable(load_module("layer_metrics", m["name"]).read)
            assert m["moves"] in names
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1


def test_the_yardstick_counts_what_the_issue_counted():
    cfg = Cell("bert_large.offline").config
    flops = load_module("flop_counts", cfg["flops"]).flops_per_inference(cfg)
    assert flops == pytest.approx(246.4e9, rel=1e-3)
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    assert peaks["bf16_flops_per_s"] / flops == pytest.approx(800, rel=2e-3)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    for m in BENCH["per_layer"]:
        assert load_module("layer_metrics", m["name"]).read({}) is None
    ctx = {"trace": {"busy_s": 2.0, "window_s": 4.0},
           "stats_delta": {"inference_count": 640, "execution_count": 20,
                           "queue.count": 640, "queue.ns": 640 * 5_000_000,
                           "success.count": 640,
                           "success.ns": 640 * 90_000_000},
           "records": np.array([[0, 0.0, 0.2, 1], [1, 0.0, 0.4, 1]]),
           "profiled": (1.0, 2.0),
           "flops_per_inference": 246.4e9, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12}}
    read = {m["name"]: load_module("layer_metrics", m["name"]).read(ctx)
            for m in BENCH["per_layer"]}
    assert read["device.idle_pct"] == pytest.approx(50.0)
    assert read["scheduler.batch_mean"] == pytest.approx(32.0)
    assert read["scheduler.queue_ms"] == pytest.approx(5.0)
    assert read["model_step.mfu_pct"] == pytest.approx(
        100 * 640 * 246.4e9 / (2.0 * 197e12))


def test_end_to_end_metrics_take_all_the_work_and_all_the_time():
    # index, sent, done, ok: one answer after the close, one failure
    rec = np.array([[0, 0.0, 1.0, 1], [1, 0.0, 2.0, 1], [2, 1.0, 3.0, 1],
                    [3, 2.0, 4.5, 1], [4, 2.0, 2.1, 0]], np.float64)
    run = {"records": rec, "window": (0.0, 4.0), "request_batch": 32,
           "setup_s": 12.5}
    read = {n: load_module("end_to_end_metrics", n).read(run)
            for n in ("infer_per_s", "latency_p50_ms", "setup_s")}
    assert read["infer_per_s"] == pytest.approx(3 * 32 / 4.0)
    assert read["latency_p50_ms"] == pytest.approx(2000.0)
    # the tail counts the answer that came after the close, and leaves out
    # what overlapped the profiler's time (here the request sent at 1.0)
    tail = load_module("layer_metrics", "latency_p99_ms").read
    assert tail({"records": rec, "profiled": (5.0, 6.0)}) > 2400.0
    assert tail({"records": rec, "profiled": (2.5, 3.5)}) == pytest.approx(
        1000.0 + 0.99 * 1000.0)
    assert read["setup_s"] == 12.5


def test_loadgen_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import chipbench.loadgen;"
            "from chipbench.files import load_module as lm;"
            "make = lm('request_makers', 'token_ids').make;"
            "lm('generators', 'unary').Generator('127.0.0.1:1',"
            " {'served': {}, 'vocab_size': 8},"
            " {'protocol': 'grpc', 'loop': 'closed', 'request_batch': 1,"
            " 'callers': 0}, 1, make);"
            "assert 'jax' not in sys.modules, 'jax imported'") % ROOT
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_an_open_loop_gives_every_seed_the_same_arrivals_in_another_order():
    unary = load_module("generators", "unary")
    traffic = {"rate_per_s": 40.0, "arrivals": "poisson"}
    a = unary.arrival_offsets(traffic, 4000000007, 5.0)
    b = unary.arrival_offsets(traffic, 11, 5.0)
    assert len(a) == len(b) == 200
    assert a[0] == 0.0 and 0 < a[-1] < 5.0 and (np.diff(a) > 0).all()
    gaps = [np.sort(np.diff(np.append(x, 5.0))) for x in (a, b)]
    assert np.allclose(gaps[0], gaps[1]) and not np.allclose(a, b)
    assert np.std(gaps[0]) > 0.5 * np.mean(gaps[0])  # not evenly spaced
    even = unary.arrival_offsets({"rate_per_s": 40.0,
                                  "arrivals": "constant"}, 3, 5.0)
    assert np.allclose(np.diff(even), 1 / 40.0)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "bert_large.offline", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == b""
    assert b"platform" in proc.stderr


def _tiny_root(tmp, factory, loop="closed"):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    cfg = copy.deepcopy(tiny_models.TINY)
    cfg["served"]["factory"] = "chipbench.tests.tiny_models:" + factory
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(tmp, "chipbench", "traffic", "few.json"),
              "w") as f:
        json.dump({"generator": "unary", "protocol": "grpc", "loop": loop,
                   "rate_per_s": 30.0, "arrivals": "poisson", "callers": 4,
                   "request_batch": 2, "warm_batches": [2, 4, 8],
                   "check_requests": 16, "trace_lead_s": 0.5,
                   "trace_seconds": 1.0}, f)
    bench = copy.deepcopy(BENCH)
    bench["configs"] = [{"name": "tiny", "source": "none", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "-"}]
    bench["workloads"] = [{"name": "tiny.few", "config": "tiny",
                           "traffic": "few", "chips": 1, "why": "-"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.few"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


@pytest.mark.parametrize("factory,control,loop,want", [
    ("make_tiny", False, "closed", True),
    # the same generator under arrivals at a fixed rate
    ("make_tiny", False, "open", True),
    # the control: the program's own int8 path in the program's place
    ("make_tiny", True, "closed", False),
    # the faults a served cell can have, planted under the timed path
    ("make_tiny_altered", False, "closed", False),
    ("make_tiny_rows_swapped", False, "closed", False),
])
def test_a_whole_run_decides_correct(tmp_path, monkeypatch, factory,
                                     control, loop, want):
    """Skips the look for a chip and drives the rest of a run."""
    import chipbench.run as run

    _tiny_root(str(tmp_path), factory, loop)
    monkeypatch.setattr(run, "memory_peak_bytes",
                        lambda devices, watch: 1 << 20)
    for key in tiny_models.TINY["control"]["env"]:
        monkeypatch.delenv(key, raising=False)
    try:
        line, compared = run.run_cell(
            "tiny.few", 4000000007, 2.0, False, platform="cpu",
            root=str(tmp_path), control=control)
    finally:
        for key in tiny_models.TINY["control"]["env"]:
            os.environ.pop(key, None)
    obj = json.loads(line)
    assert obj["correct"] is want, compared
    assert obj["attempted"] > 0 and obj["failed"] == 0
    if loop == "open":
        assert obj["attempted"] == 60  # rate x seconds, whatever the seed
    assert compared["logit_rel_l2"]["value"] is not None
    assert check.verdict(compared, obj["attempted"], 0, 0) is want
