"""The readers of the program's layer-boundary counters (the statistics
extension entries ``request``, ``queue_member``, ``batch_assembly``,
``executor_wait``, ``dispatch``, ``device_wait``, ``bucket_rows``, ``pause``).

``BENCHMARK.json`` does not declare them yet: ``result_line.build`` refuses a
traced run in which a declared metric reads ``None``, which is what these read
on a program without the counters (``PERF.md``, section 7).  The entries that
wait for that are ``PENDING`` below.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.files import load_json, load_module  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CELLS = ["bert_large.server", "bert_large.open"]
PENDING = [
    {"name": "scheduler.member_queue_ms", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "scheduler",
     "moves": "latency_p50_ms", "workloads": CELLS},
    {"name": "scheduler.pad_waste_pct", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "scheduler",
     "moves": "infer_per_s", "workloads": CELLS},
    {"name": "model_step.host_ms", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "model step",
     "moves": "latency_p50_ms", "workloads": CELLS},
    {"name": "frontend.self_ms", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "client wire and frontend",
     "moves": "latency_p50_ms", "workloads": CELLS},
    {"name": "host.pause_ms_per_s", "unit": "ms/s", "better": "lower",
     "source": "program_counter",
     "layer": "host runtime (event loop, collector)",
     "moves": "latency_p50_ms", "workloads": CELLS},
]
NAMES = [m["name"] for m in PENDING]

# 640 sequences in 24 executions that ran 768 rows, over a 4 s window
ROWS = 640
DELTA = {"inference_count": ROWS, "execution_count": 24,
         "queue.count": ROWS, "queue.ns": ROWS * 60_000_000,
         "request.count": ROWS, "request.ns": ROWS * 75_000_000,
         "queue_member.count": ROWS, "queue_member.ns": ROWS * 40_000_000,
         "batch_assembly.count": ROWS, "batch_assembly.ns": ROWS * 250_000,
         "executor_wait.count": ROWS, "executor_wait.ns": ROWS * 500_000,
         "dispatch.count": ROWS, "dispatch.ns": ROWS * 1_500_000,
         "device_wait.count": ROWS, "device_wait.ns": ROWS * 28_000_000,
         "bucket_rows.count": 768, "bucket_rows.ns": 0,
         "pause.count": 9, "pause.ns": 62_000_000}
TRACE = {"busy_s": 3.9, "window_s": 4.0}
WANT = {"scheduler.member_queue_ms": 40.0,
        "scheduler.pad_waste_pct": 100.0 * (1 - 640 / 768),
        "model_step.host_ms": 0.5 + 1.5 + 28.0,
        "frontend.self_ms": 75.0 - 40.0 - 0.25 - 30.0,
        "host.pause_ms_per_s": 62.0 / 4.0}


def _read(name: str, ctx: dict):
    return load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_gives_the_planted_value(name):
    ctx = {"stats_delta": DELTA, "trace": TRACE}
    assert _read(name, ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_returns_nothing_where_the_program_has_no_such_counter(
        name):
    """No context, a program without the extension (the accepted readers'
    own test hands every reader such a delta), and a window in which
    nothing ran: ``None``, and nothing raised."""
    parent = {k: v for k, v in DELTA.items()
              if k.split(".")[0] in ("inference_count", "execution_count",
                                     "queue", "success")}
    assert _read(name, {}) is None
    assert _read(name, {"trace": TRACE}) is None
    assert _read(name, {"stats_delta": parent, "trace": TRACE}) is None
    zero = dict.fromkeys(DELTA, 0)
    value = _read(name, {"stats_delta": zero, "trace": TRACE})
    if name == "host.pause_ms_per_s":
        assert value == 0.0   # no pause in the window is a reading: 0
    else:
        assert value is None  # a mean over no sequence is not


def test_frontend_self_time_is_the_request_less_its_children():
    ctx = {"stats_delta": dict(DELTA), "trace": TRACE}
    step = _read("model_step.host_ms", ctx)
    queue = _read("scheduler.member_queue_ms", ctx)
    assert _read("frontend.self_ms", ctx) == pytest.approx(
        75.0 - queue - 0.25 - step)
    # each child moves it by its own mean, whatever the others read
    ctx["stats_delta"]["device_wait.ns"] += ROWS * 2_000_000
    assert _read("frontend.self_ms", ctx) == pytest.approx(
        WANT["frontend.self_ms"] - 2.0)
    ctx["stats_delta"]["queue_member.ns"] -= ROWS * 5_000_000
    assert _read("frontend.self_ms", ctx) == pytest.approx(
        WANT["frontend.self_ms"] - 2.0 + 5.0)


def test_the_pending_entries_fit_the_benchmark_they_wait_for():
    """Each is a file beside the accepted readers, in the cells that report
    the end-to-end metric it moves, and under no accepted metric's name."""
    taken = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in PENDING:
        assert m["name"] not in taken
        assert callable(load_module("layer_metrics", m["name"]).read)
        moved = next(e for e in BENCH["end_to_end"]
                     if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
