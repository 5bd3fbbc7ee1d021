"""``device.idle_unexplained_pct``: the trace's idle share less what the
program's dry-time account (the statistics' ``dry_no_request``,
``dry_window``, ``dry_late``, ``dry_host`` entries) explains.  It stands on a
program without the account, where it reads the idle share whole, so the
parent's traced run prints a valid line; it is declared in the two cells
that idle and whose ``per_layer`` lists no accepted test pins.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import result_line  # noqa: E402
from chipbench.files import Cell, load_json, load_module  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
NAME = "device.idle_unexplained_pct"
CELLS = ["bert_large.server", "bert_large.open"]

# a 4 s window, the device busy 3.2 s of it; of the 0.8 s idle the account
# explains 0.5 s
TRACE = {"busy_s": 3.2, "window_s": 4.0}
PARENT = {"inference_count": 1000, "execution_count": 500,
          "queue.count": 1000, "queue.ns": 1000 * 5_000_000}
DRY = {"dry_no_request.count": 210, "dry_no_request.ns": 200_000_000,
       "dry_window.count": 300, "dry_window.ns": 180_000_000,
       "dry_late.count": 40, "dry_late.ns": 90_000_000,
       "dry_host.count": 480, "dry_host.ns": 30_000_000}


def _read(name: str, ctx: dict):
    return load_module("layer_metrics", name).read(ctx)


def test_it_reads_the_idle_share_less_what_the_account_explains():
    ctx = {"trace": TRACE, "stats_delta": {**PARENT, **DRY}}
    assert _read("device.idle_pct", ctx) == pytest.approx(20.0)
    assert _read(NAME, ctx) == pytest.approx(100.0 * (0.8 - 0.5) / 4.0)
    # each cause explains its own nanoseconds, whatever the others read
    ctx["stats_delta"]["dry_late.ns"] += 40_000_000
    assert _read(NAME, ctx) == pytest.approx(100.0 * (0.8 - 0.54) / 4.0)
    # a window's edges can book a little more than it shows: no floor at 0
    ctx["stats_delta"]["dry_no_request.ns"] += 300_000_000
    assert _read(NAME, ctx) == pytest.approx(-1.0)


@pytest.mark.parametrize("trace", [
    TRACE, {"busy_s": 3.956178523, "window_s": 4.001241637},
    {"busy_s": 3.233196146, "window_s": 4.00103133}])
def test_on_a_program_without_the_account_it_is_the_idle_share_whole(trace):
    """To the last bit: the driver's traced run of the parent prints both
    (the two other windows are the parent's, my chip run, PR 37, call A)."""
    ctx = {"trace": trace, "stats_delta": dict(PARENT)}
    assert _read(NAME, ctx) == _read("device.idle_pct", ctx)
    # an account that booked nothing explains nothing
    ctx["stats_delta"].update(dict.fromkeys(DRY, 0))
    assert _read(NAME, ctx) == _read("device.idle_pct", ctx)


def test_it_returns_nothing_without_a_trace_or_without_statistics():
    assert _read(NAME, {}) is None
    assert _read(NAME, {"trace": TRACE}) is None
    assert _read(NAME, {"stats_delta": {**PARENT, **DRY}}) is None


ACCEPTED = ["latency_p99_ms", "scheduler.queue_ms", "scheduler.batch_mean",
            "model_step.mfu_pct", "device.idle_pct", "moe.rows_per_token",
            "moe.busiest_over_mean", "mla_attention_roofline",
            "diffusion.passes_per_token", "diffusion.hbm_pct",
            "loop.steps_per_token", "loop.hbm_pct"]


def test_its_entry_fits_the_benchmark():
    """It was appended after the twelve accepted metrics (a later PR's go
    after it: nothing here holds it to stay the last), under a name of its
    own, beside ``device.idle_pct`` in the two cells that idle."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:len(ACCEPTED) + 1] == ACCEPTED + [NAME]
    assert (names + [m["name"] for m in BENCH["end_to_end"]]).count(NAME) == 1
    entry = BENCH["per_layer"][len(ACCEPTED)]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "infer_per_s",
        "workloads": CELLS}
    idle = BENCH["per_layer"][names.index("device.idle_pct")]
    assert entry["layer"] == idle["layer"] and entry["moves"] == idle["moves"]
    assert set(CELLS) <= set(idle["workloads"])
    cells = {w["name"] for w in BENCH["workloads"]}
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    for cell in CELLS:
        assert cell in cells and cell in moved.get("workloads", cells)
        assert [m["name"] for m in Cell(cell).per_layer][:6] == [
            "latency_p99_ms", "scheduler.queue_ms", "scheduler.batch_mean",
            "model_step.mfu_pct", "device.idle_pct", NAME]


@pytest.mark.parametrize("cell, names", [
    ("bert_large.offline", ["model_step.mfu_pct", "device.idle_pct"]),
    ("kimi_k2.prefill", [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "moe.rows_per_token", "moe.busiest_over_mean",
        "mla_attention_roofline"]),
    ("sdar_30b_a3b.blockgen", [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "moe.rows_per_token", "moe.busiest_over_mean",
        "diffusion.passes_per_token", "diffusion.hbm_pct"]),
    ("ouro_2_6b.loopgen", [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "loop.steps_per_token", "loop.hbm_pct"]),
])
def test_the_other_cells_report_what_they_did(cell, names):
    """The accepted metrics of the four other cells, in their order, and
    not this one (what a later PR appends to them is that PR's)."""
    reported = [m["name"] for m in Cell(cell).per_layer]
    assert reported[:len(names)] == names and NAME not in reported


@pytest.mark.parametrize("delta", [PARENT, {**PARENT, **DRY}],
                         ids=["parent", "change"])
def test_a_traced_line_with_it_is_valid_on_both_sides(delta):
    """What ``run.run_cell`` does with the readers' values, on the parent's
    statistics and on the change's: ``build`` refuses a declared metric
    that reads ``None``, and this one never does beside a trace."""
    cell = Cell("bert_large.open")
    ctx = {"trace": TRACE, "stats_delta": delta}
    values = {m["name"]: 1.0 for m in cell.per_layer}
    for name in ("device.idle_pct", NAME):
        values[name] = _read(name, ctx)
    line = result_line.build(
        cell.declared(True), values, correct=True, attempted=10, failed=0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, **TRACE},
        traced=True, compared={"logit_rel_l2": {"value": 0.01,
                                                "limit": 0.02}},
        breakdown={"device_ops": [], "idle_gaps": []})
    metrics = result_line.validate(line, cell.declared(True), True)["metrics"]
    assert metrics[NAME]["unit"] == "%"
    assert metrics[NAME]["value"] <= metrics["device.idle_pct"]["value"]
