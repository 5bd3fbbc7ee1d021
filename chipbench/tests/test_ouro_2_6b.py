"""The ``ouro_2_6b`` configuration and its cell: the file against the
catalog row it copies, the byte count of the deployment against its
arithmetic, the FLOP and byte counts by hand, the two new readers on made-up
counters, the comparator on made-up answers, and a whole run of the tiny
model on the CPU that has to come out correct (and its int8 control and two
planted faults not)."""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check  # noqa: E402
from chipbench.files import Cell, load_json, load_module  # noqa: E402
from chipbench.tests import tiny_ouro  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CFG = load_json(ROOT, "chipbench", "configs", "ouro_2_6b.json")
CELL = "ouro_2_6b.loopgen"

# the ``config`` of the catalog's row "Ouro-2.6B" (model-configs guide,
# architectures.jsonl), copied whole
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"


def test_every_key_is_the_catalogs_and_nothing_is_reduced():
    (row,) = [c for c in BENCH["configs"] if c["name"] == "ouro_2_6b"]
    assert row["source"] == CFG["source"] and row["source"].startswith(SOURCE)
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200
    assert row["reduced"] == CFG["reduced"] == []
    for key, value in CATALOG.items():
        assert CFG[key] == value, key
    # the wiring the config does not fix is assumed, line by line
    assert set(CFG["assumed"]) >= {
        "projections", "norms", "final_norm", "exit_gate", "cache",
        "rotary_layout", "generation", "seq_len", "weights"}


def test_the_deployment_is_the_arithmetic_of_the_whole_model():
    D, H, dh, F, L, V, T = (CFG["hidden_size"], CFG["num_attention_heads"],
                            CFG["head_dim"], CFG["intermediate_size"],
                            CFG["num_hidden_layers"], CFG["vocab_size"],
                            CFG["total_ut_steps"])
    work = load_module("flop_counts", "ouro_2_6b")
    assert 4 * D * H * dh == 16_777_216 and 3 * D * F == 34_603_008
    assert work.layer_params(CFG) == 51_380_224
    held = L * (work.layer_params(CFG) + 4 * D) + 2 * V * D + 2 * D + 1
    assert held == 2_667_974_657
    assert 2 * held == pytest.approx(5.336e9, rel=1e-4)
    assert 2 * held / 16e9 == pytest.approx(0.333, abs=1e-3)
    assert "5.336 GB" in CFG["deployment"]["bytes"]
    # keys and values of one position: 192 pairs of 16 heads of 128
    assert work.cache_bytes_per_position(CFG) == 1_572_864 == \
        T * L * 2 * H * dh * 2
    assert "1,572,864 B" in CFG["deployment"]["cache"]
    # a batch of 16 at 144 positions
    assert 16 * 144 * work.cache_bytes_per_position(CFG) == pytest.approx(
        3.624e9, rel=1e-3)


def test_the_flop_and_byte_counts_by_hand():
    work = load_module("flop_counts", "ouro_2_6b")
    # the issue's arithmetic: about 2.84 TFLOP a request
    assert work.flops_per_inference(CFG) == pytest.approx(2.841e12, rel=1e-3)
    matrices = 143 * 4 * 2 * 48 * 51_380_224
    scores = 4 * 48 * 16 * 2 * 2 * 128 * (128 * 129 // 2
                                          + sum(range(129, 144)))
    head = 16 * 2 * 2048 * 49152
    assert work.flops_per_inference(CFG) == matrices + scores + head
    cfg = tiny_ouro.TINY_OURO
    # D 64, 4 heads of 16, a SwiGLU of 176, 2 layers x 3 steps, vocabulary
    # 256, prompt 16, 6 new tokens
    layer = 4 * 64 * 64 + 3 * 64 * 176
    assert work.layer_params(cfg) == layer
    pairs = 16 * 17 // 2 + 17 + 18 + 19 + 20 + 21
    assert work.flops_per_inference(cfg) == (
        3 * 2 * 2 * layer * 21 + 3 * 2 * 4 * 2 * 2 * 16 * pairs
        + 6 * 2 * 64 * 256)
    position = 2 * 3 * 2 * 2 * 4 * 16
    assert work.cache_bytes_per_position(cfg) == position
    weights = 2 * (3 * 2 * layer + 64 * 256)
    assert work.prefill_bytes(cfg, 5) == weights + 2 * 5 * 16 * 64 \
        + 5 * 16 * position
    assert work.step_bytes(cfg, 5, 18) == weights + 5 * 19 * position
    assert work.generation_bytes(cfg, 5) == work.prefill_bytes(cfg, 5) + sum(
        work.step_bytes(cfg, 5, 16 + i) for i in range(1, 6))
    # the published model: a decode step reads the layers' 4.93 GB four
    # times and the head (the issue's 19.73 GB + 0.2), and at 16 sequences
    # the cache of 136 positions (the issue's 4.2 ms at 819 GB/s)
    assert 4 * 2 * 48 * work.layer_params(CFG) == pytest.approx(19.73e9,
                                                                rel=1e-3)
    assert work.step_bytes(CFG, 16, 135) - work.step_bytes(CFG, 0, 0) \
        == pytest.approx(4.2e-3 * 819e9, rel=0.01)
    assert work.step_bytes(CFG, 16, 135) == pytest.approx(23.35e9, rel=1e-3)


def test_the_cell_reports_what_the_issue_named():
    cell = Cell(CELL)
    assert cell.chips == 1
    (row,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert row == BENCH["workloads"][-1] and len(row["why"]) <= 200
    traffic = cell.traffic
    assert (traffic["generator"], traffic["protocol"], traffic["callers"],
            traffic["loop"], traffic["request_batch"],
            traffic["warm_batches"], traffic["check_requests"],
            traffic["trace_lead_s"], traffic["trace_seconds"]) == (
        "unary", "grpc", 32, "closed", 1, [8, 16], 8, 3.0, 8.0)
    served = cell.config["served"]
    assert (served["seq_len"], served["new_tokens"],
            served["batch_buckets"]) == (128, 16, [8, 16])
    assert [m["name"] for m in cell.end_to_end] == ["infer_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "loop.steps_per_token", "loop.hbm_pct"]
    assert all(m["moves"] == "infer_per_s" and m["layer"] in (
        "scheduler", "model step", "device") for m in cell.per_layer)
    # the accepted cells keep the metrics they had
    assert [m["name"] for m in Cell("sdar_30b_a3b.blockgen").per_layer] == [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "moe.rows_per_token", "moe.busiest_over_mean",
        "diffusion.passes_per_token", "diffusion.hbm_pct"]
    assert [m["name"] for m in Cell("bert_large.offline").per_layer] == [
        "model_step.mfu_pct", "device.idle_pct"]


def _ctx():
    """A traced window of eight batches of 16."""
    executions, batch = 8, 16
    return {
        "trace": {"busy_s": 7.9, "window_s": 8.0},
        "stats_delta": {
            "inference_count": executions * batch,
            "execution_count": executions,
            "loop_steps.count": executions * batch * 4 * 143,
            "loop_tokens.count": executions * batch * 143},
        "config": CFG, "chips": 1,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }


def test_the_new_readers_on_made_up_counters():
    work = load_module("flop_counts", "ouro_2_6b")
    ctx = _ctx()
    per_token = load_module("layer_metrics", "loop.steps_per_token")
    assert per_token.read(ctx) == pytest.approx(4.0)
    early = copy.deepcopy(ctx)
    early["stats_delta"]["loop_steps.count"] //= 2
    assert per_token.read(early) == pytest.approx(2.0)
    hbm = load_module("layer_metrics", "loop.hbm_pct")
    moved = 8 * (work.prefill_bytes(CFG, 16) + sum(
        work.step_bytes(CFG, 16, 128 + i) for i in range(1, 16)))
    assert hbm.read(ctx) == pytest.approx(100 * moved / (7.9 * 819e9),
                                          rel=1e-9)
    assert 40 < hbm.read(ctx) < 60
    # a program that lacks a counter (the parent): nothing, and no raise
    for missing in ("loop_steps.count", "loop_tokens.count"):
        short = copy.deepcopy(ctx)
        del short["stats_delta"][missing]
        assert per_token.read(short) is None
        if missing == "loop_tokens.count":
            assert hbm.read(short) is None
    for reader in (per_token, hbm):
        assert reader.read({}) is None
        assert reader.read({"stats_delta": {}, "trace": None}) is None


class _Replay:
    def __init__(self, want, pdf):
        self.want, self.pdf = want, pdf

    def replay(self, ids, tokens):
        assert tokens.shape == (len(ids), 16)
        return {"logits": self.want, "exit_pdf": self.pdf}


def _answers(rng, rows_of, pdf):
    """Answers consistent with ``rows_of [N,2,V]``: the arg-max tokens at
    the two ends."""
    answers = []
    for rows, p in zip(rows_of, pdf):
        tokens = rng.integers(0, 64, 16)
        tokens[0], tokens[-1] = rows[0].argmax(), rows[1].argmax()
        answers.append({"TOKENS": tokens[None].astype(np.int32),
                        "LOGITS": rows[None].astype(np.float32),
                        "EXIT_PDF": p[None].astype(np.float32)})
    return answers


def test_the_comparison_is_by_row_and_holds_tokens_and_gate():
    compare = load_module("comparators", "logit_rel_l2_forced").compare
    rng = np.random.default_rng(0)
    want = rng.standard_normal((8, 2, 64))
    pdf = rng.dirichlet(np.ones(4), (8, 2))
    inputs = [{"INPUT_IDS": np.zeros((1, 4), np.int32)} for _ in range(8)]
    # what the chip reads: the program 0.16-0.20 a row, the control 0.55-0.61
    got = want * (1 + 0.18 * rng.standard_normal(want.shape))
    near = pdf + 1e-3 * np.array([1, -1, 1, -1])
    answers = _answers(rng, got, near)
    out = compare(CFG, inputs, answers, _Replay(want, pdf))
    assert set(out) == set(CFG["limits"])
    assert out["logit_rel_l2_median"]["value"] == pytest.approx(0.18,
                                                                rel=0.25)
    assert out["exit_pdf_abs_worst"]["value"] == pytest.approx(1e-3, rel=0.01)
    assert out["token_inconsistent"] == {"value": 0, "limit": 0}
    assert check.verdict(out, 8, 0, 0)
    # every row further out, as a lower precision reads
    far = want * (1 + 0.55 * rng.standard_normal(want.shape))
    out = compare(CFG, inputs, _answers(rng, far, near), _Replay(want, pdf))
    assert out["logit_rel_l2_median"]["value"] > CFG["limits"][
        "logit_rel_l2_median"]
    assert not check.verdict(out, 8, 0, 0)
    # a token that is not the arg-max of the row returned with it
    for end in (0, -1):
        wrong = _answers(rng, got, near)
        wrong[5]["TOKENS"][0, end] += 1
        out = compare(CFG, inputs, wrong, _Replay(want, pdf))
        assert out["token_inconsistent"]["value"] == 1
        assert not check.verdict(out, 8, 0, 0)
    # a gate that reads another step's state, and one that sums to no 1
    turned = _answers(rng, got, np.roll(pdf, 1, axis=-1))
    out = compare(CFG, inputs, turned, _Replay(want, pdf))
    assert out["exit_pdf_abs_worst"]["value"] > CFG["limits"][
        "exit_pdf_abs_worst"]
    assert not check.verdict(out, 8, 0, 0)
    short = _answers(rng, got, near * 0.9)
    assert compare(CFG, inputs, short, _Replay(want, pdf))[
        "token_inconsistent"]["value"] == 8
    # two callers given each other's answers
    swapped = [answers[i] for i in (1, 0, 2, 3, 4, 5, 6, 7)]
    out = compare(CFG, inputs, swapped, _Replay(want, pdf))
    assert out["logit_rel_l2_worst"]["value"] > 1.0
    assert not check.verdict(out, 8, 0, 0)
    assert compare(CFG, [], [], _Replay(want, pdf))["logit_rel_l2_median"][
        "value"] is None


def _tiny_root(tmp, factory):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    cfg = copy.deepcopy(tiny_ouro.TINY_OURO)
    cfg["served"]["factory"] = "chipbench.tests.tiny_ouro:" + factory
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = load_json(ROOT, "chipbench", "traffic", "loopgen.json")
    with open(os.path.join(tmp, "chipbench", "traffic", "few.json"),
              "w") as f:
        json.dump(traffic, f)
    bench = copy.deepcopy(BENCH)
    bench["configs"] = [{"name": "tiny", "source": "none", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "-"}]
    bench["workloads"] = [{"name": "tiny.few", "config": "tiny",
                           "traffic": "few", "chips": 1, "why": "-"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:  # what the cell lists, the tiny cell lists
            m["workloads"] = ["tiny.few"] if CELL in m["workloads"] else []
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


@pytest.mark.parametrize("factory,control,want", [
    ("make_tiny_ouro", False, True),
    # the control: the program's own int8 storage in the program's place
    ("make_tiny_ouro", True, False),
    # the faults a looped stack can have, planted under the timed path
    ("make_tiny_ouro_stale_cache", False, False),
    ("make_tiny_ouro_no_final_norm", False, False),
], ids=["bfloat16", "int8-control", "stale-cache", "no-final-norm"])
def test_a_whole_run_of_the_tiny_model_decides_correct(tmp_path, monkeypatch,
                                                       factory, control,
                                                       want):
    """The cell's own traffic file (32 callers, one prompt a request,
    buckets 8 and 16) against the tiny model on the CPU, compared by the
    cell's reference, teacher-forced."""
    import chipbench.run as run

    _tiny_root(str(tmp_path), factory)
    monkeypatch.setattr(run, "memory_peak_bytes",
                        lambda devices, watch: 1 << 20)
    monkeypatch.delenv("TRITON_TPU_QUANT", raising=False)
    try:
        line, compared = run.run_cell(
            "tiny.few", 4000000007, 2.0, False, platform="cpu",
            root=str(tmp_path), control=control)
    finally:
        os.environ.pop("TRITON_TPU_QUANT", None)
    obj = json.loads(line)
    assert obj["correct"] is want, compared
    assert obj["attempted"] > 0 and obj["failed"] == 0
    assert set(obj["metrics"]) == {"infer_per_s", "setup_s"}
    assert compared["token_inconsistent"]["value"] == 0
    assert check.verdict(compared, obj["attempted"], 0, 0) is want
