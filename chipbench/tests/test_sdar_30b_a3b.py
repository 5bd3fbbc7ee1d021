"""The ``sdar_30b_a3b`` configuration and its cell: the file against the
catalog row it was cut from, the cut against its arithmetic, the FLOP and
byte counts by hand, the two new readers on made-up counters, the
comparator on made-up answers, and a whole run of the tiny model on the CPU
that has to come out correct (and its int8 control not)."""

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
from chipbench.tests import tiny_sdar  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CFG = load_json(ROOT, "chipbench", "configs", "sdar_30b_a3b.json")
CELL = "sdar_30b_a3b.blockgen"

# the ``config`` of the catalog's row "SDAR-30B-A3B-Chat" (model-configs
# guide, architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")


def test_every_key_is_the_catalogs_or_is_listed_as_reduced():
    (row,) = [c for c in BENCH["configs"] if c["name"] == "sdar_30b_a3b"]
    assert row["source"] == SOURCE and CFG["source"].startswith(SOURCE)
    assert row["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key in row["reduced"]:
            assert CFG[key] != value
            assert CFG["deployment"]["published"][key] == value
        else:
            assert CFG[key] == value, key
    # what the file adds of its own repeats a published number
    assert CFG["n_routed_experts"] == CFG["num_experts"]
    # what the config lacks (the catalog's ``not_given``) is assumed
    assert set(CFG["assumed"]["generation"]) >= {
        "block_length", "denoising_steps", "mask_token_id",
        "confidence_threshold", "remasking"}


def test_the_cut_is_the_arithmetic_of_the_deployment():
    dep = CFG["deployment"]
    assert dep["published"]["num_hidden_layers"] \
        == dep["pipeline_stages"] * dep["layers_per_stage"] == 48
    assert CFG["num_hidden_layers"] == dep["layers_per_stage"] == 6
    # the guide's floors: four layers or more, eight experts or more, an
    # eighth of the vocabulary or more (here: all of both)
    assert CFG["num_hidden_layers"] >= 4 and CFG["num_experts"] >= 8
    D, H, Hkv, dh = (CFG["hidden_size"], CFG["num_attention_heads"],
                     CFG["num_key_value_heads"], CFG["head_dim"])
    attention = D * (H + 2 * Hkv) * dh + H * dh * D
    assert attention == 18_874_368
    experts = CFG["num_experts"] * 3 * D * CFG["moe_intermediate_size"]
    assert experts == 603_979_776
    layer = attention + D * CFG["num_experts"] + experts
    held = 6 * layer + 2 * CFG["vocab_size"] * D
    # bfloat16: 8.72 GB, 54.5% of the chip before any activation (the
    # norms' 0.05 MB are what the compiler counts beyond it)
    assert 2 * held == pytest.approx(8.722e9, rel=1e-3)
    assert 0.5 < 2 * held / 16e9 < 0.6


def test_the_flop_and_byte_counts_by_hand():
    work = load_module("flop_counts", "sdar_30b_a3b")
    # the issue's arithmetic at the published cut: 0.96 TFLOP a request
    assert work.flops_per_inference(CFG) == pytest.approx(0.956e12, rel=2e-3)
    cfg = tiny_sdar.TINY_SDAR
    # D 64, 4 heads over 2 of 16, 8 experts of 3 x 64 x 32, top 2, 2 layers,
    # vocabulary 512, prompt 16, 12 new tokens in 3 blocks of 4
    attention = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64
    expert = 3 * 64 * 32
    per_token = 2 * 2 * (attention + 64 * 8 + 2 * expert)
    scores = lambda rows, keys: 2 * 2 * 4 * 2 * 16 * rows * keys  # noqa: E731
    prefill = 16 * per_token + sum(scores(4, e) for e in (4, 8, 12, 16))
    passes = sum(5 * (4 * per_token + scores(4, 16 + 4 * (n + 1)))
                 + 4 * 4 * 2 * 64 * 512 for n in range(3))
    assert work.flops_per_inference(cfg) == prefill + passes
    # a pass: touched experts, the layers' attention and router, the head,
    # the cache of 3 sequences at 20 positions
    weights = 11 * expert + 2 * (attention + 64 * 8) + 64 * 512
    cache = 3 * 2 * 2 * 2 * 20 * 16
    assert work.pass_bytes(cfg, 3, 11, 20) == 2 * (weights + cache)
    assert work.pass_bytes(cfg, 3, 11, 20, head=False) \
        == 2 * (weights + cache - 64 * 512)
    assert work.prefill_bytes(cfg, 3) == 2 * (
        2 * (attention + 64 * 8 + 8 * expert) + 3 * 16 * 64
        + 3 * 2 * 2 * 2 * 16 * 16)
    # the published cut: a pass of 16 sequences that touches 125.7 experts
    # a layer reads 8.2 GB (the issue's figure)
    assert work.pass_bytes(CFG, 16, 6 * 125.7, 1040) == pytest.approx(
        8.2e9, rel=0.01)


def test_the_cell_reports_what_the_issue_named():
    cell = Cell(CELL)
    assert cell.chips == 1
    traffic = cell.traffic
    assert (traffic["callers"], traffic["loop"], traffic["request_batch"],
            traffic["warm_batches"], traffic["check_requests"],
            traffic["trace_seconds"]) == (32, "closed", 1, [8, 16], 8, 8.0)
    served = cell.config["served"]
    assert (served["seq_len"], served["new_tokens"],
            served["batch_buckets"]) == (1024, 32, [8, 16])
    assert [m["name"] for m in cell.end_to_end] == ["infer_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "moe.rows_per_token", "moe.busiest_over_mean",
        "diffusion.passes_per_token", "diffusion.hbm_pct"]
    assert all(m["moves"] == "infer_per_s" for m in cell.per_layer)
    # the accepted cells keep the metrics they had
    assert [m["name"] for m in Cell("kimi_k2.prefill").per_layer] == [
        "scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
        "moe.rows_per_token", "moe.busiest_over_mean",
        "mla_attention_roofline"]


def _ctx():
    """A traced window of ten batches of 16 under the static rule, each
    pass touching 125 experts a layer."""
    executions, batch = 10, 16
    passes = executions * 40
    return {
        "trace": {"busy_s": 7.9, "window_s": 8.0},
        "stats_delta": {
            "inference_count": executions * batch,
            "execution_count": executions,
            "denoise_passes.count": passes * batch,
            "denoise_tokens.count": executions * batch * 32,
            "experts_touched.count": passes * 6 * 125,
            "expert_rows.count": executions * batch * 1184 * 6 * 8,
            "expert_tokens.count": executions * batch * 1184 * 6,
            "expert_rows_busiest.count": executions * 6 * 1500},
        "config": CFG, "chips": 1,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }


def test_the_new_readers_on_made_up_counters():
    work = load_module("flop_counts", "sdar_30b_a3b")
    ctx = _ctx()
    per_token = load_module("layer_metrics", "diffusion.passes_per_token")
    assert per_token.read(ctx) == pytest.approx(1.25)
    hbm = load_module("layer_metrics", "diffusion.hbm_pct")
    moved = 10 * (work.prefill_bytes(CFG, 16)
                  + 32 * work.pass_bytes(CFG, 16, 750, 1040)
                  + 8 * work.pass_bytes(CFG, 16, 750, 1040, head=False))
    assert hbm.read(ctx) == pytest.approx(
        100 * moved / (7.9 * 819e9), rel=1e-9)
    assert 40 < hbm.read(ctx) < 60
    # the accepted readers the cell joins read the same counters
    assert load_module("layer_metrics", "moe.rows_per_token").read(
        ctx) == pytest.approx(8.0)
    assert load_module("layer_metrics", "moe.busiest_over_mean").read(
        ctx) == pytest.approx(128 * 1500 / (16 * 1184 * 8))
    # a program that lacks a counter (the parent): nothing, and no raise
    for missing in ("denoise_passes.count", "denoise_tokens.count",
                    "experts_touched.count"):
        short = copy.deepcopy(ctx)
        del short["stats_delta"][missing]
        assert hbm.read(short) is None
        if missing != "experts_touched.count":
            assert per_token.read(short) is None
    for reader in (per_token, hbm):
        assert reader.read({}) is None
        assert reader.read({"stats_delta": {}, "trace": None}) is None


class _Replay:
    def __init__(self, want, shortfall=0.02):
        self.want, self.shortfall = want, shortfall

    def replay(self, ids, tokens, commit_pass, routes):
        assert routes.shape == (len(ids), 2, 6, 8)
        return {"logits": self.want,
                "route_shortfall": np.full(self.want.shape[:2],
                                           self.shortfall)}


def _answers(rng, want, passes=None):
    """Answers consistent with ``want [N,2,V]``: the arg-max tokens at the
    positions the passes name."""
    answers = []
    for rows in want:
        when = np.stack([rng.permutation(4) for _ in range(8)]).reshape(-1) \
            if passes is None else np.array(passes)
        tokens = rng.integers(0, 64, 32)
        tokens[int(np.argmax(when[:4] == 0))] = rows[0].argmax()
        tokens[28 + int(np.argmax(when[28:] == 3))] = rows[1].argmax()
        routes = np.stack([rng.permutation(128)[:8] for _ in range(12)])
        answers.append({"TOKENS": tokens[None].astype(np.int32),
                        "COMMIT_PASS": when[None].astype(np.int32),
                        "LOGITS": rows[None].astype(np.float32),
                        "ROUTES": routes.reshape(1, 2, 6, 8).astype(
                            np.int32)})
    return answers


def test_the_comparison_is_by_row_and_holds_tokens_to_logits():
    compare = load_module("comparators", "logit_rel_l2_replayed").compare
    rng = np.random.default_rng(0)
    want = rng.standard_normal((8, 2, 64))
    inputs = [{"INPUT_IDS": np.zeros((1, 4), np.int32)} for _ in range(8)]
    got = want * (1 + 0.015 * rng.standard_normal(want.shape))
    got[3, 1] = want[3, 1] + 0.2 * rng.standard_normal(64)  # a routing flip
    answers = _answers(rng, got)
    out = compare(CFG, inputs, answers, _Replay(want))
    assert set(out) == set(CFG["limits"])
    assert out["logit_rel_l2_median"]["value"] == pytest.approx(0.015,
                                                                rel=0.25)
    assert 0.15 < out["logit_rel_l2_worst"]["value"] < 0.3
    assert out["commit_inconsistent"] == {"value": 0, "limit": 0}
    assert check.verdict(out, 8, 0, 0)
    # every row a little further out, as a lower precision reads
    far = want * (1 + 0.08 * rng.standard_normal(want.shape))
    out = compare(CFG, inputs, _answers(rng, far), _Replay(want))
    assert out["logit_rel_l2_median"]["value"] > CFG["limits"][
        "logit_rel_l2_median"]
    assert not check.verdict(out, 8, 0, 0)
    # a token that is not the arg-max of the row returned with it
    wrong = _answers(rng, got)
    at = int(np.argmax(wrong[5]["COMMIT_PASS"][0, :4] == 0))
    wrong[5]["TOKENS"][0, at] += 1
    out = compare(CFG, inputs, wrong, _Replay(want))
    assert out["commit_inconsistent"]["value"] == 1
    assert not check.verdict(out, 8, 0, 0)
    # a block whose passes are no permutation of 0..3
    twice = _answers(rng, got)
    twice[2]["COMMIT_PASS"][0, 8:12] = [0, 1, 1, 3]
    out = compare(CFG, inputs, twice, _Replay(want))
    assert out["commit_inconsistent"]["value"] == 1
    # an expert named twice, and one that does not exist
    for bad_route in ([5, 5, 1, 2, 3, 4, 6, 7], [128, 0, 1, 2, 3, 4, 6, 7]):
        routed = _answers(rng, got)
        routed[4]["ROUTES"][0, 1, 3] = bad_route
        out = compare(CFG, inputs, routed, _Replay(want))
        assert out["commit_inconsistent"]["value"] == 1
    # a choice the reference's own probabilities do not bear out
    out = compare(CFG, inputs, answers, _Replay(want, shortfall=0.6))
    assert out["route_shortfall_worst"]["value"] == pytest.approx(0.6)
    assert not check.verdict(out, 8, 0, 0)
    # two callers given each other's answers
    swapped = [answers[i] for i in (1, 0, 2, 3, 4, 5, 6, 7)]
    out = compare(CFG, inputs, swapped, _Replay(want))
    assert out["logit_rel_l2_worst"]["value"] > 1.0
    assert not check.verdict(out, 8, 0, 0)
    assert compare(CFG, [], [], _Replay(want))["logit_rel_l2_median"][
        "value"] is None


def _tiny_root(tmp):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(tiny_sdar.TINY_SDAR, f)
    traffic = load_json(ROOT, "chipbench", "traffic", "blockgen.json")
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


@pytest.mark.parametrize("control,want", [(False, True), (True, False)],
                         ids=["bfloat16", "int8-control"])
def test_a_whole_run_of_the_tiny_model_decides_correct(tmp_path, monkeypatch,
                                                       control, want):
    """The cell's own traffic file (32 callers, one prompt a request,
    buckets 8 and 16) against the tiny model on the CPU, compared by the
    cell's reference, teacher-forced."""
    import chipbench.run as run

    _tiny_root(str(tmp_path))
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
    assert compared["commit_inconsistent"]["value"] == 0
    assert check.verdict(compared, obj["attempted"], 0, 0) is want
