"""The ``kimi_k2`` configuration and its cell: the file against the catalog
row it was cut from, the cut against its arithmetic, the FLOP count by hand
at a tiny size, the new readers on made-up counters, and a whole run of the
tiny model on the CPU that has to come out correct (and its int8 control
not)."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check  # noqa: E402
from chipbench.files import Cell, load_json, load_module  # noqa: E402
from chipbench.tests import tiny_kimi  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CFG = load_json(ROOT, "chipbench", "configs", "kimi_k2.json")

# the ``config`` of the catalog's row "Kimi-K2-Instruct"
# (model-configs guide, architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "kimi_k2",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 384, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840,
}
SOURCE = ("https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
          "config.json")


def test_every_key_is_the_catalogs_or_is_listed_as_reduced():
    (row,) = [c for c in BENCH["configs"] if c["name"] == "kimi_k2"]
    # the catalog's URL, then the deployment in the 200 characters allowed
    for source in (row["source"], CFG["source"]):
        assert source.startswith(SOURCE + "; rank 0 of ")
    assert len(row["source"]) <= 200
    assert row["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in row["reduced"]:
            assert CFG[key] != value
            assert CFG["deployment"]["published"][key] == value
        else:
            assert CFG[key] == value, key
    # no width among the reduced keys: depth, experts held and rows of the
    # vocabulary are counts
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "num_attention_heads"}
    for key in row["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in widths


def test_the_cut_is_the_arithmetic_of_the_deployment():
    dep = CFG["deployment"]
    published = dep["published"]
    assert published["n_routed_experts"] // dep["expert_parallel"] \
        == CFG["n_routed_experts"] == 12
    assert published["vocab_size"] // dep["vocab_parallel"] \
        == CFG["vocab_size"] == 20480
    # the floors of the guide: a leading dense layer and four expert layers,
    # at least 8 experts held, at least an eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= published["vocab_size"]
    D, H = CFG["hidden_size"], CFG["num_attention_heads"]
    mla = (D * 1536 + 1536 * H * 192 + D * (512 + 64) + 512 * H * 256
           + H * 128 * D)
    assert mla == 101_122_048
    expert = 3 * D * CFG["moe_intermediate_size"]
    assert expert == 44_040_192
    expert_layer = mla + D * 384 + expert + CFG["n_routed_experts"] * expert
    dense_layer = mla + 3 * D * CFG["intermediate_size"]
    held = (dense_layer + 4 * expert_layer + 2 * CFG["vocab_size"] * D)
    # bfloat16: 6.99 GB, 44% of the chip before any activation
    assert 2 * held == pytest.approx(6.99e9, rel=2e-3)
    assert 0.25 < 2 * held / 16e9 < 0.5


def test_the_flop_count_by_hand_at_a_tiny_size():
    cfg = tiny_kimi.TINY_KIMI
    flops = load_module("flop_counts", "kimi_k2")
    # D 64, H 4, q rank 32, kv rank 16, heads 16 + 8 and 12, S 24
    mla = 64 * 32 + 32 * 4 * 24 + 64 * (16 + 8) + 16 * 4 * (16 + 12) \
        + 4 * 12 * 64
    dense = 3 * 64 * 96
    expert = 3 * 64 * 32
    # 4 of 16 experts held, 4 chosen a token: one pair a token expected
    expert_layer = 64 * 16 + expert + expert * (4 * 4 / 16)
    per_token = 2 * (3 * mla + dense + 2 * expert_layer)
    attention = 2 * 3 * 4 * (24 + 12) * (24 * 25 / 2)
    head = 2 * 64 * 64
    assert flops.flops_per_inference(cfg) == 24 * per_token + attention + head
    work = flops.kernel_work(cfg, "mla_attention")
    assert work["flops"] == 2 * 4 * (24 + 12) * (24 * 25 / 2)
    assert work["bytes"] == 2 * 4 * 24 * (2 * 24 + 2 * 12)
    with pytest.raises(KeyError):
        flops.kernel_work(cfg, "no_such_kernel")
    # and the published cut: 25.4 TFLOP a prompt, 0.129 s at the peak
    assert flops.flops_per_inference(CFG) == pytest.approx(25.44e12, rel=1e-3)


def test_the_cell_reports_what_its_claims_would_need():
    cell = Cell("kimi_k2.prefill")
    assert cell.chips == 1 and cell.traffic["callers"] == 8
    assert [m["name"] for m in cell.end_to_end] == ["infer_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["scheduler.batch_mean", "model_step.mfu_pct",
                     "device.idle_pct", "moe.rows_per_token",
                     "moe.busiest_over_mean", "mla_attention_roofline"]
    assert all(m["moves"] == "infer_per_s" for m in cell.per_layer)


def test_the_new_readers_on_made_up_counters():
    read = {name: load_module("layer_metrics", name).read
            for name in ("moe.rows_per_token", "moe.busiest_over_mean",
                         "mla_attention_roofline")}
    for reader in read.values():
        assert reader({}) is None
        # a program without the counters, as the parent is
        assert reader({"stats_delta": {"inference_count": 8,
                                       "execution_count": 4},
                       "trace": {"busy_s": 3.9, "window_s": 4.0,
                                 "breakdown": {"device_ops": [
                                     ["fusion.1 bf16[2,8192,7168]", 1.0]],
                                     "idle_gaps": []}},
                       "config": CFG, "chips": 1,
                       "peaks": {"bf16_flops_per_s": 197e12}}) is None
    # 8 prompts of 8192 tokens through 4 expert layers
    tokens = 8 * 8192 * 4
    delta = {"inference_count": 8, "execution_count": 4,
             "expert_tokens.count": tokens, "expert_rows.count": tokens // 4,
             "expert_rows_busiest.count": int(1.5 * tokens / 4 / 12)}
    ctx = {"stats_delta": delta, "config": CFG, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12},
           "trace": {"busy_s": 3.9, "window_s": 4.0, "breakdown": {
               "device_ops": [["_flash_call.2 bf16[128,8192,128]", 0.8],
                              ["fusion.9 bf16[2,8192,7168]", 0.5],
                              ["_flash_call.1 bf16[128,8192,128]", 0.2]],
               "idle_gaps": []}}}
    assert read["moe.rows_per_token"](ctx) == pytest.approx(0.25)
    assert read["moe.busiest_over_mean"](ctx) == pytest.approx(1.5, rel=1e-3)
    # 8 prompts x 5 layers x 1.3746 TFLOP in 1.0 s of kernel time
    assert read["mla_attention_roofline"](ctx) == pytest.approx(
        100 * 40 * 1.3745573e12 / 197e12, rel=1e-4)


ONE_CALL = 100 * 1.3745573e12 / 197e12  # % of the peak, at 1 s a prompt's call


def _roofline(executions, rows, ops):
    delta = {"inference_count": rows, "execution_count": executions,
             "bucket_rows.count": rows}
    return load_module("layer_metrics", "mla_attention_roofline").read(
        {"stats_delta": delta, "config": CFG, "chips": 1,
         "peaks": {"bf16_flops_per_s": 197e12},
         "trace": {"busy_s": 3.9, "window_s": 4.0, "breakdown": {
             "device_ops": ops, "idle_gaps": []}}})


def test_the_roofline_counts_only_the_calls_the_line_accounts_for():
    """The line holds ten ops: steps of one prompt beside steps of two put
    four kernel ops in the trace.  At 10 ms a prompt's call (an op of the
    four scanned layers takes 40 ms a prompt, the dense layer's 10 ms),
    every accounted mix reads the same share."""
    want = ONE_CALL / 0.010
    two = [["_flash_call.14 bf16[128,8192,128]", 6 * 0.080],
           ["fusion.369 bf16[2,8192,7168]", 0.3],
           ["_flash_call.13 bf16[128,8192,128]", 6 * 0.020]]
    one = [["_flash_call.7 bf16[64,8192,128]", 4 * 0.040],
           ["_flash_call.6 bf16[64,8192,128]", 4 * 0.010]]
    assert _roofline(6, 12, two) == pytest.approx(want, rel=1e-6)
    assert _roofline(4, 4, one) == pytest.approx(want, rel=1e-6)
    # six steps of two and four of one, every op on the line
    assert _roofline(10, 16, two + one) == pytest.approx(want, rel=1e-6)
    # the shortest op fell off the line: its bucket is left out of both
    # sides, and the reading does not move (summing what is there over
    # every call, as the first reader did, would read too high)
    assert _roofline(10, 16, two + one[:1]) == pytest.approx(want, rel=1e-6)
    # a window cut in mid-step leaves the ratio of the two ops off 4
    cut = [[two[0][0], 5.4 * 0.080], two[1], [two[2][0], 6 * 0.020]]
    assert _roofline(6, 12, cut) == pytest.approx(
        want * 0.6 / (5.4 * 0.080 + 0.12), rel=1e-6)
    # nothing accounted for: no guess
    assert _roofline(6, 12, two[:2]) is None       # the dense layer's op gone
    assert _roofline(4, 4, two) is None            # no step ran at that bucket
    assert _roofline(6, 12, [["_flash_call.1 bf16[96,8192,128]", 1.0]]) is None
    assert _roofline(0, 0, two) is None


def test_steps_by_bucket():
    steps = load_module("layer_metrics",
                        "mla_attention_roofline").steps_by_bucket
    assert steps([1, 2], 10, 16) == {1: 4, 2: 6}
    assert steps([1, 2], 8, 16) == {2: 8}
    assert steps([1, 2], 8, 8) == {1: 8}
    assert steps([8], 3, 24) == {8: 3}
    assert steps([1, 2], 8, 17) is None
    assert steps([1, 2, 4], 8, 17) is None  # three buckets, two counts


class _Echo:
    """A reference that answers with what it is given under ``WANT``."""

    def __init__(self, want):
        self.want = want

    def outputs(self, inputs):
        return {"LOGITS": self.want}


def test_the_comparison_by_request_is_not_moved_by_one_request():
    import numpy as np

    compare = load_module("comparators", "logit_rel_l2_by_request").compare
    rng = np.random.default_rng(0)
    want = rng.standard_normal((8, 64))
    inputs = [{"INPUT_IDS": np.zeros((1, 4), np.int32)} for _ in range(8)]
    got = want * (1 + 0.02 * rng.standard_normal((8, 64)))
    got[3] = want[3] + 0.2 * rng.standard_normal(64)   # one flipped choice
    answers = [{"LOGITS": got[i:i + 1].astype(np.float32)} for i in range(8)]
    out = compare(CFG, inputs, answers, _Echo(want))
    assert set(out) == set(CFG["limits"])
    assert out["logit_rel_l2_median"]["value"] == pytest.approx(0.02, rel=0.2)
    assert 0.15 < out["logit_rel_l2_worst"]["value"] < 0.3
    assert check.verdict(out, 8, 0, 0)
    # every request a little further out, as a lower precision reads
    answers = [{"LOGITS": (want[i:i + 1] * (1 + 0.08 * rng.standard_normal(
        (1, 64)))).astype(np.float32)} for i in range(8)]
    out = compare(CFG, inputs, answers, _Echo(want))
    assert out["logit_rel_l2_median"]["value"] > CFG["limits"][
        "logit_rel_l2_median"]
    assert not check.verdict(out, 8, 0, 0)
    # two callers given each other's answers
    answers = [{"LOGITS": want[i:i + 1].astype(np.float32)}
               for i in (1, 0, 2, 3, 4, 5, 6, 7)]
    out = compare(CFG, inputs, answers, _Echo(want))
    assert out["logit_rel_l2_median"]["value"] < 1e-6
    assert out["logit_rel_l2_worst"]["value"] > 1.0
    assert not check.verdict(out, 8, 0, 0)
    assert compare(CFG, [], [], _Echo(want))["logit_rel_l2_median"][
        "value"] is None


def _tiny_root(tmp):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(tiny_kimi.TINY_KIMI, f)
    traffic = load_json(ROOT, "chipbench", "traffic", "prefill.json")
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
            m["workloads"] = ["tiny.few"] if "kimi_k2.prefill" in m[
                "workloads"] else []
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


@pytest.mark.parametrize("control,want", [(False, True), (True, False)],
                         ids=["bfloat16", "int8-control"])
def test_a_whole_run_of_the_tiny_model_decides_correct(tmp_path, monkeypatch,
                                                       control, want):
    """The cell's own traffic file (8 callers, one prompt a request)
    against the tiny model on the CPU, compared by the cell's reference."""
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
    assert check.verdict(compared, obj["attempted"], 0, 0) is want
