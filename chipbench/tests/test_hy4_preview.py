"""The ``hy4_preview`` configuration and its cell: the file against the
catalog row it was cut from, the cut against its arithmetic, the FLOP count
by hand at a tiny size, the new readers on made-up counters, the comparator
on made-up answers, and a whole run of the tiny model on the CPU that has to
come out correct (and its int8 control not)."""

from __future__ import annotations

import copy
import json
import math
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
from chipbench.tests import tiny_hy4  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CFG = load_json(ROOT, "chipbench", "configs", "hy4_preview.json")
CELL = "hy4_preview.prefill"

# the ``config`` of the catalog's row "Hy4-preview" (model-configs guide,
# architectures.jsonl), copied whole; its three lists are written out by rule
CATALOG = {
    "attention_bias": False, "bitwise_backward_align": False,
    "enable_ihc": True, "enable_lm_head_fp32": True, "gated_mla": True,
    "gating_type": "elementwise", "hc_eps": 1e-06, "hc_magnitude": 2,
    "hc_mult": 4, "head_dim": 64, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_types": ["full", "full"] + ["shared", "shared", "shared",
                                         "full"] * 19,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "layer_types": ["deepseek_sparse_attention"] * 78,
    "learnable_sink": True, "learnable_sink_init": 0,
    "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] + ["sparse"] * 77, "model_type": "hy_v4",
    "moe_intermediate_size": 2048, "mtp_loss_factor": 0.1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 10000000, "rope_type": "default"},
    "routed_scaling_factor": 2.827, "swiglu_limit": 10,
    "tie_word_embeddings": False, "topk_group": 1, "use_dsa": True,
    "use_mla": True, "v_head_dim": 256, "vocab_size": 120832,
}
SOURCE = "https://huggingface.co/tencent/Hy4-preview/blob/main/config.json"


def test_every_key_is_the_catalogs_or_is_listed_as_reduced():
    (row,) = [c for c in BENCH["configs"] if c["name"] == "hy4_preview"]
    for source in (row["source"], CFG["source"]):
        assert source.startswith(SOURCE + "; rank 0 of an EP32 ")
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200
    assert row["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(CATALOG["indexer_types"]) == 78
    for key, value in CATALOG.items():
        if key in row["reduced"]:
            assert CFG[key] != value
            assert CFG["deployment"]["published"][key] == value
        else:
            assert CFG[key] == value, key
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "num_attention_heads", "index_topk"}
    for key in row["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in widths
    # every line the config's keys do not fix is under ``assumed``
    for line in ("streams", "sinkhorn", "streams_close", "gate", "sink",
                 "indexer", "index_reuse", "swiglu", "router", "unused_keys",
                 "mtp", "wiring_note"):
        assert CFG["assumed"][line], line


def _block_params(cfg: dict, mlp: str, indexer: str, mtp=False) -> int:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n = cfg["hc_mult"]
    attention = (D * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * 256
                 + D * 576 + 512 * H * (192 + 256) + 2 * H * 256 * D + H)
    expert = 3 * D * cfg["moe_intermediate_size"]
    total = attention + 2 * n * D * n * (n + 2) + 6
    if indexer == "full":
        total += cfg["q_lora_rank"] * 32 * 128 + D * 128 + D * 32
    if mlp == "dense":
        total += 3 * D * cfg["intermediate_size"]
    else:
        total += D * 256 + 256 + expert * (1 + cfg["n_routed_experts"])
    return total + (2 * D * D if mtp else 0)


def test_the_cut_is_the_arithmetic_of_the_deployment():
    dep = CFG["deployment"]
    published = dep["published"]
    assert published["n_routed_experts"] // dep["expert_parallel"] \
        == CFG["n_routed_experts"] == 8
    assert published["vocab_size"] // dep["vocab_parallel"] \
        == CFG["vocab_size"] == 15104
    # the guide's floors: a leading dense layer and four expert layers, 8
    # experts held, an eighth of the vocabulary; one whole period of
    # indexer_types past the first layer
    L = CFG["num_hidden_layers"]
    assert CFG["mlp_layer_types"][:L] == ["dense"] + ["sparse"] * 4
    assert CFG["indexer_types"][:L] == ["full", "full"] + ["shared"] * 3
    assert CFG["indexer_types"][5] == "full"
    blocks = [_block_params(CFG, m, i) for m, i in zip(
        CFG["mlp_layer_types"][:L], CFG["indexer_types"][:L])]
    mtp = _block_params(CFG, "sparse", "full", mtp=True)
    assert blocks[0] == pytest.approx(615.97e6, rel=1e-5)
    assert blocks[1] == pytest.approx(617.55e6, rel=1e-5)
    assert blocks[2] == pytest.approx(608.17e6, rel=1e-5)
    assert mtp == pytest.approx(693.04e6, rel=1e-5)
    held = sum(blocks) + mtp + 2 * CFG["vocab_size"] * CFG["hidden_size"]
    # bfloat16: 7.87 GB, 49% of the chip before any activation
    assert 2 * held == pytest.approx(7.874e9, rel=1e-3)
    assert 0.25 < 2 * held / 16e9 < 0.5


def test_the_program_draws_what_the_arithmetic_counts():
    """The program's leaves at the published cut, from their shapes alone:
    what the configuration's ``bytes`` says."""
    import jax

    from triton_client_tpu.models import sparse_latent as sl

    shapes = jax.eval_shape(lambda: sl.init_params(sl.HY4_PREVIEW_EP32_SHARE))
    size = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(shapes))
    assert size == pytest.approx(7.874e9, rel=1e-3)


def test_the_flop_count_by_hand_at_a_tiny_size():
    cfg = tiny_hy4.TINY_HY4
    flops = load_module("flop_counts", "hy4_preview")
    # D 128, H 4, q rank 32, kv rank 16, heads 16 + 8 and 24, S 64, k 48,
    # an indexer of 8 x 32, 4 streams, 4 of 16 experts held, 4 a token
    attention = (128 * 32 + 32 * 4 * 24 + 128 * 24 + 16 * 4 * (16 + 24)
                 + 2 * 4 * 24 * 128 + 2 * 4 * 128 * 24)
    indexer = 32 * 8 * 32 + 128 * 32 + 128 * 8
    expert = 3 * 128 * 32
    moe = 128 * 16 + expert + expert * (4 * 4 / 16)
    per_token = (5 * attention + 3 * indexer + 3 * 128 * 96 + 4 * moe
                 + 2 * 128 * 128)
    pairs = 48 * 49 // 2 + (64 - 48) * 48
    assert flops.chosen_pairs(cfg) == pairs
    attend = 5 * 2 * 4 * (24 + 24) * pairs
    score = 3 * 64 * 65 / 2 * 2 * 8 * 32
    heads = 2 * 2 * 128 * 64
    assert flops.flops_per_inference(cfg) == pytest.approx(
        2 * 64 * per_token + attend + score + heads, rel=1e-12)
    work = flops.kernel_work(cfg, "dsa_attention")
    assert work["flops"] == 2 * 4 * (24 + 24) * pairs
    with pytest.raises(KeyError):
        flops.kernel_work(cfg, "no_such_kernel")
    # the published cut: 44.09 TFLOP a prompt, 5.77 of them the chosen
    # pairs' attention and 0.82 the indexers' scores
    assert flops.flops_per_inference(CFG) == pytest.approx(44.09e12, rel=1e-3)
    assert 6 * flops.kernel_work(CFG, "dsa_attention")["flops"] \
        == pytest.approx(5.773e12, rel=1e-3)


def test_the_cell_reports_what_it_declares():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.traffic["callers"] == 8
    assert cell.traffic_name == "prefill" and cell.config_name == "hy4_preview"
    names = [m["name"] for m in cell.per_layer]
    mine = ["scheduler.batch_mean", "model_step.mfu_pct", "device.idle_pct",
            "moe.rows_per_token", "moe.busiest_over_mean",
            "dsa.keys_per_query", "dsa.index_reuse_pct",
            "dsa_attention_roofline"]
    assert [n for n in names if n in mine] == mine
    assert {m["name"] for m in cell.end_to_end} >= {"infer_per_s", "setup_s"}
    for m in cell.per_layer:
        if m["name"].startswith("dsa"):
            assert (m["layer"], m["moves"]) == ("model step", "infer_per_s")
    (row,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert len(row["why"]) <= 200


def _delta(executions=4, rows=8):
    S, blocks = 8192, 6
    return {"inference_count": rows, "execution_count": executions,
            "bucket_rows.count": rows,
            "dsa_queries.count": rows * S * blocks,
            "dsa_pairs.count": rows * 14_681_088 * blocks,
            "index_reused.count": rows * S * 3}


def _ctx(delta, ops):
    return {"stats_delta": delta, "config": CFG, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12},
            "trace": {"busy_s": 3.9, "window_s": 4.0, "breakdown": {
                "device_ops": ops, "idle_gaps": []}}}


def test_the_new_readers_on_made_up_counters():
    read = {name: load_module("layer_metrics", name).read
            for name in ("dsa.keys_per_query", "dsa.index_reuse_pct",
                         "dsa_attention_roofline")}
    for reader in read.values():
        assert reader({}) is None
        # a program without the counters or the kernel, as the parent is
        assert reader(_ctx({"inference_count": 8, "execution_count": 4},
                           [["fusion.1 bf16[2,8192,6144]", 1.0]])) is None
    ctx = _ctx(_delta(), [["_dsa_call.16 bf16[128,8192,256]", 3 * 0.4],
                          ["fusion.9 bf16[2,8192,6144]", 0.5],
                          ["_dsa_call.14 bf16[128,8192,256]", 0.4],
                          ["_dsa_call.15 bf16[128,8192,256]", 0.4],
                          ["_dsa_call.17 bf16[128,8192,256]", 0.4]])
    assert read["dsa.keys_per_query"](ctx) == 1792.125
    assert read["dsa.index_reuse_pct"](ctx) == 50.0
    # 4 steps of two prompts, 6 blocks, 0.962 TFLOP a block and prompt, in
    # 2.4 s of kernel time
    one = 2 * 64 * 512 * 14_681_088
    assert read["dsa_attention_roofline"](ctx) == pytest.approx(
        100 * 4 * 2 * 6 * one / (2.4 * 197e12), rel=1e-9)
    # a block's op fell off the line: nothing accounted for, no guess
    ctx["trace"]["breakdown"]["device_ops"].pop()
    assert read["dsa_attention_roofline"](ctx) is None


class _Replay:
    """A reference that answers with what it is given."""

    def __init__(self, logits, index_short=0.0, route_short=0.0):
        self.logits = logits
        self.short = (index_short, route_short)
        self.given = None

    def replay(self, ids, first_tokens, chosen, routes):
        self.given = (first_tokens, chosen, routes)
        n = len(self.logits)
        return {"logits": self.logits,
                "index_shortfall": np.full(n, self.short[0]),
                "route_shortfall": np.full(n, self.short[1])}


def _planes(chosen):
    """``[..., S]`` bool -> the served bit planes ``[..., W]`` int32, key
    ``s`` at bit ``s // W`` of word ``s % W``."""
    S = chosen.shape[-1]
    per_plane = -(-S // 32)
    W = max(128, -(-per_plane // 128) * 128)
    padded = np.zeros(chosen.shape[:-1] + (32 * W,), bool)
    padded[..., :S] = chosen
    on = padded.reshape(chosen.shape[:-1] + (32, W)).astype(np.uint64)
    words = (on << np.arange(32, dtype=np.uint64)[:, None]).sum(-2)
    return words.astype(np.uint32).view(np.int32)


def _answers(n=8, seed=0):
    cfg = tiny_hy4.TINY_HY4
    rng = np.random.default_rng(seed)
    S, k, E = cfg["served"]["seq_len"], cfg["index_topk"], 16
    logits = rng.standard_normal((n, 2, cfg["vocab_size"]))
    chosen = np.zeros((n, 3, S, S), bool)
    for t in range(S):
        for i in range(n):
            for j in range(3):
                chosen[i, j, t, rng.permutation(t + 1)[:min(t + 1, k)]] = True
    routes = np.argsort(rng.random((n, 4, S, E)), axis=-1)[..., :4]
    inputs = [{"INPUT_IDS": np.zeros((1, S), np.int32)} for _ in range(n)]
    return cfg, logits, _planes(chosen), routes.astype(np.int32), inputs


def _pack(logits, chosen, routes):
    return [{"TOKENS": np.argmax(logits[i:i + 1], -1).astype(np.int32),
             "LOGITS": logits[i:i + 1].astype(np.float32),
             "CHOSEN": chosen[i:i + 1], "ROUTES": routes[i:i + 1]}
            for i in range(len(logits))]


def test_the_comparison_reads_rows_choices_and_tokens():
    compare = load_module("comparators", "logit_rel_l2_indexed").compare
    cfg, want, chosen, routes, inputs = _answers()
    rng = np.random.default_rng(1)
    got = want * (1 + 0.01 * rng.standard_normal(want.shape))
    ref = _Replay(want)
    out = compare(cfg, inputs, _pack(got, chosen, routes), ref)
    assert set(out) == set(cfg["limits"])
    assert out["logit_rel_l2_median"]["value"] == pytest.approx(0.01, rel=0.2)
    assert out["index_shortfall_worst"]["value"] == 0.0
    assert out["route_shortfall_worst"]["value"] == 0.0
    assert out["token_inconsistent"]["value"] == 0
    assert check.verdict(out, 8, 0, 0)
    # the reference is told the program's first tokens, keys and experts
    np.testing.assert_array_equal(ref.given[0], np.argmax(got[:, 0], -1))
    np.testing.assert_array_equal(ref.given[1], chosen)
    np.testing.assert_array_equal(ref.given[2], routes)
    # what the reference measured of the choices is the verdict's
    out = compare(cfg, inputs, _pack(got, chosen, routes),
                  _Replay(want, 2.5, 0.3))
    assert out["index_shortfall_worst"]["value"] == 2.5
    assert out["route_shortfall_worst"]["value"] == 0.3
    assert not check.verdict(out, 8, 0, 0)
    # a row one key short, a key past its query, an expert named twice, a
    # token that is not its row's arg-max
    S = cfg["served"]["seq_len"]
    for fault in range(4):
        bad_chosen, bad_routes = chosen.copy(), routes.copy()
        answers = _pack(got, chosen, routes)
        if fault == 0:
            # a word of one key (64 keys lie in plane 0 alone)
            word = np.flatnonzero(bad_chosen[2, 0, S - 1])[0]
            bad_chosen[2, 0, S - 1, word] = 0
        elif fault == 1:
            # query 3 takes key 4 besides its own four
            bad_chosen[2, 2, 3, 4] |= 1
        elif fault == 2:
            bad_routes[2, 1, 7, 1] = bad_routes[2, 1, 7, 0]
        if fault == 3:
            answers[2]["TOKENS"] = answers[2]["TOKENS"] + 1
        else:
            answers[2].update(CHOSEN=bad_chosen[2:3], ROUTES=bad_routes[2:3])
        out = compare(cfg, inputs, answers, ref)
        assert out["token_inconsistent"]["value"] == 1, fault
        assert not check.verdict(out, 8, 0, 0)
    # two callers given each other's answers
    swapped = _pack(got, chosen, routes)
    swapped[0], swapped[1] = swapped[1], swapped[0]
    out = compare(cfg, inputs, swapped, ref)
    assert out["logit_rel_l2_worst"]["value"] > 1.2
    assert compare(cfg, [], [], ref)["logit_rel_l2_median"]["value"] is None


def _tiny_root(tmp):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(tiny_hy4.TINY_HY4, f)
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
            m["workloads"] = ["tiny.few"] if CELL in m["workloads"] else []
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
    assert compared["token_inconsistent"]["value"] == 0
