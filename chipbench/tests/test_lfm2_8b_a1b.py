"""The ``lfm2_8b_a1b`` configuration and its cell: the file against the
catalog row it copies, the byte count of the deployment against its
arithmetic, the FLOP and byte counts by hand, the two new readers on made-up
counters, the comparator on made-up answers, and a whole run of the tiny
model on the CPU that has to come out correct (and its int8 control and
three wrong hand-overs of state not)."""

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
from chipbench.tests import tiny_lfm2  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CFG = load_json(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")
CELL = "lfm2_8b_a1b.chatgen"

_PERIOD = ["full_attention", "conv", "conv", "conv"]
# the ``config`` of the catalog's row "LFM2-8B-A1B" (model-configs guide,
# architectures.jsonl), copied whole
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv"] + _PERIOD * 4
    + ["full_attention", "conv", "conv"] * 2,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"


def test_every_key_is_the_catalogs_but_the_depth():
    (row,) = [c for c in BENCH["configs"] if c["name"] == "lfm2_8b_a1b"]
    assert row["source"] == CFG["source"] and row["source"].startswith(SOURCE)
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200
    assert row["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # the depth is cut to the two dense layers and three whole periods; the
    # pattern stays whole and its first 14 entries are the layers held
    assert CFG["num_hidden_layers"] == 14
    assert CFG["deployment"]["published"] == {"num_hidden_layers": 24}
    held = CFG["layer_types"][:14]
    assert held == ["conv", "conv"] + _PERIOD * 3
    assert [i for i, k in enumerate(CFG["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    # the floors: a whole period, four layers after the dense ones, eight
    # experts, the whole vocabulary
    assert CFG["num_hidden_layers"] - CFG["num_dense_layers"] >= 4
    assert CFG["num_experts"] >= 8 and CFG["n_routed_experts"] == 32
    # the wiring the config does not fix is assumed, line by line
    assert set(CFG["assumed"]) >= {
        "head_dim", "router_eps", "tie_word_embeddings", "layer", "conv",
        "attention", "dense_ffn", "experts", "closing", "generation",
        "seq_len", "weights"}
    assert (CFG["assumed"]["head_dim"], CFG["assumed"]["router_eps"]) == (
        64, 1e-6)


def test_the_deployment_is_the_arithmetic_of_the_cut():
    D, E = CFG["hidden_size"], CFG["num_experts"]
    work = load_module("flop_counts", "lfm2_8b_a1b")
    part = work.op_params(CFG)
    assert part == {"conv": 4 * D * D + 3 * D, "full_attention": 10_485_760}
    assert part["conv"] == 16_783_360
    assert work.dense_ffn_params(CFG) == 44_040_192
    assert E * work.expert_params(CFG) == 352_321_536
    kinds = work.kinds(CFG)
    assert (kinds.count("conv"), kinds.count("full_attention")) == (11, 3)
    norms = 14 * 2 * D + 3 * 2 * 64 + D
    held = (12 * (E * work.expert_params(CFG) + D * E + E)
            + 3 * part["full_attention"] + 11 * part["conv"]
            + 2 * work.dense_ffn_params(CFG) + CFG["vocab_size"] * D + norms)
    assert held == 4_667_077_376
    # bfloat16 but the 12 x 32 bias values, which are float32
    assert 2 * held + 2 * 12 * E == 9_334_155_520
    assert "4,667,077,376 parameters = 9,334,155,520 B" in \
        CFG["deployment"]["bytes"]
    assert 9_334_155_520 / 16e9 == pytest.approx(0.583, abs=1e-3)
    # state: 6,144 B a position of keys and values, 90,112 B of conv rows
    assert work.cache_bytes_per_position(CFG) == 6_144
    assert work.conv_state_bytes(CFG) == 90_112
    assert "6,144 B a position" in CFG["deployment"]["state"]
    assert "90,112 B a sequence" in CFG["deployment"]["state"]
    assert 16 * (544 * 6_144 + 90_112) == pytest.approx(54.9e6, rel=1e-3)


def test_the_flop_and_byte_counts_by_hand():
    work = load_module("flop_counts", "lfm2_8b_a1b")
    D, V = 2048, 65536
    # the issue's arithmetic: a token passes 967.6 M matrix parameters
    per_token = (work.other_params(CFG)
                 + 12 * 4 * work.expert_params(CFG))
    assert per_token == pytest.approx(967.6e6, rel=1e-4)
    matrices = 2 * (per_token - D * V) * 543
    scores = 3 * 32 * 2 * 2 * 64 * (512 * 513 // 2 + sum(range(513, 544)))
    head = 32 * 2 * D * V
    assert work.flops_per_inference(CFG) == matrices + scores + head
    assert work.flops_per_inference(CFG) == pytest.approx(0.9173e12, rel=1e-3)
    cfg = tiny_lfm2.TINY_LFM2
    # D 64, 8 heads of 8 over 2, a dense FFN of 224, 8 experts of 56 top 2,
    # 10 layers (8 conv, 2 attention; 2 dense), vocabulary 256, prompt 16,
    # 6 new tokens
    conv, attention = 4 * 64 * 64 + 3 * 64, 64 * (8 + 4) * 8 + 64 * 64
    other = (8 * conv + 2 * attention + 2 * 3 * 64 * 224 + 8 * 64 * 8
             + 64 * 256)
    assert work.other_params(cfg) == other
    expert = 3 * 64 * 56
    pairs = 16 * 17 // 2 + 17 + 18 + 19 + 20 + 21
    assert work.flops_per_inference(cfg) == (
        2 * (other - 64 * 256 + 8 * 2 * expert) * 21
        + 2 * 8 * 2 * 2 * 8 * pairs + 6 * 2 * 64 * 256)
    position, rows = 2 * 2 * 2 * 2 * 8, 2 * 8 * 2 * 64
    assert work.cache_bytes_per_position(cfg) == position
    assert work.conv_state_bytes(cfg) == rows
    assert work.prefill_bytes(cfg, 5) == (
        2 * (other + 8 * 8 * expert + 5 * 16 * 64)
        + 5 * (16 * position + rows))
    assert work.step_bytes(cfg, 5, 30, 18) == (
        2 * (30 * expert + other + 5 * 64) + 5 * (19 * position + 2 * rows))
    assert work.generation_bytes(cfg, 5, 140) == work.prefill_bytes(cfg, 5) \
        + sum(work.step_bytes(cfg, 5, 0, 16 + i - 1) for i in range(1, 6)) \
        + 2 * 140 * expert
    # the published cut: a decode step of 16 sequences that touches 88% of
    # 32 experts a layer reads 7.4 GB of experts and 0.88 GB of the rest
    touched = 0.88 * 32 * 12
    assert 2 * touched * work.expert_params(CFG) == pytest.approx(7.44e9,
                                                                  rel=1e-2)
    assert 2 * work.other_params(CFG) == pytest.approx(0.878e9, rel=1e-2)
    assert work.step_bytes(CFG, 16, touched, 527) == pytest.approx(8.38e9,
                                                                   rel=1e-2)
    # no share can read over 100: a step that touched every expert moves
    # what the prefill's weights are, and no more
    assert work.step_bytes(CFG, 0, 32 * 12, 0) == \
        work.prefill_bytes(CFG, 0)


def test_the_cell_reports_what_the_issue_named():
    cell = Cell(CELL)
    assert cell.chips == 1
    # in ``workloads`` exactly once, wherever later cells stand
    (row,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (row["config"], row["traffic"]) == ("lfm2_8b_a1b", "chatgen")
    assert len(row["why"]) <= 200
    traffic = cell.traffic
    assert (traffic["generator"], traffic["protocol"], traffic["callers"],
            traffic["loop"], traffic["request_batch"],
            traffic["warm_batches"], traffic["check_requests"],
            traffic["trace_lead_s"], traffic["trace_seconds"]) == (
        "unary", "grpc", 32, "closed", 1, [8, 16], 8, 3.0, 8.0)
    served = cell.config["served"]
    assert (served["seq_len"], served["new_tokens"],
            served["batch_buckets"]) == (512, 32, [8, 16])
    assert [m["name"] for m in cell.end_to_end] == ["infer_per_s", "setup_s"]
    # the metrics the cell is accepted with, in their order; what a later
    # PR declares here stands between or after them and is that PR's to
    # assert
    accepted = ["scheduler.batch_mean", "model_step.mfu_pct",
                "device.idle_pct", "moe.rows_per_token",
                "moe.busiest_over_mean", "hybrid.hbm_pct",
                "hybrid.steps_per_token"]
    mine = [m for m in cell.per_layer if m["name"] in accepted]
    assert [m["name"] for m in mine] == accepted
    assert all(m["moves"] == "infer_per_s" and m["layer"] in (
        "scheduler", "model step", "device") for m in mine)
    new = {m["name"]: m for m in mine if m["name"].startswith("hybrid.")}
    assert new["hybrid.hbm_pct"]["source"] == "device_trace"
    assert new["hybrid.steps_per_token"]["source"] == "program_counter"
    assert all(m["workloads"] == [CELL] for m in new.values())


def _ctx():
    """A traced window of eight batches of 16, each decode step touching
    28 of 32 experts a layer."""
    executions, batch = 8, 16
    return {
        "trace": {"busy_s": 7.9, "window_s": 8.0},
        "stats_delta": {
            "inference_count": executions * batch,
            "execution_count": executions,
            "decode_steps.count": executions * batch * 31,
            "decode_tokens.count": executions * batch * 31,
            "experts_touched.count": executions * 31 * 12 * 28},
        "config": CFG, "chips": 1,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }


def test_the_new_readers_on_made_up_counters():
    work = load_module("flop_counts", "lfm2_8b_a1b")
    ctx = _ctx()
    per_token = load_module("layer_metrics", "hybrid.steps_per_token")
    assert per_token.read(ctx) == pytest.approx(1.0)
    drafted = copy.deepcopy(ctx)
    drafted["stats_delta"]["decode_steps.count"] //= 2
    assert per_token.read(drafted) == pytest.approx(0.5)
    hbm = load_module("layer_metrics", "hybrid.hbm_pct")
    moved = 8 * (work.prefill_bytes(CFG, 16) + sum(
        work.step_bytes(CFG, 16, 12 * 28, 512 + i - 1) for i in range(1, 32)))
    assert hbm.read(ctx) == pytest.approx(100 * moved / (7.9 * 819e9),
                                          rel=1e-9)
    assert 25 < hbm.read(ctx) < 45
    # every expert of every layer at every step: what the weights alone
    # allow, and still under the roofline at this pace
    full = copy.deepcopy(ctx)
    full["stats_delta"]["experts_touched.count"] = 8 * 31 * 12 * 32
    assert hbm.read(ctx) < hbm.read(full) < 100
    # a program that lacks a counter (the parent): nothing, and no raise
    for missing in ("decode_steps.count", "decode_tokens.count",
                    "experts_touched.count"):
        short = copy.deepcopy(ctx)
        del short["stats_delta"][missing]
        if missing != "experts_touched.count":
            assert per_token.read(short) is None
        if missing != "decode_tokens.count":
            assert hbm.read(short) is None
    for reader in (per_token, hbm):
        assert reader.read({}) is None
        assert reader.read({"stats_delta": {}, "trace": None}) is None


class _Replay:
    def __init__(self, want, short):
        self.want, self.short = want, short

    def replay(self, ids, tokens, routes):
        assert tokens.shape == (len(ids), 32)
        assert routes.shape == (len(ids), 543, 12, 4)
        return {"logits": self.want, "route_shortfall": self.short}


def _answers(rng, rows_of):
    """Answers consistent with ``rows_of [N,3,V]``: the arg-max tokens at
    the first, the second and the last place, four experts a layer."""
    answers = []
    for rows in rows_of:
        tokens = rng.integers(0, 64, 32)
        tokens[0], tokens[1], tokens[-1] = (r.argmax() for r in rows)
        routes = np.argsort(rng.random((543, 12, 32)), axis=-1)[..., :4]
        answers.append({"TOKENS": tokens[None].astype(np.int32),
                        "LOGITS": rows[None].astype(np.float32),
                        "ROUTES": routes[None].astype(np.int32)})
    return answers


def test_the_comparison_is_by_row_and_holds_tokens_and_routes():
    compare = load_module("comparators", "logit_rel_l2_greedy").compare
    rng = np.random.default_rng(0)
    want = rng.standard_normal((8, 3, 64))
    short = np.full((8, 543), 0.03)
    inputs = [{"INPUT_IDS": np.zeros((1, 4), np.int32)} for _ in range(8)]
    limit = CFG["limits"]["logit_rel_l2_median"]
    got = want * (1 + 0.5 * limit * rng.standard_normal(want.shape))
    answers = _answers(rng, got)
    out = compare(CFG, inputs, answers, _Replay(want, short))
    assert set(out) == set(CFG["limits"])
    assert out["logit_rel_l2_median"]["value"] == pytest.approx(0.5 * limit,
                                                                rel=0.3)
    assert out["route_shortfall_worst"]["value"] == pytest.approx(0.03)
    assert out["token_inconsistent"] == {"value": 0, "limit": 0}
    assert check.verdict(out, 8, 0, 0)
    # every row further out, as a lower precision reads
    far = want * (1 + 2.5 * limit * rng.standard_normal(want.shape))
    out = compare(CFG, inputs, _answers(rng, far), _Replay(want, short))
    assert out["logit_rel_l2_median"]["value"] > limit
    assert not check.verdict(out, 8, 0, 0)
    # the first decode step alone wrong (a state handed over wrongly): the
    # median of 24 rows stands, the worst row does not
    handed = got.copy()
    handed[:, 1] = rng.standard_normal((8, 64))
    out = compare(CFG, inputs, _answers(rng, handed), _Replay(want, short))
    assert out["logit_rel_l2_median"]["value"] < limit
    assert out["logit_rel_l2_worst"]["value"] > 1.0
    assert not check.verdict(out, 8, 0, 0)
    # a token that is not the arg-max of the row returned with it
    for place in (0, 1, -1):
        wrong = _answers(rng, got)
        wrong[5]["TOKENS"][0, place] += 1
        out = compare(CFG, inputs, wrong, _Replay(want, short))
        assert out["token_inconsistent"]["value"] == 1
        assert not check.verdict(out, 8, 0, 0)
    # an expert named twice, and one that does not exist
    for value in (None, 32):
        wrong = _answers(rng, got)
        routes = wrong[2]["ROUTES"]
        routes[0, 300, 7, 0] = routes[0, 300, 7, 1] if value is None \
            else value
        out = compare(CFG, inputs, wrong, _Replay(want, short))
        assert out["token_inconsistent"]["value"] == 1
    # experts the reference would not have chosen
    out = compare(CFG, inputs, answers,
                  _Replay(want, np.where(np.arange(543) == 77, 0.4, short)))
    assert out["route_shortfall_worst"]["value"] == pytest.approx(0.4)
    assert not check.verdict(out, 8, 0, 0)
    # two callers given each other's answers
    swapped = [answers[i] for i in (1, 0, 2, 3, 4, 5, 6, 7)]
    out = compare(CFG, inputs, swapped, _Replay(want, short))
    assert out["logit_rel_l2_worst"]["value"] > 1.0
    assert not check.verdict(out, 8, 0, 0)
    assert compare(CFG, [], [], _Replay(want, short))["logit_rel_l2_median"][
        "value"] is None


def _tiny_root(tmp, factory):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    cfg = copy.deepcopy(tiny_lfm2.TINY_LFM2)
    cfg["served"]["factory"] = "chipbench.tests.tiny_lfm2:" + factory
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = load_json(ROOT, "chipbench", "traffic", "chatgen.json")
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
    ("make_tiny_lfm2", False, True),
    # the control: the program's own int8 storage in the program's place
    ("make_tiny_lfm2", True, False),
    # the faults a hand-over of two kinds of state can have, planted under
    # the timed path
    ("make_tiny_lfm2_conv_rows_swapped", False, False),
    ("make_tiny_lfm2_conv_rows_from_the_start", False, False),
    ("make_tiny_lfm2_cache_of_another_row", False, False),
], ids=["bfloat16", "int8-control", "conv-rows-swapped", "conv-rows-forgotten",
        "cache-of-another-row"])
def test_a_whole_run_of_the_tiny_model_decides_correct(tmp_path, monkeypatch,
                                                       factory, control,
                                                       want):
    """The cell's own traffic file (32 callers, one prompt a request,
    buckets 8 and 16) against the tiny model on the CPU, compared by the
    cell's reference, teacher-forced and told the routes."""
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
