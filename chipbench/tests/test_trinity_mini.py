"""The ``trinity_mini`` configuration and its cell: the file against the
catalog row it copies, the byte count of the deployment against its
arithmetic, the FLOP count by hand, the two new readers on made-up counters
and a made-up breakdown, the comparator on made-up answers, and a whole run
of the tiny model on the CPU that has to come out correct (and its int8
control not)."""

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
from chipbench.tests import tiny_trinity  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CFG = load_json(ROOT, "chipbench", "configs", "trinity_mini.json")
CELL = "trinity_mini.longprefill"
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"

_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the ``config`` of the catalog's row "Trinity-Mini" (model-configs guide,
# architectures.jsonl), copied whole
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": _PERIOD * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}


def test_every_key_is_the_catalogs_but_the_depth():
    (row,) = [c for c in BENCH["configs"] if c["name"] == "trinity_mini"]
    assert row["source"] == CFG["source"] and row["source"].startswith(SOURCE)
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200
    assert row["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # the depth is cut to the two dense layers and the four expert layers
    # after them; the pattern stays whole and its first 6 entries are held
    assert CFG["num_hidden_layers"] == 6
    assert CFG["deployment"]["published"] == {"num_hidden_layers": 32}
    assert CFG["layer_types"][:6] == _PERIOD + _PERIOD[:2]
    # the floors: a whole period and four layers after the dense ones,
    # eight experts, the whole vocabulary
    assert CFG["num_hidden_layers"] - CFG["num_dense_layers"] >= 4
    assert CFG["n_routed_experts"] == CFG["num_experts"] == 128
    assert set(CFG["assumed"]) >= {
        "embedding", "layer", "attention", "sliding", "full", "gate",
        "dense_ffn", "experts", "router_eps", "expert_bias", "closing",
        "seq_len", "weights"}
    assert CFG["assumed"]["router_eps"] == 1e-20


def test_the_deployment_is_the_arithmetic_of_the_cut():
    work = load_module("flop_counts", "trinity_mini")
    part = work.layer_params(CFG)
    assert part == {"attention": 27_262_976, "dense_ffn": 37_748_736,
                    "moe": 56_885_248}
    D, E = CFG["hidden_size"], CFG["num_experts"]
    norms = 4 * D + 2 * CFG["head_dim"]
    dense = part["attention"] + part["dense_ffn"] + norms
    expert = (part["attention"] + D * E + E + 6_291_456 + E * 6_291_456
              + norms)
    assert (dense, expert) == (65_020_160, 839_131_520)
    held = 2 * dense + 4 * expert + 2 * CFG["vocab_size"] * D + D
    assert held == 4_306_554_880
    # bfloat16 but the 4 x 128 bias values, which are float32
    assert 2 * held + 2 * 4 * E == 8_613_110_784
    assert "4,306,554,880 parameters = 8,613,110,784 B" in \
        CFG["deployment"]["bytes"]
    # a held expert sees what it sees in the deployment: 2,048 rows a prompt
    assert CFG["served"]["seq_len"] * CFG["num_experts_per_tok"] // E == 2048


def test_the_flop_count_by_hand():
    work = load_module("flop_counts", "trinity_mini")
    S = 32768
    assert work.attended_pairs(CFG, "full_attention") == 536_887_296
    assert work.attended_pairs(CFG, "sliding_attention") == 65_012_736
    per_token = 6 * 27_262_976 + 2 * 37_748_736 + 4 * 56_885_248
    assert 2 * per_token == pytest.approx(933.2e6, rel=1e-4)
    attention = 16_384 * (536_887_296 + 5 * 65_012_736)
    assert work.flops_per_inference(CFG) == 2.0 * per_token * S \
        + attention + 2.0 * 2048 * 200192
    assert work.flops_per_inference(CFG) == pytest.approx(44.70e12,
                                                          rel=1e-3)
    kernel = work.kernel_work(CFG, "window_attention")
    assert kernel["flops"] == 16_384 * 65_012_736
    assert kernel["bytes"] == 2.0 * S * 128 * (2 * 32 + 2 * 4)
    # without the window the step would be 83.36 TFLOP
    whole = dict(CFG, layer_types=["full_attention"] * 32)
    assert work.flops_per_inference(whole) == pytest.approx(83.36e12,
                                                            rel=1e-3)
    with pytest.raises(KeyError):
        work.kernel_work(CFG, "dsa_attention")
    tiny = tiny_trinity.TINY_TRINITY
    pairs = sum(min(t + 1, 8) for t in range(40))
    assert work.attended_pairs(tiny, "sliding_attention") == pairs == 292


def test_the_cell_reports_what_it_was_added_with():
    cell = Cell(CELL)
    assert cell.chips == 1
    # in ``workloads`` exactly once, wherever later cells stand
    (row,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (row["config"], row["traffic"]) == ("trinity_mini", "longprefill")
    assert len(row["why"]) <= 200
    traffic = cell.traffic
    assert (traffic["generator"], traffic["protocol"], traffic["callers"],
            traffic["loop"], traffic["request_batch"],
            traffic["warm_batches"], traffic["check_requests"],
            traffic["trace_lead_s"], traffic["trace_seconds"]) == (
        "unary", "grpc", 8, "closed", 1, [1], 8, 3.0, 4.0)
    served = cell.config["served"]
    assert (served["seq_len"], served["max_batch_size"],
            served["batch_buckets"]) == (32768, 1, [1])
    assert [m["name"] for m in cell.end_to_end] == ["infer_per_s", "setup_s"]
    # the metrics the cell is accepted with, in their order; what a later
    # change declares here stands between or after them and is its own to
    # assert
    accepted = ["scheduler.batch_mean", "model_step.mfu_pct",
                "device.idle_pct", "moe.rows_per_token",
                "moe.busiest_over_mean", "window.keys_per_query",
                "window_attention_roofline"]
    mine = [m for m in cell.per_layer if m["name"] in accepted]
    assert [m["name"] for m in mine] == accepted
    assert all(m["moves"] == "infer_per_s" and m["layer"] in (
        "scheduler", "model step", "device") for m in mine)
    new = {m["name"]: m for m in mine if m["name"].startswith("window")}
    assert new["window.keys_per_query"]["source"] == "program_counter"
    assert new["window_attention_roofline"]["source"] == "device_trace"
    assert new["window_attention_roofline"]["unit"] == "%"
    assert all(m["workloads"] == [CELL] for m in new.values())


def _ctx(window_ops):
    """A traced window of nine steps of one prompt each, the windowed
    kernel's op on the line at ``window_ops`` seconds (None: not on it)."""
    ops = [["_flash_call.1 bf16[32,32768,128]", 0.7],
           ["gmm.3 f32[32768,1024]", 0.35]]
    if window_ops is not None:
        ops.append(["_window_call.1 bf16[32,32768,128]", window_ops])
    return {
        "trace": {"busy_s": 3.99, "window_s": 4.0,
                  "breakdown": {"device_ops": ops}},
        "stats_delta": {
            "inference_count": 9, "execution_count": 9,
            "bucket_rows.count": 9,
            "window_queries.count": 9 * 5 * 32768,
            "window_pairs.count": 9 * 5 * 65_012_736,
            "expert_rows.count": 9 * 4 * 32768 * 8,
            "expert_tokens.count": 9 * 4 * 32768},
        "config": CFG, "chips": 1,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }


def test_the_new_readers_on_made_up_counters():
    keys = load_module("layer_metrics", "window.keys_per_query")
    assert keys.read(_ctx(0.5)) == pytest.approx(1984.03, abs=0.005)
    roofline = load_module("layer_metrics", "window_attention_roofline")
    # nine steps, five sliding layers, 1.0652 TFLOP a call, in 0.5 s
    want = 100 * 9 * 5 * 16_384 * 65_012_736 / (0.5 * 197e12)
    assert roofline.read(_ctx(0.5)) == pytest.approx(want, rel=1e-12)
    assert 40 < want < 80
    # a line without the kernel's op (the parent), a program without the
    # counters, nothing at all: nothing, and no raise
    assert roofline.read(_ctx(None)) is None
    short = _ctx(0.5)
    del short["stats_delta"]["window_pairs.count"]
    assert keys.read(short) is None
    for reader in (keys, roofline):
        assert reader.read({}) is None
        assert reader.read({"stats_delta": {}, "trace": None}) is None
    rows = load_module("layer_metrics", "moe.rows_per_token")
    assert rows.read(_ctx(0.5)) == pytest.approx(8.0)


class _Replay:
    def __init__(self, want, short):
        self.want, self.short = want, short

    def replay(self, ids, routes):
        assert routes.shape == (len(ids), 4, 32768, 8)
        return {"logits": self.want, "route_shortfall": self.short}


def _answers(rng, rows_of):
    """Answers consistent with ``rows_of [N,1,V]``: the arg-max token, 8
    distinct experts a token and layer."""
    answers = []
    for rows in rows_of:
        routes = np.tile(np.arange(8, dtype=np.int32), (4, 32768, 1))
        routes += rng.integers(0, 15, (4, 32768, 1), dtype=np.int32) * 8
        answers.append({"TOKENS": np.array([[rows[0].argmax()]], np.int32),
                        "LOGITS": rows[None].astype(np.float32),
                        "ROUTES": routes[None]})
    return answers


def test_the_comparison_is_by_row_and_holds_tokens_and_routes():
    compare = load_module("comparators", "logit_rel_l2_routed").compare
    rng = np.random.default_rng(0)
    want = rng.standard_normal((8, 1, 64))
    short = np.full((8, 32768), 0.01)
    inputs = [{"INPUT_IDS": np.zeros((1, 4), np.int32)} for _ in range(8)]
    limit = CFG["limits"]["logit_rel_l2_median"]
    got = want * (1 + 0.5 * limit * rng.standard_normal(want.shape))
    answers = _answers(rng, got)
    out = compare(CFG, inputs, answers, _Replay(want, short))
    assert set(out) == set(CFG["limits"])
    assert out["logit_rel_l2_median"]["value"] == pytest.approx(0.5 * limit,
                                                                rel=0.3)
    assert out["route_shortfall_worst"]["value"] == pytest.approx(0.01)
    assert out["token_inconsistent"] == {"value": 0, "limit": 0}
    assert check.verdict(out, 8, 0, 0)
    # every row further out, as a lower precision reads
    far = want * (1 + 2.5 * limit * rng.standard_normal(want.shape))
    out = compare(CFG, inputs, _answers(rng, far), _Replay(want, short))
    assert out["logit_rel_l2_median"]["value"] > limit
    assert not check.verdict(out, 8, 0, 0)
    # a token that is not the arg-max of the row returned with it
    wrong = _answers(rng, got)
    wrong[5]["TOKENS"][0, 0] += 1
    out = compare(CFG, inputs, wrong, _Replay(want, short))
    assert out["token_inconsistent"]["value"] == 1
    assert not check.verdict(out, 8, 0, 0)
    # an expert named twice, and one that does not exist
    for value in (None, 128):
        wrong = _answers(rng, got)
        routes = wrong[2]["ROUTES"]
        routes[0, 3, 300, 0] = routes[0, 3, 300, 1] if value is None \
            else value
        out = compare(CFG, inputs, wrong, _Replay(want, short))
        assert out["token_inconsistent"]["value"] == 1
    # experts the reference would not have chosen
    out = compare(CFG, inputs, answers,
                  _Replay(want, np.where(np.arange(32768) == 77, 0.4, short)))
    assert out["route_shortfall_worst"]["value"] == pytest.approx(0.4)
    assert not check.verdict(out, 8, 0, 0)
    # two callers given each other's answers
    swapped = [answers[i] for i in (1, 0, 2, 3, 4, 5, 6, 7)]
    out = compare(CFG, inputs, swapped, _Replay(want, short))
    assert out["logit_rel_l2_worst"]["value"] > 1.0
    assert not check.verdict(out, 8, 0, 0)
    assert compare(CFG, [], [], _Replay(want, short))["logit_rel_l2_median"][
        "value"] is None


def _tiny_root(tmp):
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    with open(os.path.join(tmp, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(tiny_trinity.TINY_TRINITY, f)
    traffic = load_json(ROOT, "chipbench", "traffic", "longprefill.json")
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
    """The cell's own traffic file (8 callers, one prompt a request, a
    bucket of one) against the tiny model on the CPU, compared by the
    cell's reference told the routes; the int8 control in the program's
    place is not correct."""
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
