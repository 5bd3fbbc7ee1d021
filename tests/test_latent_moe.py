"""The latent-attention / expert block (models/latent_moe.py) against the
benchmark's plain reference (chipbench/references/kimi_k2.py) at a tiny size
on the CPU, every ratio of the published cut kept: a dense layer first, 4 of
16 experts held, q·k 24 wide and v 12, a 64-row slice of the vocabulary.

Tolerances.  With the bfloat16 weights upcast and everything computed in
float32 the program and the reference do the same arithmetic in another
order: 1e-4 relative (read: 8e-6).  As served (bfloat16 activations, f32
accumulation) the tiny sums round coarsely: the logits' relative L2 reads
0.017-0.023, held under 0.035; int8 storage reads several times that.
"""

import dataclasses
import math
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import triton_client_tpu.grpc as grpcclient  # noqa: E402
from chipbench.files import load_json, load_module  # noqa: E402
from chipbench.tests.tiny_kimi import TINY_KIMI, program_config  # noqa: E402
from triton_client_tpu.models import language  # noqa: E402
from triton_client_tpu.models import latent_moe as lm  # noqa: E402
from triton_client_tpu.models import parts  # noqa: E402
from triton_client_tpu.server import ModelRegistry  # noqa: E402
from triton_client_tpu.server.model import ModelStats  # noqa: E402
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module("references", "kimi_k2")
TINY = program_config(TINY_KIMI)
S = TINY.seq_len


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def params():
    return lm.init_params(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, TINY.vocab_size, (3, S)).astype(np.int32)


@pytest.fixture(scope="module")
def wanted(tokens):
    return REF.Reference(TINY_KIMI).outputs({"INPUT_IDS": tokens})


def _forward(params, tokens, cfg=TINY):
    return jax.jit(lambda p, t: lm.forward(p, t, cfg))(
        params, jnp.asarray(tokens))


# -- program against reference ----------------------------------------------

def test_float32_program_is_the_reference(params, tokens, wanted):
    logits, rows = _forward(_f32(params), tokens)
    assert logits.shape == (3, TINY.vocab_size)
    assert _rel_l2(logits, wanted["LOGITS"]) < 1e-4
    # the rows each held expert was routed, by prompt and expert layer
    assert rows.shape == (3, TINY.n_expert_layers, TINY.n_routed_experts)
    np.testing.assert_array_equal(np.asarray(rows), wanted["EXPERT_ROWS"])


def test_served_precision_stays_inside_its_band(params, tokens, wanted):
    logits, rows = _forward(params, tokens)
    assert logits.dtype == jnp.float32
    assert _rel_l2(logits, wanted["LOGITS"]) < 0.035
    # bfloat16 rounds a few scores across the eighth place
    assert np.abs(np.asarray(rows) - wanted["EXPERT_ROWS"]).sum() <= 8


def test_int8_storage_reads_outside_the_band(tokens, wanted):
    quantized = lm.init_params(TINY, quantized=True)
    assert quantized["experts"]["we_gate"].dtype == jnp.int8
    assert quantized["dense"][0]["w_qa"].dtype == jnp.int8
    assert quantized["experts"]["router"].dtype == jnp.bfloat16
    logits, _ = _forward(quantized, tokens)
    assert _rel_l2(logits, wanted["LOGITS"]) > 0.05


def test_weights_are_bfloat16_and_follow_the_layer_and_the_expert():
    layer = lm._layer_params(TINY, 1)
    assert {a.dtype for k, a in layer.items() if k != "router_bias"} \
        == {jnp.dtype(jnp.bfloat16)}
    want = REF.layer_weights(TINY_KIMI, 1)
    for name in ("w_qa", "w_kb", "w_o", "router", "ws_down", "router_bias"):
        np.testing.assert_array_equal(
            np.asarray(layer[name], np.float32), np.asarray(want[name]))
    # expert 6 is the third this share holds (first_expert 4)
    expert = REF.expert_weights(TINY_KIMI, 1, 6)
    np.testing.assert_array_equal(
        np.asarray(layer["we_down"][2], np.float32),
        np.asarray(expert["down"]))
    other = lm._layer_params(TINY, 2)
    assert not np.array_equal(np.asarray(layer["w_qa"], np.float32),
                              np.asarray(other["w_qa"], np.float32))


# -- the expert layer is told what it holds ---------------------------------

def _expert_layer_inputs(first_expert, held=4, layer=1, n_tokens=48):
    cfg = dataclasses.replace(TINY, first_expert=first_expert,
                              n_routed_experts=held)
    blk = _f32(lm._layer_params(cfg, layer))
    h = jax.random.normal(jax.random.PRNGKey(3), (n_tokens, cfg.hidden_size),
                          jnp.float32)
    return cfg, blk, h


def _dense_reference(cfg, blk, h, experts, layer=1):
    """The reference's form: every named expert for every token, masked."""
    idx, weights = REF.route(h, {"router": blk["router"],
                                 "router_bias": blk["router_bias"]},
                             TINY_KIMI)
    y = jnp.zeros_like(h)
    for e in experts:
        y = y + REF.expert_part(h, idx, weights, e,
                                REF.expert_weights(TINY_KIMI, layer, e))
    return y, idx


@pytest.mark.parametrize("first_expert", [0, 4, 8, 12])
def test_held_experts_part_is_the_dense_form_masked(first_expert):
    cfg, blk, h = _expert_layer_inputs(first_expert)
    idx, weights = lm.route(blk, h, cfg)
    y, counts = jax.jit(lambda: lm.held_experts(blk, h, idx, weights, cfg))()
    held = range(first_expert, first_expert + 4)
    want, ref_idx = _dense_reference(cfg, blk, h, held)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(counts), [[int((np.asarray(idx) == e).sum())
                              for e in held]])


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide §4: the held experts' parts over all four shares, with the
    shared expert counted once, are the uncut reference's layer."""
    total = jnp.zeros((48, TINY.hidden_size), jnp.float32)
    for first_expert in (0, 4, 8, 12):
        cfg, blk, h = _expert_layer_inputs(first_expert)
        idx, weights = lm.route(blk, h, cfg)
        total = total + lm.held_experts(blk, h, idx, weights, cfg)[0]
    shared = parts.swiglu(h, blk["ws_gate"], blk["ws_up"], blk["ws_down"])
    uncut, _ = _dense_reference(cfg, blk, h, range(16))
    w = REF.layer_weights(TINY_KIMI, 1)
    uncut = uncut + REF._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    # every token's k pairs landed on exactly one share
    assert float(jnp.abs(total).sum()) > 0


def test_worst_case_routing_is_exact():
    """Every token picks the four held experts: 4 T = 192 pairs where even
    routing sends 48, so the loop walks three more chunks; none dropped."""
    cfg, blk, h = _expert_layer_inputs(4)
    bias = np.zeros(cfg.routed_experts_total, np.float32)
    bias[4:8] = 10.0
    blk = dict(blk, router_bias=jnp.asarray(bias))
    idx, weights = lm.route(blk, h, cfg)
    assert sorted(np.unique(np.asarray(idx))) == [4, 5, 6, 7]
    y, counts = jax.jit(lambda: lm.held_experts(blk, h, idx, weights, cfg))()
    np.testing.assert_array_equal(np.asarray(counts), [[48] * 4])
    want, _ = _dense_reference(cfg, blk, h, range(4, 8))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # and nobody picks a held expert: the part is exactly nothing
    blk = dict(blk, router_bias=jnp.asarray(-bias))
    idx, weights = lm.route(blk, h, cfg)
    y, counts = lm.held_experts(blk, h, idx, weights, cfg)
    assert float(jnp.abs(y).max()) == 0.0 and int(counts.sum()) == 0


def test_the_bias_moves_the_choice_and_not_the_weight():
    cfg, blk, h = _expert_layer_inputs(4)
    scores = jax.nn.sigmoid(h @ blk["router"])
    plain, _ = lm.route(dict(blk, router_bias=jnp.zeros_like(
        blk["router_bias"])), h, cfg)
    bias = blk["router_bias"] * 30.0
    idx, weights = lm.route(dict(blk, router_bias=bias), h, cfg)
    assert (np.sort(np.asarray(idx), -1)
            != np.sort(np.asarray(plain), -1)).any()
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jax.lax.top_k(scores + bias, 4)[1]))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(weights),
        picked / (picked.sum(-1, keepdims=True) + 1e-20) * 2.827, rtol=1e-6)


# -- rotary, scale, counts of work ------------------------------------------

def test_yarn_frequencies_and_softmax_scale_of_the_published_cut():
    cfg = lm.KIMI_K2_EP32_SHARE
    inv_freq, multiplier = lm.yarn_inv_freq(cfg)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    # beta_fast = beta_slow = 1: the ramp lies between pairs 19 and 20
    # (64 ln(4096 / 2 pi) / (2 ln 50000) = 19.16): own frequencies up to 19,
    # interpolated by the factor 32 from 20 on
    np.testing.assert_allclose(np.asarray(inv_freq[:20]), base[:20],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv_freq[20:]), base[20:] / 32,
                               rtol=1e-6)
    assert multiplier == 1.0
    m = 0.1 * math.log(32) + 1
    assert lm.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    ref_freq, ref_mult = REF.yarn_inv_freq(
        load_json(ROOT, "chipbench", "configs", "kimi_k2.json"))
    np.testing.assert_allclose(np.asarray(inv_freq), ref_freq, rtol=1e-6)
    assert ref_mult == 1.0


def test_the_published_cut_is_the_configuration_file_key_by_key():
    cfg = load_json(ROOT, "chipbench", "configs", "kimi_k2.json")
    want = dataclasses.asdict(program_config(cfg))
    got = dataclasses.asdict(lm.KIMI_K2_EP32_SHARE)
    assert sorted(got) == sorted(want)
    for key in got:
        assert got[key] == want[key], key


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_flops_are_the_yardsticks(which):
    cfg = TINY_KIMI if which == "tiny" else load_json(
        ROOT, "chipbench", "configs", "kimi_k2.json")
    yardstick = load_module("flop_counts", "kimi_k2").flops_per_inference(cfg)
    assert lm.flops_per_inference(program_config(cfg)) == pytest.approx(
        yardstick, rel=1e-12)
    if which == "published":
        assert yardstick == pytest.approx(25.44e12, rel=1e-3)
        model = language.make_kimi_k2()
        assert float(model.config.parameters[
            "flops_per_inference"].string_value) == pytest.approx(yardstick)


# -- the cache ---------------------------------------------------------------

def test_prefill_then_decode_is_the_full_forward_at_every_step(params,
                                                               tokens):
    """Prefill of 20 tokens returns the latent cache (c_kv and k_rope: 24
    values a token a layer here, 576 as published); four one-token steps in
    the absorbed form give the reference's full forward over each prefix."""
    p32 = _f32(params)
    logits, _, cache = jax.jit(lambda p, t: lm.prefill(p, t, TINY))(
        p32, jnp.asarray(tokens[:, :20]))
    assert cache[0].shape == (3, 3, 20, TINY.kv_lora_rank)
    assert cache[1].shape == (3, 3, 20, TINY.qk_rope_head_dim)
    cache = tuple(jnp.pad(c, ((0, 0), (0, 0), (0, S - 20), (0, 0)))
                  for c in cache)
    step = jax.jit(lambda p, t, c, pos: lm.decode_step(p, t, c, pos, TINY))
    reference = REF.Reference(TINY_KIMI)
    for pos in range(20, S):
        logits, cache = step(p32, jnp.asarray(tokens[:, pos]), cache, pos)
        want = reference.outputs({"INPUT_IDS": tokens[:, :pos + 1]})
        assert _rel_l2(logits, want["LOGITS"]) < 1e-4, pos


# -- the served path ---------------------------------------------------------

def test_expert_rows_leave_out_the_rows_the_batcher_padded():
    stats = ModelStats()
    stats.settle_device_counters()  # nothing queued: most models
    counts = np.array([[[3, 0, 1], [2, 2, 0]], [[9, 9, 9], [9, 9, 9]]])
    # the second row is padding
    stats.queue_device_counters({"expert_rows": counts}, 1, 10)
    entries = stats.extension_entries()
    assert entries["expert_rows"] == {"count": 8, "ns": 0}
    assert entries["expert_tokens"] == {"count": 1 * 10 * 2, "ns": 0}
    assert entries["expert_rows_busiest"] == {"count": 3 + 2, "ns": 0}
    stats.queue_device_counters({"expert_rows": counts}, 2, 10)
    assert stats.expert_rows == 8 + 8 + 54
    assert stats.expert_rows_busiest == 5 + (12 + 11)


@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    registry.register_model(language.make_kimi_k2(TINY))
    with ServerHarness(registry) as h:
        yield h


def _infer(client, ids):
    inp = grpcclient.InferInput("INPUT_IDS", list(ids.shape), "INT32")
    inp.set_data_from_numpy(ids)
    return client.infer("kimi_k2", [inp])


def test_factory_through_the_server_and_the_grpc_client(server, params,
                                                        tokens):
    want_logits, want_rows = _forward(params, tokens[:2])
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        md = client.get_model_metadata("kimi_k2", as_json=True)
        assert [(t["name"], t["datatype"], t["shape"])
                for t in md["inputs"]] == [("INPUT_IDS", "INT32",
                                            ["-1", str(S)])]
        assert [(t["name"], t["shape"]) for t in md["outputs"]] == [
            ("LOGITS", ["-1", str(TINY.vocab_size)])]
        config = client.get_model_config("kimi_k2", as_json=True)["config"]
        assert config["max_batch_size"] == 2
        assert config["dynamic_batching"]["preferred_batch_size"] == [1, 2]
        one = _infer(client, tokens[:1])
        two = _infer(client, tokens[:2])
        assert one.as_numpy("EXPERT_ROWS") is None
    assert one.as_numpy("LOGITS").shape == (1, TINY.vocab_size)
    np.testing.assert_allclose(two.as_numpy("LOGITS"),
                               np.asarray(want_logits), rtol=2e-2, atol=2e-3)
    (row,) = server.core.statistics("kimi_k2")
    stats = row["inference_stats"]
    rows = np.asarray(want_rows)
    assert row["inference_count"] == 3
    assert stats["expert_tokens"]["count"] == 3 * S * TINY.n_expert_layers
    assert stats["expert_rows"]["count"] == int(rows[0].sum() + rows.sum())
    assert stats["expert_rows_busiest"]["count"] == int(
        rows[0].max(-1).sum() + rows.sum(0).max(-1).sum())


def test_ids_outside_the_slice_are_clipped(server, tokens):
    ids = tokens[:1].copy()
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        inside = _infer(client, np.minimum(ids + 1000, TINY.vocab_size - 1))
        outside = _infer(client, ids + 1000)
    np.testing.assert_array_equal(inside.as_numpy("LOGITS"),
                                  outside.as_numpy("LOGITS"))


def test_a_mesh_of_two_is_refused_until_the_layer_has_its_exchange(
        monkeypatch):
    monkeypatch.setenv("TRITON_TPU_SERVE_MESH_KIMI_K2", "ep=2")
    run = language._LazyBlock(TINY, "kimi_k2", "latent_moe", "forward")
    with pytest.raises(ValueError, match="exchange"):
        run(jnp.zeros((1, S), jnp.int32))


def test_the_zoo_registers_it_without_allocating():
    from triton_client_tpu.models import zoo

    registry = ModelRegistry()
    zoo.register_all(registry)
    model = registry.get("kimi_k2")
    assert model.config.input[0].dims == [8192]
    assert model.config.output[0].dims == [20480]
    assert list(
        model.config.dynamic_batching.preferred_batch_size) == [1, 2]


# -- one expert layer for two models: every routed expert held ---------------

def _all_held_cfg(E=8, k=2):
    """What ``route`` and ``held_experts`` ask of a configuration, for a
    layer that holds every expert it routes over (block_diffusion's)."""
    import types

    return types.SimpleNamespace(
        n_routed_experts=E, routed_experts_total=E, first_expert=0,
        num_experts_per_tok=k, scoring_func="softmax", router_bias=False,
        routed_scaling_factor=1.0, router_eps=1e-20)


def _expert_blk(E, D=32, F=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": jax.random.normal(keys[0], (D, E)) * 0.5,
            "we_gate": jax.random.normal(keys[1], (E, D, F)) / math.sqrt(D),
            "we_up": jax.random.normal(keys[2], (E, D, F)) / math.sqrt(D),
            "we_down": jax.random.normal(keys[3], (E, F, D)) / math.sqrt(F)}


@pytest.mark.parametrize("T", [64, 4096])
def test_all_held_is_the_dense_form_and_the_matmul_combine(T, monkeypatch):
    """Every routed expert held: ``T*k`` pairs exactly, several passes at T
    = 4096 (the pass is cut to 2,048 pairs here), un-sorted by the inverse
    permutation.  Against every expert computed for every token and masked
    (the dense form), and against the form that holds a few of many (the
    chunk with slack, the 0/1-matmul combine) given the same pairs."""
    monkeypatch.setattr(lm, "_PAIRS_A_PASS", 2048)
    E, k = 8, 2
    cfg, blk = _all_held_cfg(E, k), _expert_blk(E)
    h = jax.random.normal(jax.random.PRNGKey(T), (T, 32))
    idx, weights = lm.route(blk, h, cfg)
    y, rows = jax.jit(lambda h, idx, w: lm.held_experts(
        blk, h, idx, w, cfg, batch=4))(h, idx, weights)
    dense = sum(
        jnp.sum(jnp.where(idx == e, weights, 0.0), -1)[:, None]
        * parts.swiglu(h, blk["we_gate"][e], blk["we_up"][e],
                       blk["we_down"][e]) for e in range(E))
    assert _rel_l2(y, dense) < 1e-5
    assert rows.shape == (4, E) and int(rows.sum()) == T * k
    np.testing.assert_array_equal(
        np.asarray(rows), np.stack([np.bincount(
            np.asarray(part).ravel(), minlength=E)
            for part in np.split(np.asarray(idx), 4)]))
    # one more expert routed over than held: the other form, the same pairs
    few = _all_held_cfg(E, k)
    few.routed_experts_total = E + 1
    other, other_rows = jax.jit(lambda h, idx, w: lm.held_experts(
        blk, h, idx, w, few, batch=4))(h, idx, weights)
    assert _rel_l2(y, other) < 1e-5
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(other_rows))


def test_all_held_reads_a_stack_of_layers_in_place():
    """``first_group``: the experts of every layer as one stack, this
    layer's groups in the middle of it; the other layers' get no rows."""
    E, k, T = 8, 2, 64
    cfg, blk = _all_held_cfg(E, k), _expert_blk(E, seed=1)
    h = jax.random.normal(jax.random.PRNGKey(5), (T, 32))
    idx, weights = lm.route(blk, h, cfg)
    y, _ = lm.held_experts(blk, h, idx, weights, cfg)
    stack = dict(blk, first_group=jnp.int32(E), **{
        name: jnp.concatenate([jnp.full_like(blk[name], 3.0), blk[name],
                               jnp.full_like(blk[name], -2.0)])
        for name in ("we_gate", "we_up", "we_down")})
    z, _ = jax.jit(lambda s: lm.held_experts(s, h, idx, weights, cfg))(stack)
    np.testing.assert_allclose(np.asarray(z), np.asarray(y), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_route_against_the_closed_form(scoring):
    """Softmax over all experts, top k, renormalised (Qwen3-MoE's); sigmoid
    with a selection bias, weights from the scores alone, times a factor
    (DeepSeek-V3's): one function, told which by the config."""
    E, k, T = 8, 3, 16
    blk = _expert_blk(E, seed=2)
    h = jax.random.normal(jax.random.PRNGKey(9), (T, 32))
    logits = np.asarray(h @ blk["router"], np.float64)
    cfg = _all_held_cfg(E, k)
    cfg.scoring_func = scoring
    if scoring == "softmax":
        scores = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        chosen_by, factor = scores, 1.0
    else:
        bias = np.linspace(-0.3, 0.3, E)
        blk["router_bias"] = jnp.asarray(bias, jnp.float32)
        cfg.router_bias, cfg.routed_scaling_factor = True, 2.5
        scores = 1.0 / (1.0 + np.exp(-logits))
        chosen_by, factor = scores + bias, 2.5
    want_idx = np.argsort(-chosen_by, axis=-1, kind="stable")[:, :k]
    picked = np.take_along_axis(scores, want_idx, -1)
    want = picked / picked.sum(-1, keepdims=True) * factor
    idx, weights = lm.route(blk, h, cfg)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights),
                           np.argsort(np.asarray(idx), -1), -1),
        np.take_along_axis(want, np.argsort(want_idx, -1), -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), factor, rtol=1e-5)


def test_route_refuses_a_scoring_it_does_not_know():
    cfg = _all_held_cfg()
    cfg.scoring_func = "tanh"
    with pytest.raises(ValueError, match="scoring_func"):
        lm.route(_expert_blk(8), jnp.zeros((4, 32)), cfg)


@pytest.mark.parametrize("k,n,want", [
    (2048, 768, (128, 2048, 768)),       # sdar_30b_a3b's gate and up: whole
    (768, 2048, (128, 768, 2048)),       # its down
    (7168, 2048, (256, 1024, 1024)),     # kimi_k2's gate and up: as PR 28
    (2048, 7168, (256, 1024, 1024)),     # its down
], ids=["sdar-up", "sdar-down", "kimi-up", "kimi-down"])
def test_the_grouped_matmuls_tile_follows_the_experts_shape(k, n, want):
    assert lm._gmm_tiling(k, n) == want
