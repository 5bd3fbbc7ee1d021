"""Sliding-window and full attention in one stack over 128 sigmoid-routed
experts and a shared one (models/window_moe.py, Trinity-Mini's block)
against the benchmark's plain reference (chipbench/references/trinity_mini.py)
at a tiny size on the CPU: hidden 64, 4 query heads over 2 key/value heads
of 16, a window of 8, one dense sliding layer then a period of (sliding,
sliding, sliding, full) over 8 experts of 32 of which a token takes 2 and a
shared one, a vocabulary of 256, a prompt of 40.

Tolerances.  With the bfloat16 weights upcast and everything computed in
float32 the program (one scan over the layers, the kinds taken by
``lax.cond``, the expert layer's grouped form) and the reference (a layer
at a time, attention in blocks of queries, an expert at a time) do the same
arithmetic in another order: 1e-5 relative on the logit row (read: 0.9e-6
to 1.3e-6).  As served (bfloat16 matrices, float32 stream) the row lies
within the band bfloat16 leaves (read: median 0.009-0.011 over 8 prompts);
int8 storage lies outside it (0.027-0.045).
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
from chipbench.tests.tiny_trinity import (  # noqa: E402
    TINY_TRINITY,
    program_config,
)
from triton_client_tpu.models import language  # noqa: E402
from triton_client_tpu.models import latent_moe as lm  # noqa: E402
from triton_client_tpu.models import parts  # noqa: E402
from triton_client_tpu.models import window_moe as wm  # noqa: E402
from triton_client_tpu.server import ModelRegistry  # noqa: E402
from triton_client_tpu.server.model import ModelStats  # noqa: E402
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module("references", "trinity_mini")
TINY = program_config(TINY_TRINITY)
S, LE, K = TINY.seq_len, TINY.n_expert_layers, TINY.num_experts_per_tok


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def params():
    return wm.init_params(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, TINY.vocab_size, (3, S)).astype(np.int32)


def _forward(params, tokens, cfg=TINY):
    out = jax.jit(lambda p, t: wm.forward(p, t, cfg))(
        params, jnp.asarray(tokens))
    return jax.tree_util.tree_map(np.asarray, out)


# -- program against reference ----------------------------------------------

def test_the_float32_block_is_the_reference(params, tokens):
    """In float32 the last position's logits are the reference's, its own
    routing equals the program's, and so do the pairs on each expert."""
    got = _forward(_f32(params), tokens)
    keep = {}
    want = REF.Reference(TINY_TRINITY).forward(tokens, keep=keep)
    assert got["logits"].shape == (3, 1, TINY.vocab_size)
    for n in range(3):
        assert _rel_l2(got["logits"][n], want["logits"][n]) < 1e-5, n
    np.testing.assert_array_equal(got["counters"]["expert_rows"],
                                  np.stack(keep["expert_rows"], axis=1))
    np.testing.assert_array_equal(got["tokens"][:, 0],
                                  got["logits"][:, 0].argmax(-1))
    # told the program's routes, nothing moves and nothing falls short
    told = REF.Reference(TINY_TRINITY).replay(tokens, got["routes"])
    np.testing.assert_array_equal(told["logits"], want["logits"])
    assert told["route_shortfall"].max() == 0.0


def test_as_served_the_band_is_bfloat16s_and_int8_lies_outside(params):
    """As served (bfloat16 matrices, float32 stream): the reference told
    the program's routes gives the row inside the band bfloat16 leaves;
    int8 storage lies outside it."""
    prompts = np.random.default_rng(4).integers(
        0, TINY.vocab_size, (8, S)).astype(np.int32)
    reference = REF.Reference(TINY_TRINITY)
    limits = TINY_TRINITY["limits"]

    def rows(p):
        got = _forward(p, prompts)
        want = reference.replay(prompts, got["routes"])
        assert want["route_shortfall"].max() < limits["route_shortfall_worst"]
        return [_rel_l2(got["logits"][n], want["logits"][n])
                for n in range(8)]

    served = rows(params)
    control = rows(wm.init_params(TINY, quantized=True))
    limit = limits["logit_rel_l2_median"]
    assert np.median(served) < limit < np.median(control), (
        np.median(served), np.median(control))
    assert max(served) < limits["logit_rel_l2_worst"] < max(control)


def test_weights_are_the_references_bit_for_bit(params):
    first = TINY.num_dense_layers
    reference = REF.Reference(TINY_TRINITY)
    for layer in range(TINY.num_hidden_layers):
        mine = {name: leaf[layer] for name, leaf in params["layers"].items()}
        want = dict(reference._attention_weights(layer))
        if layer < first:
            mine.update({n: v[layer] for n, v in params["dense"].items()})
            want.update(reference._dense_weights(layer))
        else:
            n = layer - first
            mine.update({name: v[n] for name, v in params["moe"].items()})
            want.update(reference._router_weights(layer))
            for e in (0, TINY.num_experts - 1):
                expert = reference._expert(layer, e)
                for name in ("gate", "up", "down"):
                    np.testing.assert_array_equal(
                        np.asarray(params["experts"]["we_" + name][
                            n * TINY.num_experts + e], np.float32),
                        expert[name])
        for name, value in want.items():
            np.testing.assert_array_equal(np.asarray(mine[name], np.float32),
                                          value, err_msg=f"{layer} {name}")
        for name in ("ln_attn", "ln_attn_out", "ln_ffn", "ln_ffn_out",
                     "ln_qh", "ln_kh"):
            np.testing.assert_array_equal(np.asarray(mine[name], np.float32),
                                          1.0)
    for name in ("embed", "head"):       # untied
        np.testing.assert_array_equal(np.asarray(params[name], np.float32),
                                      reference._outer[name]())


# -- the two kinds of attention ---------------------------------------------

@pytest.mark.parametrize("kind", [wm.SLIDING, wm.FULL])
def test_a_sliding_layer_sees_its_window_and_a_full_layer_every_key(params,
                                                                    kind):
    """A change at positions 0 .. 9 moves the last position's attention in
    a full layer, and not in a sliding layer of window 8 (positions 32 ..
    39 are its last row's keys); a full layer has no positions: its last
    row is the same whatever order the keys before it came in."""
    blk = {name: leaf[0] for name, leaf in _f32(params)["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, TINY.hidden_size))
    cos, sin = parts.rotary(TINY.head_dim, TINY.rope_theta, jnp.arange(S))

    def last(x):
        return np.asarray(wm._attention(blk, x, TINY, kind, cos, sin)[0, -1])

    moved = x.at[:, :10].add(1.0)
    assert np.allclose(last(x), last(moved), rtol=0, atol=0) == (
        kind == wm.SLIDING)
    shuffled = x.at[:, :-1].set(x[:, :-1][:, ::-1])
    assert np.allclose(last(x), last(shuffled), rtol=1e-5, atol=1e-5) == (
        kind == wm.FULL)


def test_the_output_is_gated_and_normed_after_the_branch(params):
    """The norm after the branch takes out any scale of ``W_o`` (the
    sandwich); the gate does not: it weighs each head and channel by its
    own ``sigmoid(h W_g)``, so a steeper gate moves the branch."""
    blk = {name: leaf[0] for name, leaf in _f32(params)["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, S, TINY.hidden_size))
    cos, sin = parts.rotary(TINY.head_dim, TINY.rope_theta, jnp.arange(S))

    def branch(**changed):
        return np.asarray(wm._attention(dict(blk, **changed), x, TINY,
                                        wm.SLIDING, cos, sin) - x)

    # up to the norm's epsilon (1e-5 beside mean squares of a hundredth)
    np.testing.assert_allclose(branch(w_o=3.0 * blk["w_o"]), branch(),
                               rtol=2e-3, atol=1e-5)
    assert not np.allclose(branch(w_g=8.0 * blk["w_g"]), branch(),
                           atol=1e-3)


def test_the_embedding_is_scaled_by_the_root_of_the_width(params, tokens):
    """``mup_enabled``: ``h = embed(ids) * sqrt(hidden_size)``; without it
    the row differs."""
    plain = dataclasses.replace(TINY, mup_enabled=False)
    a = _forward(_f32(params), tokens)["logits"]
    b = _forward(_f32(params), tokens, plain)["logits"]
    assert not np.allclose(a, b, atol=1e-3)
    assert math.sqrt(wm.TRINITY_MINI_STAGE.hidden_size) == pytest.approx(
        45.2548, rel=1e-5)


def test_the_shared_expert_is_added_to_the_routed_ones(params):
    """An expert layer's FFN is the shared expert's part plus the routed
    experts' weighted sum: with the routed experts' output matrices at zero
    the layer is the shared expert alone, and with the shared expert's the
    routed ones alone."""
    blk = {n: v[0] for n, v in _f32(params)["moe"].items()}
    blk.update({n: v[:TINY.num_experts]
                for n, v in _f32(params)["experts"].items()},
               ln_ffn=jnp.ones(TINY.hidden_size),
               ln_ffn_out=jnp.ones(TINY.hidden_size))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, TINY.hidden_size))
    h = parts.rmsnorm(x, blk["ln_ffn"], TINY.rms_norm_eps).reshape(S, -1)

    def closed(y):
        return np.asarray(x + parts.rmsnorm(
            y.reshape(1, S, -1), blk["ln_ffn_out"], TINY.rms_norm_eps))

    shared = parts.swiglu(h, blk["ws_gate"], blk["ws_up"], blk["ws_down"])
    idx, weights = lm.route(blk, h, TINY)
    routed, _ = lm.held_experts(blk, h, idx, weights, TINY)
    for zeroed, want in (("we_down", shared), ("ws_down", routed)):
        got, _, chose = wm._moe(dict(blk, **{zeroed: 0 * blk[zeroed]}), x,
                                TINY)
        np.testing.assert_allclose(np.asarray(got), closed(want), rtol=1e-5,
                                   atol=1e-5)
    got, _, _ = wm._moe(blk, x, TINY)
    np.testing.assert_allclose(np.asarray(got), closed(shared + routed),
                               rtol=1e-5, atol=1e-5)
    assert chose.shape == (1, S, K)


def test_the_router_takes_the_top_of_score_and_bias_and_scales_the_share():
    """AFMoE's router through ``latent_moe.route``: sigmoid scores, the top
    8 of score + bias, each chosen score over the chosen ones' sum (+
    1e-20), times ``route_scale`` 2.826."""
    blk = _f32(wm._layer_params(TINY, 2))
    h = jax.random.normal(jax.random.PRNGKey(5), (48, TINY.hidden_size))
    idx, weights = (np.asarray(a) for a in lm.route(blk, h, TINY))
    scores = np.asarray(jax.nn.sigmoid(h @ blk["router"]), np.float64)
    by = scores + np.asarray(blk["router_bias"])
    np.testing.assert_array_equal(
        np.sort(idx, -1), np.sort(np.argsort(-by, -1)[:, :K], -1))
    picked = np.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-20) * 2.826,
        rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 2.826, rtol=1e-5)
    # the bias chooses: some token takes another expert without it
    plain, _ = lm.route(dict(blk, router_bias=jnp.zeros_like(
        blk["router_bias"])), h, TINY)
    assert (np.sort(np.asarray(plain), -1) != np.sort(idx, -1)).any()


def test_the_counters_count_the_windows_pairs(params, tokens):
    """``window_queries`` = rows x sliding layers, ``window_pairs`` = the
    keys each row sees under the window's mask, summed: at 40 and a window
    of 8, sum_t min(t + 1, 8) = 292 a layer, four sliding layers."""
    got = _forward(params, tokens)["counters"]
    assert got["window_queries"].tolist() == [4 * S] * 3
    pairs = sum(min(t + 1, 8) for t in range(S))
    assert pairs == 292
    assert got["window_pairs"].tolist() == [4 * pairs] * 3
    # the same count as the mask the reference and the kernel apply
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    assert int(((j <= i) & (i - j < 8)).sum()) == pairs
    assert got["expert_rows"].shape == (3, LE, TINY.num_experts)
    assert (got["expert_rows"].sum(-1) == S * K).all()


def test_a_row_that_is_padding_moves_no_other_row(params, tokens):
    """A row beside it changes no row's routes, counters or (beyond the
    order of a sum) logits."""
    alone = _forward(_f32(params), tokens[:1])
    beside = _forward(_f32(params), np.concatenate(
        [tokens[:1], np.zeros((1, S), np.int32)]))
    np.testing.assert_array_equal(beside["routes"][0], alone["routes"][0])
    np.testing.assert_array_equal(beside["tokens"][0], alone["tokens"][0])
    assert _rel_l2(beside["logits"][0], alone["logits"][0]) < 1e-5
    np.testing.assert_array_equal(beside["counters"]["expert_rows"][0],
                                  alone["counters"]["expert_rows"][0])


# -- the configuration -------------------------------------------------------

def test_the_constant_is_the_configuration_file():
    """``TRINITY_MINI_STAGE`` is ``chipbench/configs/trinity_mini.json`` key
    by key; the block's FLOP count is the benchmark's own, the windowed
    kernel's work the exact pairs times 16,384; the file's bytes are what
    ``init_params`` draws, by shape."""
    cfg = load_json(ROOT, "chipbench", "configs", "trinity_mini.json")
    assert program_config(cfg) == wm.TRINITY_MINI_STAGE
    yardstick = load_module("flop_counts", "trinity_mini")
    for mine, theirs in ((wm.TRINITY_MINI_STAGE, cfg), (TINY, TINY_TRINITY)):
        assert wm.flops_per_inference(mine) == pytest.approx(
            yardstick.flops_per_inference(theirs), rel=1e-12)
    assert yardstick.flops_per_inference(cfg) == pytest.approx(44.70e12,
                                                               rel=1e-3)
    work = yardstick.kernel_work(cfg, "window_attention")["flops"]
    assert work == 16_384 * 65_012_736 == 16_384 * wm.attended_pairs(
        wm.TRINITY_MINI_STAGE, wm.SLIDING)
    assert work == pytest.approx(1.0652e12, rel=1e-4)
    assert wm.attended_pairs(wm.TRINITY_MINI_STAGE, wm.FULL) == 536_887_296
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: wm.init_params(wm.TRINITY_MINI_STAGE)))
    count = sum(int(np.prod(leaf.shape)) for leaf in shapes)
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in shapes)
    assert (count, held) == (4_306_554_880, 8_613_110_784)
    assert "4,306,554,880 parameters = 8,613,110,784 B" in \
        cfg["deployment"]["bytes"]
    assert held / 16e9 == pytest.approx(0.538, abs=1e-3)


@pytest.mark.parametrize("bad,match", [
    ({"num_key_value_heads": 3}, "key/value heads"),
    ({"layer_types": (wm.SLIDING,) * 4 + ("window",)}, "names 5 layers"),
    ({"layer_types": (wm.SLIDING,) * 4}, "names 4 layers"),
    ({"num_dense_layers": 5}, "expert layer"),
    ({"num_dense_layers": 0}, "at least one"),
    ({"score_func": "softmax"}, "sigmoid"),
    ({"route_norm": False}, "renormalises"),
    ({"sliding_window": 0}, "sliding_window")])
def test_a_shape_the_block_cannot_run_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(TINY, **bad)


# -- the served path ---------------------------------------------------------

def test_device_counters_leave_out_the_rows_the_batcher_padded():
    stats = ModelStats()
    counters = {"window_queries": np.array([160, 160]),
                "window_pairs": np.array([1168, 1168])}
    stats.queue_device_counters(counters, 1, S)  # the second row is padding
    entries = stats.extension_entries()
    assert entries["window_queries"] == {"count": 160, "ns": 0}
    assert entries["window_pairs"] == {"count": 1168, "ns": 0}


@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    registry.register_model(language.make_trinity_mini(TINY))
    with ServerHarness(registry) as h:
        yield h


def test_the_factory_serves_the_step_and_its_counters(server, params, tokens):
    """One prompt a request, a bucket of one: the answer is the step's, and
    the counters reach the statistics."""
    model = language.make_trinity_mini(TINY)
    batching = model.config.dynamic_batching
    assert model.config.max_batch_size == 1
    assert list(batching.preferred_batch_size) == [1]
    want = _forward(params, tokens[:1])
    inp = grpcclient.InferInput("INPUT_IDS", [1, S], "INT32")
    inp.set_data_from_numpy(tokens[:1])
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        result = client.infer("trinity_mini", [inp])
    np.testing.assert_array_equal(result.as_numpy("TOKENS"), want["tokens"])
    np.testing.assert_array_equal(result.as_numpy("LOGITS"), want["logits"])
    np.testing.assert_array_equal(result.as_numpy("ROUTES"), want["routes"])
    assert result.as_numpy("ROUTES").shape == (1, LE, S, K)
    assert result.as_numpy("DEVICE_COUNTER.window_pairs") is None
    stats = server.core.statistics("trinity_mini")[0]["inference_stats"]
    assert stats["window_queries"]["count"] == 4 * S
    assert stats["window_pairs"]["count"] == 4 * 292
    assert stats["expert_tokens"]["count"] == S * LE
    assert stats["expert_rows"]["count"] == K * S * LE
    assert stats["dsa_pairs"]["count"] == 0


def test_the_other_blocks_keep_their_buckets():
    """The buckets are ``max_batch_size`` and its half, one at a batch of
    one: the other drawn blocks keep theirs."""
    from chipbench.tests import tiny_kimi, tiny_lfm2

    lfm2 = language.make_lfm2_8b_a1b(tiny_lfm2.program_config(
        tiny_lfm2.TINY_LFM2))
    kimi = language.make_kimi_k2(tiny_kimi.program_config(
        tiny_kimi.TINY_KIMI))
    assert list(lfm2.config.dynamic_batching.preferred_batch_size) == [8, 16]
    assert list(kimi.config.dynamic_batching.preferred_batch_size) == [1, 2]


def test_the_int8_control_is_served_from_the_same_factory(monkeypatch,
                                                          tokens):
    monkeypatch.setenv("TRITON_TPU_QUANT_TRINITY_MINI", "int8")
    run = language._LazyBlock(TINY, "trinity_mini", "window_moe", "forward")
    out = run(jnp.asarray(tokens[:1]))
    held = run._params
    assert held["layers"]["w_q"].dtype == held["moe"]["we_gate"].dtype \
        == held["dense"]["w_gate"].dtype == jnp.int8
    assert "w_g_scale" in held["layers"] and held["experts"] == {}
    assert held["moe"]["router"].dtype == jnp.bfloat16    # the router stays
    assert np.isfinite(np.asarray(out["logits"])).all()


def test_the_zoo_registers_it_without_allocating():
    from triton_client_tpu.models import zoo

    registry = ModelRegistry()
    zoo.register_all(registry)
    model = registry.get("trinity_mini")
    assert model.config.input[0].dims == [32768]
    assert [o.name for o in model.config.output] == ["TOKENS", "LOGITS",
                                                     "ROUTES"]
    assert list(model.config.output[2].dims) == [4, 32768, 8]
