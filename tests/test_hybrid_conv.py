"""Gated short convolutions beside grouped-query attention
(models/hybrid_conv.py) against the benchmark's plain reference
(chipbench/references/lfm2_8b_a1b.py) at a tiny size on the CPU: hidden 64, 8
query heads over 2 key/value heads of 8, a dense FFN of 224 in the two
leading conv layers, then two periods of (attention, conv, conv, conv) over
8 experts of 56 of which a token takes 2, a vocabulary of 256, a prompt of
16 and 6 new tokens.

Tolerances.  With the bfloat16 weights upcast and everything computed in
float32 the program (kernel-shaped attention, a scan over periods, two kinds
of state handed from the prefill to the decode steps) and the reference (one
full causal forward, a layer at a time, no state) do the same arithmetic in
another order: 1e-5 relative on a logit row (read: 1e-6 to 2e-6).  A planted
fault reads 0.3 and more.  The tokens are compared exactly: at these sizes
no arg-max lies within float32's rounding of its runner-up.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import triton_client_tpu.grpc as grpcclient  # noqa: E402
from chipbench.files import load_json, load_module  # noqa: E402
from chipbench.tests import tiny_kimi, tiny_lfm2, tiny_sdar  # noqa: E402
from chipbench.tests.tiny_lfm2 import TINY_LFM2, program_config  # noqa: E402
from triton_client_tpu.models import block_diffusion as bd  # noqa: E402
from triton_client_tpu.models import hybrid_conv as hc  # noqa: E402
from triton_client_tpu.models import language  # noqa: E402
from triton_client_tpu.models import latent_moe as lm  # noqa: E402
from triton_client_tpu.server import ModelRegistry  # noqa: E402
from triton_client_tpu.server.model import ModelStats  # noqa: E402
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module("references", "lfm2_8b_a1b")
TINY = program_config(TINY_LFM2)
P, G = TINY.seq_len, TINY.new_tokens
LE, K = TINY.n_expert_layers, TINY.num_experts_per_tok


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def params():
    return hc.init_params(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, TINY.vocab_size, (3, P)).astype(np.int32)


@pytest.fixture(scope="module")
def forced():
    return np.random.default_rng(1).integers(
        0, TINY.vocab_size, (3, G - 1)).astype(np.int32)


def _generate(params, tokens, cfg=TINY):
    out = jax.jit(lambda p, t: hc.generate(p, t, cfg))(
        params, jnp.asarray(tokens))
    return jax.tree_util.tree_map(np.asarray, out)


def _through_the_state(params, prompt, forced, cfg=TINY, hand_over=None):
    """Prefill, then one decode step a forced token: the logits at the
    prompt's last position and at every forced one ``[b, 1 + steps, V]``.
    ``hand_over`` alters the state between the prefill and the first decode
    step (a planted fault)."""

    @jax.jit
    def run(p, prompt, forced):
        logits, state, _, _ = hc.prefill(p, prompt, cfg)
        if hand_over is not None:
            state = jax.tree_util.tree_map(hand_over, state)
        rows = [logits]
        for i in range(forced.shape[1]):
            logits, state, _, _ = hc.decode_step(
                p, state, forced[:, i], prompt.shape[1] + i, cfg)
            rows.append(logits)
        return jnp.stack(rows, axis=1)

    return np.asarray(run(params, jnp.asarray(prompt), jnp.asarray(forced)))


def _file_cfg(**changed):
    return dict(TINY_LFM2, **changed)


# -- program against reference ----------------------------------------------

def test_prefill_then_decode_is_the_full_forward_at_every_position(
        params, tokens, forced):
    """Teacher-forced on tokens of the test's own: the prefill's last
    position and each decode step after it, through both kinds of state,
    give the logits the reference's one causal forward gives there."""
    logits = _through_the_state(_f32(params), tokens, forced)
    want = REF.Reference(TINY_LFM2).forward(
        np.concatenate([tokens, forced], axis=1), np.arange(P - 1, P + G - 1))
    assert logits.shape == (3, G, TINY.vocab_size)
    for n in range(3):
        for i in range(G):
            assert _rel_l2(logits[n, i], want["logits"][n, i]) < 1e-5, (n, i)


def test_the_generation_is_greedy_and_the_references(params, tokens):
    """``generate`` in float32: the tokens are the reference's own greedy
    trajectory, the three rows returned are the reference's at those
    positions (routed as told: nothing to tell in float32), and the
    counters read a step a token."""
    got = _generate(_f32(params), tokens)
    reference = REF.Reference(TINY_LFM2)
    own = reference.generate(tokens)
    np.testing.assert_array_equal(got["tokens"], own["tokens"])
    for n in range(3):
        for row, at in enumerate((0, 1, G - 1)):
            assert _rel_l2(got["logits"][n, row], own["logits"][n, at]) < 1e-5
    keep = {}
    reference.forward(np.concatenate([tokens, got["tokens"][:, :G - 1]], 1),
                      [P - 1], keep=keep)
    replayed = reference.replay(tokens, got["tokens"], got["routes"])
    assert replayed["route_shortfall"].max() == 0.0
    assert got["routes"].shape == (3, P + G - 1, LE, K)
    assert replayed["route_shortfall"].shape == (3, P + G - 1)
    # what the program chose is what the reference chooses by itself
    np.testing.assert_array_equal(
        np.stack(keep["expert_rows"], axis=1),
        (got["routes"][..., None] == np.arange(TINY.num_experts)).sum(
            axis=(1, 3)))
    np.testing.assert_array_equal(got["counters"]["decode_steps"], [G - 1] * 3)
    np.testing.assert_array_equal(got["counters"]["decode_tokens"],
                                  [G - 1] * 3)
    # every pair of prompt and answer but the last token, as the reference
    # routes them
    np.testing.assert_array_equal(
        got["counters"]["expert_rows"],
        np.stack(keep["expert_rows"], axis=1))
    assert got["counters"]["expert_rows"].sum() == 3 * LE * K * (P + G - 1)
    # experts with a row, a decode step and layer each, over the rows 0..r
    touched = got["counters"]["experts_touched"]
    assert (np.diff(touched) >= 0).all()
    assert (G - 1) * LE * 1 <= touched[0] <= (G - 1) * LE * K
    assert touched[-1] <= (G - 1) * LE * min(3 * K, TINY.num_experts)


def test_the_state_after_the_prefill_is_the_last_rows_and_the_prompts_keys(
        params, tokens):
    """A conv layer hands over ``z`` of the prompt's last two positions, in
    their order; an attention layer the prompt's keys and values and room,
    still empty, for the answer's."""
    _, state, _, _ = jax.jit(lambda p, t: hc.prefill(p, t, TINY))(
        _f32(params), jnp.asarray(tokens))
    keep = {}
    REF.Reference(TINY_LFM2).forward(tokens, [P - 1], keep=keep)
    first, p = TINY.num_dense_layers, TINY.period
    seen = 0
    for layer, kind in enumerate(TINY.layer_types):
        if layer < first:
            held = state["dense"][layer]
            mine = jax.tree_util.tree_map(lambda a: a[0], held)
        else:
            held = state["periods"][(layer - first) % p]
            mine = jax.tree_util.tree_map(
                lambda a: a[(layer - first) // p], held)
        if kind == hc.CONV:
            assert mine.shape == (3, TINY.conv_L_cache - 1, TINY.hidden_size)
            np.testing.assert_allclose(
                np.asarray(mine), keep["z"][layer][:, P - 2:P],
                rtol=1e-4, atol=1e-5)
            seen += 1
        else:
            assert keep["z"][layer] is None
            for cache in mine:
                assert cache.shape == (3, TINY.num_key_value_heads, P + G,
                                       TINY.head_dim)
                assert float(jnp.abs(cache[:, :, :P]).min(axis=-1).max()) > 0
                assert float(jnp.abs(cache[:, :, P:]).max()) == 0.0
    assert seen == TINY.layer_types.count(hc.CONV) == 8


@pytest.mark.parametrize("fault", [
    "conv_rows_swapped", "conv_rows_forgotten", "conv_rows_of_the_padded_end",
    "cache_of_another_row"])
def test_a_wrong_hand_over_fails_the_comparators_limits(
        params, tokens, forced, fault):
    """The commonest faults of a prefill that hands two kinds of state on:
    the last two rows in the wrong order, none at all, rows taken from a
    padded end, the keys and values of another row of the batch.  The first decode step reads what
    was handed over and nothing else: its row reads far outside the limits,
    whatever the steps between pass on to the closing row (a conv layer
    itself forgets in two positions), which is why the served model returns
    the first decode step's row too."""
    alter = {
        "conv_rows_swapped": lambda a: a[:, :, ::-1] if a.ndim == 4 else a,
        "conv_rows_forgotten": lambda a: a * 0 if a.ndim == 4 else a,
        "conv_rows_of_the_padded_end":
            lambda a: jnp.roll(a, 1, axis=2).at[:, :, 0].set(0)
            if a.ndim == 4 else a,
        "cache_of_another_row": lambda a: jnp.roll(a, 1, axis=1)
        if a.ndim == 5 else a,
    }[fault]
    logits = _through_the_state(_f32(params), tokens, forced,
                                hand_over=alter)
    want = REF.Reference(TINY_LFM2).forward(
        np.concatenate([tokens, forced], axis=1),
        np.arange(P - 1, P + G - 1))["logits"]
    limits = TINY_LFM2["limits"]
    for n in range(3):
        # the prefill's own row is sound
        assert _rel_l2(logits[n, 0], want[n, 0]) < 1e-5
        assert _rel_l2(logits[n, 1], want[n, 1]) \
            > 4 * limits["logit_rel_l2_median"], (fault, n)
    # here, five steps on, the closing row has not forgotten either
    assert np.median([_rel_l2(logits[n, -1], want[n, -1])
                      for n in range(3)]) > limits["logit_rel_l2_median"]


@pytest.mark.parametrize("length", [1, 2, 5])
def test_a_prompt_shorter_than_the_conv_window(params, length):
    """A prompt of fewer positions than ``conv_L_cache``: the rows before
    position 0 are zeros, in the prefill and in what it hands over."""
    prompt = np.random.default_rng(2).integers(
        0, TINY.vocab_size, (2, length)).astype(np.int32)
    forced = np.random.default_rng(3).integers(
        0, TINY.vocab_size, (2, 4)).astype(np.int32)
    cfg = dataclasses.replace(TINY, seq_len=length, new_tokens=5)
    logits = _through_the_state(_f32(params), prompt, forced, cfg)
    want = REF.Reference(TINY_LFM2).forward(
        np.concatenate([prompt, forced], axis=1),
        np.arange(length - 1, length + 4))["logits"]
    for n in range(2):
        for i in range(5):
            assert _rel_l2(logits[n, i], want[n, i]) < 1e-5, (n, i)


def test_a_row_that_is_padding_moves_no_other_row(params, tokens):
    """The batcher pads a bucket with rows of zeros: the real rows' answer
    is what they give alone, and the device's counters leave the padding's
    pairs to the padding."""
    alone = _generate(_f32(params), tokens[:1])
    padded = _generate(_f32(params), np.concatenate(
        [tokens[:1], np.zeros((2, P), np.int32)]))
    np.testing.assert_array_equal(padded["tokens"][0], alone["tokens"][0])
    np.testing.assert_array_equal(padded["routes"][0], alone["routes"][0])
    assert _rel_l2(padded["logits"][0], alone["logits"][0]) < 1e-5
    np.testing.assert_array_equal(padded["counters"]["expert_rows"][0],
                                  alone["counters"]["expert_rows"][0])
    assert padded["counters"]["experts_touched"][0] \
        == alone["counters"]["experts_touched"][0]


def test_a_pattern_that_does_not_repeat_is_one_period(tokens):
    """Twelve of the tiny pattern's layers: the ten expert layers repeat
    nothing, so the scan has one step of ten positions; the published 24
    layers likewise (22), the published cut four at a time."""
    cfg = program_config(_file_cfg(num_hidden_layers=12))
    assert (TINY.period, cfg.period) == (4, 10)
    got = _generate(_f32(hc.init_params(cfg)), tokens, cfg)
    own = REF.Reference(_file_cfg(num_hidden_layers=12)).generate(tokens)
    np.testing.assert_array_equal(got["tokens"], own["tokens"])
    for n in range(3):
        assert _rel_l2(got["logits"][n, 2], own["logits"][n, G - 1]) < 1e-5
    published = load_json(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")
    assert program_config(dict(published, num_hidden_layers=24)).period == 22
    assert hc.LFM2_8B_A1B_STAGE.period == 4


def test_as_served_the_band_is_bfloat16s_and_int8_lies_outside(params):
    """As served (bfloat16 matrices and state, float32 stream): the
    reference, teacher-forced on the program's tokens and told its routes,
    gives the three rows inside the band that bfloat16 leaves; int8 storage
    lies outside it."""
    prompts = np.random.default_rng(4).integers(
        0, TINY.vocab_size, (8, P)).astype(np.int32)
    reference = REF.Reference(TINY_LFM2)

    def rows(p):
        got = _generate(p, prompts)
        np.testing.assert_array_equal(got["tokens"][:, [0, 1, -1]],
                                      got["logits"].argmax(-1))
        want = reference.replay(prompts, got["tokens"], got["routes"])
        assert want["route_shortfall"].max() < TINY_LFM2["limits"][
            "route_shortfall_worst"]
        return [_rel_l2(got["logits"][n, r], want["logits"][n, r])
                for n in range(8) for r in range(3)]

    served = rows(params)
    control = rows(hc.init_params(TINY, quantized=True))
    limit = TINY_LFM2["limits"]["logit_rel_l2_median"]
    assert np.median(served) < limit < np.median(control), (
        np.median(served), np.median(control))
    assert max(served) < TINY_LFM2["limits"]["logit_rel_l2_worst"]


def test_weights_are_the_references_bit_for_bit(params):
    """Drawn as the reference's own programs draw them (a draw compiled
    alone and one not compiled can round an element in ten thousand
    apart)."""
    first, p = TINY.num_dense_layers, TINY.period
    reference = REF.Reference(TINY_LFM2)
    for layer, kind in enumerate(TINY.layer_types):
        if layer < first:
            mine = params["dense"][layer]
            ffn = reference._dense_weights(layer)
        else:
            at = (layer - first) // p
            mine = {k: v[at]
                    for k, v in params["periods"][(layer - first) % p].items()}
            ffn = reference._router_weights(layer)
            for e in (0, TINY.num_experts - 1):
                want = reference._expert(layer, e)
                row = (layer - first) * TINY.num_experts + e
                for name in ("gate", "up", "down"):
                    np.testing.assert_array_equal(
                        np.asarray(params["experts"]["we_" + name][row],
                                   np.float32), want[name])
        for name, want in {**reference._op_weights[kind](layer),
                           **ffn}.items():
            np.testing.assert_array_equal(np.asarray(mine[name], np.float32),
                                          want, err_msg=f"{layer} {name}")
        for name in ("ln_op", "ln_ffn"):
            np.testing.assert_array_equal(np.asarray(mine[name], np.float32),
                                          1.0)
    np.testing.assert_array_equal(np.asarray(params["embed"], np.float32),
                                  reference._embedding())
    assert "head" not in params            # tied: the embedding is the head


# -- the router --------------------------------------------------------------

def _router_inputs():
    blk = _f32(hc._layer_params(TINY, 3))
    h = jax.random.normal(jax.random.PRNGKey(5), (48, TINY.hidden_size),
                          jnp.float32)
    return blk, h


def test_the_bias_selects_and_the_scores_alone_weigh():
    """The top k is taken of score + bias; a kept expert's weight is its
    score over the chosen scores' sum (+ 1e-6): a bias that changes the
    chosen set changes a kept expert's weight through that sum alone."""
    blk, h = _router_inputs()
    scores = np.asarray(jax.nn.sigmoid(h @ blk["router"]), np.float64)
    plain, plain_w = lm.route(dict(blk, router_bias=jnp.zeros_like(
        blk["router_bias"])), h, TINY)
    bias = np.zeros(TINY.num_experts, np.float32)
    bias[5] = 10.0                          # expert 5 is always chosen now
    idx, weights = (np.asarray(a) for a in lm.route(
        dict(blk, router_bias=jnp.asarray(bias)), h, TINY))
    assert (idx == 5).any(axis=-1).all()
    assert ((np.asarray(plain) == 5).any(axis=-1)).mean() < 0.9
    picked = np.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # the bias itself is in no weight: expert 5's is its score's share
    assert weights.max() <= 1.0
    # where the chosen set did not change, nothing did
    same = (np.sort(idx, -1) == np.sort(np.asarray(plain), -1)).all(-1)
    assert same.any() and not same.all()
    np.testing.assert_allclose(np.sort(weights[same], -1),
                               np.sort(np.asarray(plain_w)[same], -1),
                               rtol=1e-6)


def test_the_sum_is_taken_with_this_blocks_epsilon():
    """Scores so small that the epsilon decides: ``1e-6`` under the sum
    here, ``1e-20`` in ``kimi_k2`` and ``sdar_30b_a3b``; each from its
    config, no caller's option."""
    blk, h = _router_inputs()
    far = dict(blk, router=-jnp.abs(blk["router"]) * 40.0)
    h = jnp.abs(h)
    scores = np.asarray(jax.nn.sigmoid(h @ far["router"]), np.float64)
    idx, weights = (np.asarray(a, np.float64)
                    for a in lm.route(far, h, TINY))
    picked = np.take_along_axis(scores, idx.astype(int), axis=-1)
    assert picked.sum(-1).max() < 1e-7
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-4)
    assert weights.sum(-1).max() < 0.1     # the epsilon holds them down
    kimis = dataclasses.replace(TINY, router_eps=1e-20)
    _, renormed = lm.route(far, h, kimis)
    np.testing.assert_allclose(np.asarray(renormed).sum(-1), 1.0, rtol=1e-4)
    assert hc.LFM2_8B_A1B_STAGE.router_eps == 1e-6
    assert lm.KIMI_K2_EP32_SHARE.router_eps == 1e-20
    assert bd.SDAR_30B_A3B_STAGE.router_eps == 1e-20


def _parents_route(blk, h, cfg):
    """``latent_moe.route`` as the parent commit had it, the constant in
    its place."""
    logits = jnp.dot(h, blk["router"], preferred_element_type=jnp.float32)
    scores = (jax.nn.softmax(logits, axis=-1)
              if cfg.scoring_func == "softmax" else jax.nn.sigmoid(logits))
    chosen_by = scores + blk["router_bias"] if cfg.router_bias else scores
    _, idx = jax.lax.top_k(chosen_by, cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
               * cfg.routed_scaling_factor)
    return idx, weights


@pytest.mark.parametrize("name", ["kimi_k2", "sdar_30b_a3b"])
def test_route_is_bit_identical_to_the_parents_for_the_other_blocks(name):
    if name == "kimi_k2":
        cfg = tiny_kimi.program_config(tiny_kimi.TINY_KIMI)
        blk = lm._layer_params(cfg, cfg.first_k_dense_replace)
    else:
        cfg = tiny_sdar.program_config(tiny_sdar.TINY_SDAR)
        blk = bd._layer_params(cfg, 0)
    h = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    for run in (lambda f: f(blk, h, cfg),
                lambda f: jax.jit(lambda b, x: f(b, x, cfg))(blk, h)):
        idx, weights = run(lm.route)
        want_idx, want = run(_parents_route)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
        np.testing.assert_array_equal(np.asarray(weights), np.asarray(want))


# -- the configuration -------------------------------------------------------

def test_the_constant_is_the_configuration_file():
    """``LFM2_8B_A1B_STAGE`` is ``chipbench/configs/lfm2_8b_a1b.json`` key
    by key, the two FLOP counts agree, and the file's bytes and parameter
    counts are what ``init_params`` draws, by shape."""
    cfg = load_json(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")
    assert program_config(cfg) == hc.LFM2_8B_A1B_STAGE
    yardstick = load_module("flop_counts", "lfm2_8b_a1b")
    assert hc.flops_per_inference(hc.LFM2_8B_A1B_STAGE) == pytest.approx(
        yardstick.flops_per_inference(cfg), rel=1e-12)
    assert hc.flops_per_inference(TINY) == pytest.approx(
        yardstick.flops_per_inference(TINY_LFM2), rel=1e-12)
    assert yardstick.flops_per_inference(cfg) == pytest.approx(0.9173e12,
                                                               rel=1e-3)
    shapes = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: hc.init_params(hc.LFM2_8B_A1B_STAGE)))
    count = sum(int(np.prod(leaf.shape)) for leaf in shapes)
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in shapes)
    assert (count, held) == (4_667_077_376, 9_334_155_520)
    assert "4,667,077,376 parameters = 9,334,155,520 B" in \
        cfg["deployment"]["bytes"]
    assert held / 16e9 == pytest.approx(0.583, abs=1e-3)
    # the whole published model, tied: the published "8.3B"
    whole = program_config(dict(cfg, num_hidden_layers=24))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: hc.init_params(whole))))
    assert count == 8_339_930_560
    assert "8,339,930,560" in cfg["deployment"]["bytes"]


@pytest.mark.parametrize("bad,match", [
    ({"num_key_value_heads": 3}, "key/value heads"),
    ({"layer_types": ("conv",) * 9 + ("window",)}, "'conv' or"),
    ({"layer_types": ("conv",) * 9}, "names 9 layers"),
    ({"num_dense_layers": 10}, "expert layer"),
    ({"conv_bias": True}, "no convolution bias"),
    ({"norm_topk_prob": False}, "renormalises"),
    ({"conv_L_cache": 1}, "at least 2"), ({"new_tokens": 0}, "at least 2")])
def test_a_shape_the_block_cannot_run_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(TINY, **bad)


# -- the served path ---------------------------------------------------------

def test_device_counters_leave_out_the_rows_the_batcher_padded():
    stats = ModelStats()
    counters = {"decode_steps": np.array([5, 5]),
                "decode_tokens": np.array([5, 5]),
                "experts_touched": np.array([70, 110])}
    stats.queue_device_counters(counters, 1, 21)  # the second row is padding
    entries = stats.extension_entries()
    assert entries["decode_steps"] == {"count": 5, "ns": 0}
    assert entries["decode_tokens"] == {"count": 5, "ns": 0}
    assert entries["experts_touched"] == {"count": 70, "ns": 0}
    stats.queue_device_counters(counters, 2, 21)
    assert (stats.decode_steps, stats.decode_tokens) == (15, 15)
    assert stats.experts_touched == 180


@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    registry.register_model(language.make_lfm2_8b_a1b(TINY))
    with ServerHarness(registry) as h:
        yield h


def test_the_factory_serves_the_generation_and_its_counters(server, params,
                                                            tokens):
    inp = grpcclient.InferInput("INPUT_IDS", list(tokens.shape), "INT32")
    inp.set_data_from_numpy(tokens)
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        result = client.infer("lfm2_8b_a1b", [inp])
    want = _generate(params, np.concatenate(
        [tokens, np.zeros((8 - len(tokens), P), np.int32)]))
    np.testing.assert_array_equal(result.as_numpy("TOKENS"),
                                  want["tokens"][:3])
    np.testing.assert_array_equal(result.as_numpy("LOGITS"),
                                  want["logits"][:3])
    np.testing.assert_array_equal(result.as_numpy("ROUTES"),
                                  want["routes"][:3])
    assert result.as_numpy("LOGITS").shape == (3, 3, TINY.vocab_size)
    assert result.as_numpy("ROUTES").shape == (3, P + G - 1, LE, K)
    assert result.as_numpy("DEVICE_COUNTER.decode_steps") is None
    stats = server.core.statistics("lfm2_8b_a1b")[0]["inference_stats"]
    # the padded rows' steps, tokens and pairs are not counted
    assert stats["decode_steps"]["count"] == 3 * (G - 1)
    assert stats["decode_tokens"]["count"] == 3 * (G - 1)
    assert stats["expert_tokens"]["count"] == 3 * (P + G - 1) * LE
    assert stats["expert_rows"]["count"] == K * stats["expert_tokens"][
        "count"]
    assert stats["experts_touched"]["count"] == int(
        want["counters"]["experts_touched"][2])
    assert stats["loop_steps"]["count"] == 0


def test_the_int8_control_is_served_from_the_same_factory(monkeypatch,
                                                          tokens):
    monkeypatch.setenv("TRITON_TPU_QUANT_LFM2_8B_A1B", "int8")
    run = language._LazyBlock(TINY, "lfm2_8b_a1b", "hybrid_conv", "generate")
    out = run(jnp.asarray(tokens))
    held = run._params["periods"][1]
    assert held["w_in"].dtype == held["we_gate"].dtype == jnp.int8
    assert "w_out_scale" in held and run._params["experts"] == {}
    assert run._params["dense"][0]["w_gate"].dtype == jnp.int8
    assert held["conv_w"].dtype == jnp.bfloat16     # the taps stay
    assert np.isfinite(np.asarray(out["logits"])).all()


def test_a_mesh_of_two_is_refused(monkeypatch):
    monkeypatch.setenv("TRITON_TPU_SERVE_MESH_LFM2_8B_A1B", "tp=2")
    run = language._LazyBlock(TINY, "lfm2_8b_a1b", "hybrid_conv", "generate")
    with pytest.raises(ValueError, match="exchange"):
        run(jnp.zeros((1, P), jnp.int32))


def test_the_zoo_registers_it_without_allocating():
    from triton_client_tpu.models import zoo

    registry = ModelRegistry()
    zoo.register_all(registry)
    model = registry.get("lfm2_8b_a1b")
    assert model.config.input[0].dims == [512]
    assert [list(o.dims) for o in model.config.output] == [
        [32], [3, 65536], [543, 12, 4]]
    assert model.config.max_batch_size == 16
    assert list(model.config.dynamic_batching.preferred_batch_size) == [8, 16]
    assert tiny_lfm2.make_tiny_lfm2().config.output[1].dims == [3, 256]
