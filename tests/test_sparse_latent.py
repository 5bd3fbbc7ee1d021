"""The learned-sparse-attention block (models/sparse_latent.py) and its kernel
(ops/sparse_attention.py) against the benchmark's plain reference
(chipbench/references/hy4_preview.py) at a tiny size on the CPU, the
published structure kept: a dense layer with a full indexer, an expert layer
with one, two expert layers that reuse its choice, the MTP module, four
streams, 48 keys chosen of up to 64, 4 of 16 experts held.

The reference is told the program's choices (its ``chosen`` bit planes and
``routes``) and measures how far they lie under its own.  Tolerances.  With
the bfloat16 weights upcast and everything computed in float32 the program
and the reference do the same arithmetic in another order: 1e-5 relative
(read: 1.4e-6), and no shortfall.  As served (bfloat16 activations, f32
accumulation) the median row reads 0.010-0.015 over 11 sets of eight prompts;
int8 storage 0.036-0.048.
"""

import dataclasses
import functools
import hashlib
import inspect

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import triton_client_tpu.grpc as grpcclient  # noqa: E402
from chipbench.files import load_json, load_module  # noqa: E402
from chipbench.tests.tiny_hy4 import TINY_HY4, program_config  # noqa: E402
from triton_client_tpu.models import language  # noqa: E402
from triton_client_tpu.models import latent_moe as lm  # noqa: E402
from triton_client_tpu.models import parts  # noqa: E402
from triton_client_tpu.models import sparse_latent as sl  # noqa: E402
from triton_client_tpu.ops import sparse_attention as sa  # noqa: E402
from triton_client_tpu.ops import stream_mixing as sm  # noqa: E402
from triton_client_tpu.server import ModelRegistry  # noqa: E402
from triton_client_tpu.server.model import ModelStats  # noqa: E402
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402

REF = load_module("references", "hy4_preview")
TINY = program_config(TINY_HY4)
S = TINY.seq_len


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _rel_l2_rows(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


@pytest.fixture(scope="module")
def params():
    return sl.init_params(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, TINY.vocab_size, (3, S)).astype(np.int32)


def _forward(params, tokens, cfg=TINY):
    return jax.jit(lambda p, t: sl.forward(p, t, cfg))(
        params, jnp.asarray(tokens))


@pytest.fixture(scope="module")
def float32_out(params, tokens):
    return _forward(_f32(params), tokens)


def _replay(out, tokens, chosen=None):
    """The reference told ``out``'s choices (or ``chosen`` for its bit
    planes) and its first tokens."""
    return REF.Reference(TINY_HY4).replay(
        tokens, np.asarray(out["tokens"][:, 0]),
        np.asarray(out["chosen"] if chosen is None else chosen),
        np.asarray(out["routes"]))


@pytest.fixture(scope="module")
def wanted(float32_out, tokens):
    return _replay(float32_out, tokens)


# -- program against reference ----------------------------------------------

def test_float32_program_is_the_reference(float32_out, wanted):
    """Both rows: the main head's and the MTP module's (given the program's
    own first token); every full indexer's keys and every token's experts
    are the reference's own top k."""
    logits = float32_out["logits"]
    assert logits.shape == (3, 2, TINY.vocab_size)
    assert _rel_l2_rows(logits, wanted["logits"]).max() < 1e-5
    assert wanted["index_shortfall"].max() < 1e-4
    assert wanted["route_shortfall"].max() < 1e-6
    np.testing.assert_array_equal(
        np.asarray(float32_out["tokens"]),
        np.argmax(np.asarray(logits), axis=-1))


def test_served_precision_stays_inside_its_band(params, tokens):
    out = _forward(params, tokens)
    assert out["logits"].dtype == jnp.float32
    wanted = _replay(out, tokens)
    rows = _rel_l2_rows(out["logits"], wanted["logits"])
    assert np.median(rows) < 0.025 and rows.max() < 0.05
    assert wanted["index_shortfall"].max() < 0.15
    assert wanted["route_shortfall"].max() < 0.01


def test_int8_storage_reads_outside_the_band(tokens):
    quantized = sl.init_params(TINY, quantized=True)
    assert quantized["groups"][1]["we_gate"].dtype == jnp.int8
    assert quantized["groups"][0]["w_g"].dtype == jnp.int8
    assert quantized["mtp"]["eh_proj"].dtype == jnp.int8
    assert quantized["groups"][1]["hc_phi"].dtype == jnp.bfloat16
    out = _forward(quantized, tokens)
    wanted = _replay(out, tokens)
    assert np.median(_rel_l2_rows(out["logits"], wanted["logits"])) > 0.025


def test_keys_chosen_otherwise_than_by_the_scores_read_a_shortfall(
        float32_out, tokens, wanted):
    """Where a full indexer's rows past ``index_topk`` take the first keys
    in place of the best-scored, the reference told them reads shortfalls
    of standard deviations, and the rows move."""
    k = TINY.index_topk
    bad = np.asarray(float32_out["chosen"]).copy()
    first = np.arange(S)[None, :] < k
    bad[:, 1, k:] = np.asarray(sa.pack(jnp.asarray(
        np.broadcast_to(first, (S - k, S)))))
    told = _replay(float32_out, tokens, chosen=bad)
    assert told["index_shortfall"].min() > 1.0
    assert _rel_l2_rows(float32_out["logits"], told["logits"]).min() > 1e-3


# -- the choice of keys -------------------------------------------------------

def test_bit_planes_round_trip():
    rng = np.random.default_rng(1)
    for n in (5, 64, 300, 8192):
        chosen = rng.random((2, 3, n)) < 0.3
        bits = sa.pack(jnp.asarray(chosen))
        assert bits.shape == (2, 3, sa.words(n)) and bits.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(sa.unpack(bits, n)), chosen)
    assert sa.words(8192) == 256 and sa.words(64) == 128


def test_choose_is_top_k_with_ties_to_the_lower_position():
    """The exact threshold, a bit of the key at a time: the same set as
    ``lax.top_k`` over the causal scores, ties included, and every key of
    a row that holds fewer than k."""
    rng = np.random.default_rng(2)
    n, k = 96, 20
    scores = rng.standard_normal((2, n, n)).astype(np.float32)
    scores[0, 50, :40] = 0.5          # a tie across the k-th place
    scores[1, 70, ::3] = -0.0
    scores[1, 70, 1::3] = 0.0
    rows = jnp.arange(n)
    chosen = np.asarray(sl.choose(jnp.asarray(scores), rows, k))
    causal = np.arange(n)[None, :] <= np.arange(n)[:, None]
    value, index = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    for b in range(2):
        for t in range(n):
            want = set(np.asarray(index[b, t])[np.isfinite(value[b, t])])
            assert set(np.flatnonzero(chosen[b, t])) == want, (b, t)
    assert chosen.sum(-1).tolist() == [[min(t + 1, k) for t in range(n)]] * 2


def test_sortable_keys_order_as_the_floats():
    x = np.array([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, np.inf], np.float32)
    keys = np.asarray(sl.sortable(jnp.asarray(x)))
    assert (np.diff(keys.astype(np.int64)) > 0).all()
    u = jnp.asarray(np.random.default_rng(3).integers(
        0, 2 ** 32, (4, 50), dtype=np.uint64).astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(sl._kth_largest(u, 7)),
        np.sort(np.asarray(u), axis=-1)[:, -7])


def _dense_with_sink(q, k, v, sink, scale):
    n = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None], s,
                  -jnp.inf)
    z = sink[None, :, None, None]
    m = jnp.maximum(s.max(-1, keepdims=True), z)
    p = jnp.exp(s - m)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      p / (jnp.exp(z - m) + p.sum(-1, keepdims=True)), v)


def _qkv(n, d=24, dv=40, seed=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (2, 3, n, d)),
            jax.random.normal(keys[1], (2, 3, n, d)),
            jax.random.normal(keys[2], (2, 3, n, dv)),
            jax.random.normal(keys[3], (3,)))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
def test_below_index_topk_the_sparse_attention_is_dense_causal_with_the_sink(
        interpret):
    """Queries at positions under ``index_topk`` choose every earlier key:
    the sparse attention is then dense causal latent attention, the sink in
    its denominator."""
    n, k = 160, 200
    q, kk, v, sink = _qkv(n)
    scores = jax.random.normal(jax.random.PRNGKey(5), (2, n, n))
    bits = sa.pack(sl.choose(scores, jnp.arange(n), k))
    got = sa.sparse_attention(q, kk, v, bits, sink, sm_scale=0.2,
                              interpret=interpret)
    want = _dense_with_sink(q, kk, v, sink, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("n,k,empty_rows", [
    (300, 40, False), (1100, 200, False), (700, 90, False), (4160, 1000, False),
    (1100, 200, True), (4000, 900, False)],
    ids=["300-40", "1100-200", "single-tails", "words-256", "empty-rows",
         "planes-32"])
def test_the_kernel_reads_the_chosen_keys_and_no_others(n, k, empty_rows):
    """The kernel in the pallas interpreter against the plain form, where
    the choice binds: a query block of 128 and of 512 rows, a row of keys
    longer than one plane, padding of both; blocks that finish with one,
    two and three single planes after their wide steps (``single-tails``),
    rows of 256 words (``words-256``), rows with no chosen key in a
    wide step or none at all (``empty-rows``), and all 32 planes, the last
    wide step reading bit 31, the word's sign (``planes-32``).  Sinks of
    both signs."""
    q, kk, v, _ = _qkv(n, seed=n)
    sink = jnp.array([-3.0, 0.5, 4.0])
    scores = jax.random.normal(jax.random.PRNGKey(n), (2, n, n))
    chosen = sl.choose(scores, jnp.arange(n), k)
    if empty_rows:
        rows, keys = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
        W = sa.words(n)
        wide_step = (keys // W >= 4) & (keys // W < 8)    # planes 4..7
        chosen &= ~((rows % 2 == 1) & wide_step) & (rows % 5 != 0)
    bits = sa.pack(chosen)
    got = sa.sparse_attention(q, kk, v, bits, sink, sm_scale=0.2,
                              interpret=True)
    want = sa.sparse_attention_reference(q, kk, v, bits, sink, sm_scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # a key left out moves the rows that had chosen it
    other = sa.pack(sl.choose(-scores, jnp.arange(n), k))
    moved = sa.sparse_attention_reference(q, kk, v, other, sink, sm_scale=0.2)
    assert float(jnp.abs(moved - want)[:, :, k:].min(-1).max()) > 1e-3


@pytest.mark.parametrize("S,D,dv,want", [
    (8192, 256, 256, (512, 4, 32, 272, 64, 16)),  # Hy4-preview's, W = 256
    (4160, 24, 40, (512, 4, 17, 89, 20, 9)),
    (1100, 24, 40, (512, 4, 9, 21, 5, 1)),
    (700, 24, 40, (128, 4, 6, 21, 3, 9)),
    (300, 24, 40, (128, 4, 3, 6, 0, 6)),            # fewer planes than 4
], ids=["served", "words-256", "block-512", "block-128", "few-planes"])
def test_the_kernels_plan(S, D, dv, want):
    """What the kernel walks at a shape, from the function the call itself
    sizes its blocks with: every plane up to a block's last row, the wide
    steps and the single planes after them adding up to it."""
    W = sa.words(S)
    plan = sa._dsa_plan(S, D, dv, W, jnp.bfloat16)
    assert plan[:6] == want
    walks = [min(sa._planes_to_row(i, plan.block, W), plan.n_planes)
             for i in range(-(-S // plan.block))]
    assert sum(walks) == plan.walked
    assert plan.wide * plan.wide_steps + plan.single_steps == plan.walked
    # the chip has 128 MiB of VMEM; the call scopes what the plan asks
    assert plan.vmem_bytes < 100 << 20


def test_a_shared_block_attends_over_its_full_blocks_choice(params):
    """A shared layer runs no indexer and holds none of its leaves; it
    returns, and attends over, the choice it was given."""
    p = _f32(params)
    full = jax.tree_util.tree_map(lambda a: a[0], p["groups"][1])
    shared = jax.tree_util.tree_map(lambda a: a[0], p["groups"][2])
    assert "w_qi" in full and "w_qi" not in shared
    cos, sin = parts.rotary(TINY.qk_rope_head_dim, TINY.rope_theta,
                            jnp.arange(S))
    X = jax.random.normal(jax.random.PRNGKey(6), (2, S, TINY.hc_mult,
                                                  TINY.hidden_size))
    kinds = dict(sl.groups(TINY))
    _, chosen, _ = sl.block(full, X, (sl.SPARSE, sl.FULL), TINY, jnp.float32,
                            cos, sin, None)
    X2, again, _ = sl.block(shared, X, (sl.SPARSE, sl.SHARED), TINY,
                            jnp.float32, cos, sin, chosen)
    assert [2, 3] in kinds.values()
    for a, b in zip(again, chosen):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # given another choice the same block answers otherwise
    other = (sa.pack(jnp.broadcast_to(jnp.eye(S, dtype=bool), (2, S, S))),
             ) + chosen[1:]
    X3, _, _ = sl.block(shared, X, (sl.SPARSE, sl.SHARED), TINY, jnp.float32,
                        cos, sin, other)
    assert float(jnp.abs(X3 - X2).max()) > 1e-3


# -- the four streams ---------------------------------------------------------

def test_sinkhorn_leaves_rows_and_columns_summing_to_one(params):
    blk = jax.tree_util.tree_map(lambda a: a[0], _f32(params)["groups"][1])
    X = jax.random.normal(jax.random.PRNGKey(7), (2, S, TINY.hc_mult,
                                                  TINY.hidden_size))
    # each normalisation divides by the sum + hc_eps: a sum reads 1 - hc_eps
    # at worst, and float32 rounds a sum of four by a few units of 2^-24
    within = TINY.hc_eps + 8 * 2.0 ** -24
    for which in (0, 1):
        pre, post, res = sl.mixing(blk, X, which, TINY)
        assert res.shape == (2, S, 4, 4) and float(res.min()) > 0
        for axis in (-1, -2):
            assert float(jnp.abs(res.sum(axis) - 1).max()) <= within
        assert 0 < float(pre.min()) and float(pre.max()) < 1
        assert 0 < float(post.min()) and float(post.max()) < TINY.hc_magnitude
        # the coefficients follow the token
        assert float(jnp.abs(res[0, 0] - res[0, 1]).max()) > 1e-4


def _streams_and_block(params, dt, seed=9):
    """Streams of three prompts of 512 (1,536 tokens: three tiles of the
    kernels at the tiny width) and layer 1's leaves, upcast for f32."""
    p = _f32(params) if dt == jnp.float32 else params
    blk = jax.tree_util.tree_map(lambda a: a[0], p["groups"][1])
    X = 3 * jax.random.normal(jax.random.PRNGKey(seed), (
        3, 512, TINY.hc_mult, TINY.hidden_size))
    return blk, X


@pytest.mark.parametrize("which", [0, 1], ids=["attention", "ffn"])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_hc_pre_kernel_is_the_plain_form(params, dt, which):
    """The ``hc.pre`` kernel in the pallas interpreter over several tiles:
    the normed input and, through ``post_and_res``, the coefficients of
    ``mixing``.  In f32 the sums run in another order (a few units of
    2^-24); in bfloat16 ``x`` rounds to Phi's dtype where the plain form
    rounds it, and ``h`` (whose rows have an rms near one) may land a unit
    of bf16 away on a few values in ten thousand, or 2^-12 where ``u``
    cancels to near zero."""
    blk, X4 = _streams_and_block(params, dt)
    n, D = TINY.hc_mult, TINY.hidden_size
    ln = ("ln_attn", "ln_ffn")[which]
    X = X4.reshape(-1, n * D)
    assert sm._rows(X.shape[0], 1) < X.shape[0]     # more than one tile
    h, a = sm.hc_pre(X, blk["hc_phi"][which], blk["hc_alpha"][which],
                     blk["hc_bias"][which], blk[ln], n=n, hc_eps=TINY.hc_eps,
                     eps=TINY.rms_norm_eps, dt=dt, interpret=True)
    pre, post, res = sl.mixing(blk, X4, which, TINY)
    u = sum(pre[..., i, None] * X4[:, :, i] for i in range(n))
    want = np.asarray(parts.rmsnorm(u, blk[ln], TINY.rms_norm_eps).astype(
        dt).reshape(-1, D), np.float64)
    got = np.asarray(h, np.float64)
    assert h.dtype == dt and a.shape == (X.shape[0], n * (n + 2))
    kpost, kres = sl.post_and_res(a, blk, which, TINY)
    if dt == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        close = dict(rtol=1e-6, atol=1e-6)
    else:
        off = np.abs(got - want)
        assert (off <= 2.0 ** -7 * np.abs(want) + 2.0 ** -12).all()
        assert (off > 0).mean() < 1e-3
        close = dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(kpost),
                               np.asarray(post.reshape(-1, n)), **close)
    np.testing.assert_allclose(np.asarray(kres),
                               np.asarray(res.reshape(-1, n, n)), **close)


@pytest.mark.parametrize("which", [0, 1], ids=["attention", "ffn"])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_hc_post_kernel_is_the_plain_form(params, dt, which):
    """The ``hc.post`` kernel in the pallas interpreter over several tiles:
    ``X_i <- sum_j H_res_ij X_j + H_post_i y`` with ``mixing``'s
    coefficients, in f32 whatever the sublayer's dtype, the streams summed
    in the plain form's order."""
    blk, X4 = _streams_and_block(params, dt, seed=10 + which)
    n, D = TINY.hc_mult, TINY.hidden_size
    _, post, res = sl.mixing(blk, X4, which, TINY)
    y = jax.random.normal(jax.random.PRNGKey(12), X4.shape[:2] + (D,))
    want = jnp.stack([sum(res[..., i, j, None] * X4[:, :, j] for j in range(n))
                      + post[..., i, None] * y for i in range(n)], axis=2)
    got = sm.hc_post(X4.reshape(-1, n * D), y.reshape(-1, D),
                     post.reshape(-1, n), res.reshape(-1, n, n),
                     interpret=True)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.reshape(got.shape)),
                               rtol=1e-6, atol=2e-6)


def test_the_tiles_follow_the_shapes():
    """Hy4-preview's ``[2,8192]`` step: 64 tokens a tile of ``hc.pre``
    (Phi held once beside them), 32 of ``hc.post``; the tiny width fills a
    tile of 512, a token count of 16 times an odd number takes tiles of 16,
    and the kernels refuse what they cannot tile."""
    cfg = sl.HY4_PREVIEW_EP32_SHARE
    width, d = cfg.hc_mult * cfg.hidden_size, cfg.hidden_size
    pre = 2 * (4 * width + 2 * d + 4 * 128) + 4 * d
    assert sm._rows(16384, pre, width * 128 * 2 + 64 * d) == 64
    assert sm._rows(16384, 8 * (2 * width + d + 128)) == 32
    assert sm._rows(1536, 1) == 512 and sm._rows(48, 1) == 16
    X = jax.random.normal(jax.random.PRNGKey(13), (48, 4 * 128))
    got = sm.hc_post(X, jnp.ones((48, 128)), jnp.ones((48, 4)),
                     jnp.broadcast_to(jnp.eye(4), (48, 4, 4)), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(X + 1), rtol=1e-6)
    with pytest.raises(ValueError, match="lane-aligned"):
        sm.hc_post(X[:, :200], X[:, :50], jnp.ones((48, 4)),
                   jnp.ones((48, 4, 4)), interpret=True)
    with pytest.raises(ValueError, match="multiple of 16"):
        sm.hc_post(X[:24], X[:24, :128], jnp.ones((24, 4)),
                   jnp.ones((24, 4, 4)), interpret=True)


def test_a_whole_forward_through_the_interpreted_kernels_is_the_plain_one(
        monkeypatch, params, float32_out, tokens):
    """Every sublayer of the tiny f32 program through the two kernels in the
    pallas interpreter: the same keys, experts and tokens, logits within
    float32's rounding of the plain forward's."""
    monkeypatch.setattr(sl, "_sublayer", functools.partial(
        sl._sublayer, interpret=True))
    out = _forward(_f32(params), tokens)
    assert _rel_l2_rows(out["logits"], float32_out["logits"]).max() < 1e-5
    for name in ("tokens", "chosen", "routes"):
        np.testing.assert_array_equal(np.asarray(out[name]),
                                      np.asarray(float32_out[name]))


# -- the expert layer ---------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The held experts' parts over the four shares of 4 of 16, with the
    shared expert counted once, are the uncut reference's layer."""
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 48, TINY.hidden_size))
    total = jnp.zeros((1, 48, TINY.hidden_size))
    for first_expert in (0, 4, 8, 12):
        cfg = dataclasses.replace(TINY, first_expert=first_expert)
        blk = _f32(sl._block_params(cfg, 1))
        part, rows = sl._ffn(dict(blk, ws_down=jnp.zeros_like(blk["ws_down"])),
                             h, sl.SPARSE, cfg)
        total = total + part
    shared, _ = sl._ffn(dict(blk, we_down=jnp.zeros_like(blk["we_down"])),
                        h, sl.SPARSE, cfg)
    w = REF.block_weights(TINY_HY4, 1)
    with jax.default_matmul_precision("highest"):
        idx, weights = REF.route(h[0], w, TINY_HY4)
        limit = TINY_HY4["swiglu_limit"]
        uncut = REF._swiglu(h[0], w["ws_gate"], w["ws_up"], w["ws_down"],
                            limit)
        for e in range(16):
            we = REF.expert_weights(TINY_HY4, 1, e)
            gate = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
            uncut = uncut + gate[:, None] * REF._swiglu(
                h[0], we["gate"], we["up"], we["down"], limit)
    np.testing.assert_allclose(np.asarray((total + shared)[0]),
                               np.asarray(uncut), rtol=1e-4, atol=1e-5)


def test_the_swiglu_clamp():
    g = jnp.array([-20.0, -1.0, 0.5, 9.0, 30.0])
    u = jnp.array([-30.0, 2.0, -0.5, 11.0, 4.0])
    np.testing.assert_allclose(
        np.asarray(parts.gated(g, u, 10)),
        np.asarray(jax.nn.silu(jnp.minimum(g, 10)) * jnp.clip(u, -10, 10)))
    np.testing.assert_array_equal(np.asarray(parts.gated(g, u, None)),
                                  np.asarray(jax.nn.silu(g) * u))


def test_the_latent_moe_block_is_bit_for_bit_what_it_was():
    """``kimi_k2``'s tiny forward, served and int8, as it read before the
    clamp and the new leaves existed: both are off by default."""
    from chipbench.tests.tiny_kimi import TINY_KIMI
    from chipbench.tests.tiny_kimi import program_config as kimi_config

    cfg = kimi_config(TINY_KIMI)
    assert inspect.signature(lm.held_experts).parameters[
        "limit"].default is None
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (3, cfg.seq_len)).astype(np.int32)
    fwd = jax.jit(lambda p, t: lm.forward(p, t, cfg))
    digest = []
    for quantized in (False, True):
        logits, _ = fwd(lm.init_params(cfg, quantized=quantized),
                        jnp.asarray(ids))
        digest.append(hashlib.sha256(
            np.asarray(logits).tobytes()).hexdigest()[:16])
    assert digest == ["0466df9f698395b9", "0df0a9b956139f74"]
    assert sorted(lm._layer_params(cfg, 1)) == sorted(
        ["ln_attn", "ln_q", "ln_kv", "ln_ffn", "w_qa", "w_qb_nope",
         "w_qb_rope", "w_kva", "w_kb", "w_vb", "w_o", "router",
         "router_bias", "we_gate", "we_up", "we_down", "ws_gate", "ws_up",
         "ws_down"])


# -- weights, counts of work, the answer --------------------------------------

def test_weights_are_bfloat16_and_follow_the_block_and_the_expert():
    blk = sl._block_params(TINY, 1)
    assert {a.dtype for k, a in blk.items() if k not in (
        "router_bias", "hc_alpha", "hc_bias")} == {jnp.dtype(jnp.bfloat16)}
    want = REF.block_weights(TINY_HY4, 1)
    for name in ("w_qa", "w_g", "sink", "w_qi", "hc_phi", "hc_alpha"):
        np.testing.assert_array_equal(np.asarray(blk[name], np.float32),
                                      np.asarray(want[name]))
    np.testing.assert_array_equal(
        np.asarray(blk["we_down"][2], np.float32),
        np.asarray(REF.expert_weights(TINY_HY4, 1, 6)["down"]))
    mtp = sl._block_params(TINY, TINY.num_hidden_layers)
    np.testing.assert_array_equal(
        np.asarray(mtp["eh_proj"], np.float32),
        np.asarray(REF.block_weights(TINY_HY4, 4)["eh_proj"]))
    assert "w_qi" not in sl._block_params(TINY, 2)


def test_the_published_cut_is_the_configuration_file_key_by_key():
    cfg = load_json(sl.__file__.rsplit("/triton_client_tpu/", 1)[0],
                    "chipbench", "configs", "hy4_preview.json")
    assert program_config(cfg) == sl.HY4_PREVIEW_EP32_SHARE
    assert [g for g, _ in sl.groups(sl.HY4_PREVIEW_EP32_SHARE)] == [
        (sl.DENSE, sl.FULL), (sl.SPARSE, sl.FULL), (sl.SPARSE, sl.SHARED)]


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_flops_are_the_yardsticks(which):
    root = sl.__file__.rsplit("/triton_client_tpu/", 1)[0]
    cfg = TINY_HY4 if which == "tiny" else load_json(
        root, "chipbench", "configs", "hy4_preview.json")
    yardstick = load_module("flop_counts", "hy4_preview")
    assert sl.flops_per_inference(program_config(cfg)) == pytest.approx(
        yardstick.flops_per_inference(cfg), rel=1e-12)
    assert sl.selected_pairs(program_config(cfg)) == \
        yardstick.chosen_pairs(cfg)
    if which == "published":
        assert yardstick.chosen_pairs(cfg) == 14_681_088
        assert yardstick.flops_per_inference(cfg) == pytest.approx(
            44.09e12, rel=1e-3)
        model = language.make_hy4_preview()
        assert float(model.config.parameters[
            "flops_per_inference"].string_value) == pytest.approx(
                yardstick.flops_per_inference(cfg))


def test_the_counters_and_the_reported_choices(params, tokens):
    out = _forward(params, tokens)
    counters = {k: np.asarray(v) for k, v in out["counters"].items()}
    blocks = TINY.n_blocks
    assert counters["dsa_queries"].tolist() == [S * blocks] * 3
    assert counters["dsa_pairs"].tolist() == [
        sl.selected_pairs(TINY) * blocks] * 3
    assert counters["index_reused"].tolist() == [S * 2] * 3
    assert counters["expert_rows"].shape == (3, 4, TINY.n_routed_experts)
    # every full block's bit planes: min(t + 1, k) keys a query, none past it
    chosen = np.asarray(sa.unpack(out["chosen"], S))
    assert out["chosen"].shape == (3, TINY.full_blocks, S, sa.words(S))
    assert (chosen & ~np.tri(S, dtype=bool)).sum() == 0
    np.testing.assert_array_equal(
        chosen.sum(-1), np.broadcast_to(
            np.minimum(np.arange(S) + 1, TINY.index_topk), chosen.shape[:-1]))
    # dsa_pairs counts the planes: layer 0's once, layer 1's in it and the
    # two shared layers, the MTP module's once
    assert counters["dsa_pairs"].tolist() == (
        chosen.sum((2, 3)) @ np.array([1, 3, 1])).tolist()
    routes = np.sort(np.asarray(out["routes"]), axis=-1)
    assert routes.shape == (3, TINY.expert_blocks, S, TINY.num_experts_per_tok)
    assert routes.min() >= 0 and routes.max() < TINY.routed_experts_total
    assert (np.diff(routes, axis=-1) > 0).all()


def test_dsa_counters_leave_out_the_rows_the_batcher_padded():
    stats = ModelStats()
    stats.queue_device_counters({"dsa_queries": np.array([60, 60]),
                                 "dsa_pairs": np.array([900, 900]),
                                 "index_reused": np.array([20, 20])}, 1, 10)
    entries = stats.extension_entries()
    assert entries["dsa_queries"] == {"count": 60, "ns": 0}
    assert entries["dsa_pairs"] == {"count": 900, "ns": 0}
    assert entries["index_reused"] == {"count": 20, "ns": 0}


# -- the served path ---------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    registry.register_model(language.make_hy4_preview(TINY))
    with ServerHarness(registry) as h:
        yield h


def test_factory_through_the_server_and_the_grpc_client(server, params,
                                                        tokens):
    want = _forward(params, tokens[:2])
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        md = client.get_model_metadata("hy4_preview", as_json=True)
        assert [(t["name"], t["datatype"], t["shape"]) for t in md[
            "outputs"]] == [
            ("TOKENS", "INT32", ["-1", "2"]),
            ("LOGITS", "FP32", ["-1", "2", str(TINY.vocab_size)]),
            ("CHOSEN", "INT32", ["-1", "3", str(S), "128"]),
            ("ROUTES", "INT32", ["-1", "4", str(S), "4"])]
        inp = grpcclient.InferInput("INPUT_IDS", [2, S], "INT32")
        inp.set_data_from_numpy(tokens[:2])
        two = client.infer("hy4_preview", [inp])
    np.testing.assert_array_equal(two.as_numpy("TOKENS"),
                                  np.asarray(want["tokens"]))
    for name in ("CHOSEN", "ROUTES"):
        np.testing.assert_array_equal(two.as_numpy(name),
                                      np.asarray(want[name.lower()]))
    assert two.as_numpy("DEVICE_COUNTER.dsa_pairs") is None
    (row,) = server.core.statistics("hy4_preview")
    stats = row["inference_stats"]
    assert stats["dsa_queries"]["count"] == 2 * S * TINY.n_blocks
    assert stats["dsa_pairs"]["count"] == \
        2 * sl.selected_pairs(TINY) * TINY.n_blocks
    assert stats["index_reused"]["count"] == 2 * S * 2
    assert stats["expert_tokens"]["count"] == 2 * S * 4


def test_the_zoo_registers_it_without_allocating():
    from triton_client_tpu.models import zoo

    registry = ModelRegistry()
    zoo.register_all(registry)
    model = registry.get("hy4_preview")
    assert model.config.input[0].dims == [8192]
    assert [list(o.dims) for o in model.config.output] == [
        [2], [2, 15104], [3, 8192, 256], [5, 8192, 8]]
    assert list(
        model.config.dynamic_batching.preferred_batch_size) == [1, 2]
