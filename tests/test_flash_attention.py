"""Pallas flash-attention kernel vs the jnp reference (interpret mode on
the CPU mesh; the real-TPU path is exercised by bench/serving)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from triton_client_tpu import parallel  # noqa: E402
from triton_client_tpu.ops import (  # noqa: E402
    flash_attention,
    flash_attention_reference,
)


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


_F32 = (jnp.float32, 2e-5)  # dtype, tolerance
# bf16 operands into both dots (P rounded once, before P·V), f32 statistics:
# the output's own rounding is 2^-9, the served rows' tolerance
_BF16 = (jnp.bfloat16, 2e-2)


@pytest.mark.parametrize(
    "shape,causal,dtype,tol,dv",
    [
        *[pytest.param(shape, causal, *_F32, None,
                       id=f"{'x'.join(map(str, shape))}-f32-"
                          f"{'causal' if causal else 'bidir'}")
          for causal in (True, False)
          for shape in [
              (1, 2, 128, 64),   # block-aligned
              (2, 4, 384, 64),   # BERT-large's row, whole in one program
              (1, 2, 100, 32),   # padding path: S not a block multiple
              (1, 1, 8, 16),     # tiny: S smaller than any block
          ]],
        # the served short-row family, as the zoo's dtype sends it
        pytest.param((1, 16, 384, 64), False, *_BF16, None,
                     id="bert_large-1x16x384x64-bf16-bidir"),
        pytest.param((2, 16, 384, 64), False, *_BF16, None,
                     id="bert_large-2x16x384x64-bf16-bidir"),
        pytest.param((1, 16, 128, 128), True, *_BF16, None,
                     id="llama-1x16x128x128-bf16-causal"),
        pytest.param((1, 4, 200, 64), False, *_BF16, None,
                     id="odd-1x4x200x64-bf16-bidir"),
        pytest.param((1, 4, 200, 64), True, *_BF16, None,
                     id="odd-1x4x200x64-bf16-causal"),
        # head blocks: 8 programs of 2 heads (a block holds _ROW_BLOCK_BYTES)
        pytest.param((1, 16, 384, 128), False, *_F32, None,
                     id="split-heads-1x16x384x128-f32-bidir"),
        # past _ROW_MAX_S: the looped form, S no multiple of its blocks
        pytest.param((1, 2, 600, 64), True, *_F32, None,
                     id="looped-1x2x600x64-f32-causal"),
        pytest.param((1, 2, 1100, 32), True, *_BF16, None,
                     id="looped-1x2x1100x32-bf16-causal"),
        # values of another width than the keys (latent attention: q·k over
        # 192, v 128 wide): the looped form at any length, output as v
        pytest.param((1, 2, 1100, 24), True, *_F32, 16,
                     id="dv-looped-1x2x1100x24v16-f32-causal"),
        pytest.param((1, 2, 1100, 24), True, *_BF16, 16,
                     id="dv-looped-1x2x1100x24v16-bf16-causal"),
        pytest.param((2, 2, 200, 24), True, *_F32, 16,
                     id="dv-short-2x2x200x24v16-f32-causal"),
        pytest.param((1, 2, 384, 48), False, *_F32, 64,
                     id="dv-wider-1x2x384x48v64-f32-bidir"),
        pytest.param((1, 1, 1024, 192), True, *_BF16, 128,
                     id="dv-mla-1x1x1024x192v128-bf16-causal"),
        # each path of the looped form's walk (``_loop_plan``): blocks of
        # 128 below S = 1024 and of 512 from there, a wide step of four
        # one row of one block: the diagonal alone, whole and padded
        pytest.param((1, 2, 128, 24), True, *_F32, 16,
                     id="walk-diagonal-alone-1x2x128x24v16-f32-causal"),
        pytest.param((1, 2, 100, 24), True, *_BF16, 16,
                     id="walk-diagonal-padded-1x2x100x24v16-bf16-causal"),
        pytest.param((1, 2, 100, 24), False, *_F32, 16,
                     id="walk-one-padded-block-1x2x100x24v16-f32-bidir"),
        # single whole blocks, then the diagonal (no program reaches four)
        pytest.param((1, 2, 512, 48), True, *_F32, 32,
                     id="walk-narrow-1x2x512x48v32-f32-causal"),
        # a wide step, single blocks and the diagonal, 3 : 2 as 192 : 128
        pytest.param((1, 2, 1000, 48), True, *_F32, 32,
                     id="walk-wide-1x2x1000x48v32-f32-causal"),
        pytest.param((1, 2, 1000, 48), True, *_BF16, 32,
                     id="walk-wide-1x2x1000x48v32-bf16-causal"),
        # no causal mask: the padding crosses the last block alone
        pytest.param((1, 2, 600, 64), False, *_F32, None,
                     id="walk-padded-1x2x600x64-f32-bidir"),
        pytest.param((1, 2, 600, 64), False, *_BF16, None,
                     id="walk-padded-1x2x600x64-bf16-bidir"),
        pytest.param((1, 2, 1000, 48), False, *_BF16, 32,
                     id="walk-wide-padded-1x2x1000x48v32-bf16-bidir"),
        # no mask at all: neither causal nor padded
        pytest.param((1, 2, 640, 64), False, *_F32, None,
                     id="walk-unmasked-1x2x640x64-f32-bidir"),
        pytest.param((1, 1, 2048, 32), False, *_BF16, None,
                     id="walk-unmasked-wide-1x1x2048x32-bf16-bidir"),
        # blocks of 512: wide steps of 2048, single blocks, the diagonal
        pytest.param((1, 1, 2600, 48), True, *_BF16, 32,
                     id="walk-512-1x1x2600x48v32-bf16-causal"),
        pytest.param((1, 1, 2600, 24), False, *_F32, 16,
                     id="walk-512-1x1x2600x24v16-f32-bidir"),
    ],
)
def test_kernel_matches_reference(shape, causal, dtype, tol, dv):
    q = _rand(shape, dtype, 1)
    k = _rand(shape, dtype, 2)
    v = _rand(shape, dtype, 3)
    if dv is not None:
        shape = shape[:3] + (dv,)
        v = _rand(shape, dtype, 3)
    want = flash_attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert got.dtype == dtype and got.shape == shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
@pytest.mark.parametrize("S,D,dv", [
    (100, 24, 16),     # one padded block
    (600, 64, 64),     # blocks of 128, the last one padded
    (1000, 48, 32),    # a wide step before it
    (1100, 48, 32),    # blocks of 512
], ids=["100", "600", "1000", "1100"])
def test_a_padded_key_carries_no_weight(S, D, dv, causal, dtype):
    """The looped form pads S to its block with zero keys and zero values:
    with every real value 1, any weight on a padded key would pull a row's
    output below 1, and a row without a finite maximum would be NaN."""
    q = _rand((1, 2, S, D), dtype, 30)
    k = _rand((1, 2, S, D), dtype, 31)
    v = jnp.ones((1, 2, S, dv), dtype)
    got = np.asarray(flash_attention(q, k, v, causal=causal, interpret=True),
                     np.float32)
    assert np.isfinite(got).all()
    # f32: the row sums' rounding; bf16: P is rounded before P.V, not in l
    np.testing.assert_allclose(got, 1.0, rtol=0, atol=(
        1e-5 if dtype == jnp.float32 else 2 ** -8))


@pytest.mark.parametrize("S,D,dv,causal,want", [
    # (block, wide, padded S, walked, masked, dead columns).  Latent
    # attention's prefill: 16 of 136 blocks masked, under an eighth, and
    # no column of a block computed for nothing
    (8192, 192, 128, True, (512, 2048, 8192, 136, 16, 0)),
    (4096, 64, 64, True, (512, 2048, 4096, 36, 8, 0)),      # longctx_tpu's
    (4096, 64, 64, False, (512, 2048, 4096, 64, 0, 0)),     # no mask at all
    (5000, 64, 64, False, (512, 2048, 5120, 100, 10, 1200)),
    (5000, 64, 64, True, (512, 2048, 5120, 55, 10, 120)),
    (600, 64, 64, True, (128, 512, 640, 15, 5, 40)),
    (384, 24, 16, False, (128, 384, 384, 9, 0, 0)),
    (100, 24, 16, True, (128, 128, 128, 1, 1, 28)),
], ids=["mla", "longctx", "longctx-bidir", "odd-bidir", "odd-causal",
        "short", "short-dv", "one-block"])
def test_the_looped_forms_plan(S, D, dv, causal, want):
    """What the looped form walks at a shape, from the function the call
    itself sizes its blocks with."""
    import importlib

    fa = importlib.import_module("triton_client_tpu.ops.flash_attention")
    plan = fa._loop_plan(S, D, dv, jnp.bfloat16, causal)
    assert plan[:6] == want
    assert plan.wide % plan.block == 0 and plan.seq_pad % plan.block == 0
    # the chip has 128 MiB of VMEM; the call scopes what the plan asks
    assert plan.vmem_bytes < 100 << 20
    # a program's walk: whole blocks from key 0, then the masked one
    n = plan.seq_pad // plan.block
    padded = plan.seq_pad > S
    whole = [fa._whole_blocks(i, n, causal, padded) for i in range(n)]
    assert whole == (list(range(n)) if causal else [n - int(padded)] * n)


def test_bf16_inputs_accumulate_in_fp32():
    shape = (1, 2, 128, 64)
    q = _rand(shape, jnp.bfloat16, 4)
    k = _rand(shape, jnp.bfloat16, 5)
    v = _rand(shape, jnp.bfloat16, 6)
    want = flash_attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dv", [32, 16], ids=["dv=d", "dv-16"])
def test_custom_scale(dv):
    shape = (1, 1, 64, 32)
    q = _rand(shape, jnp.float32, 7)
    k = _rand(shape, jnp.float32, 8)
    v = _rand(shape[:3] + (dv,), jnp.float32, 9)
    want = flash_attention_reference(q, k, v, causal=True, sm_scale=0.5)
    got = flash_attention(q, k, v, causal=True, sm_scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_cpu_fallback_is_reference():
    # without interpret/force on a non-TPU backend the public entry point
    # must return the reference result (no pallas involved)
    shape = (1, 1, 16, 8)
    q = _rand(shape, jnp.float32, 10)
    k = _rand(shape, jnp.float32, 11)
    v = _rand(shape, jnp.float32, 12)
    want = flash_attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dv", [16, 8, 24], ids=["dv=d", "dv-8", "dv-24"])
def test_gradients_match_reference(dv):
    """custom_vjp: grads through the kernel equal grads through the
    reference (the training path at sp=1); with values of another width
    the output and every cotangent follow v."""
    shape = (1, 2, 32, 16)
    q = _rand(shape, jnp.float32, 20)
    k = _rand(shape, jnp.float32, 21)
    v = _rand(shape[:3] + (dv,), jnp.float32, 22)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_reference(q, k, v, causal=True) ** 2)

    g_kernel = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gk, gr, x in zip(g_kernel, g_ref, (q, k, v)):
        assert gk.shape == x.shape
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)


def _single_shard(fn, *args):
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("sp",))
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
        check_vma=False)(*args)


def test_matches_ring_attention_single_shard():
    """The kernel must agree with the flagship's ring attention at sp=1 —
    the exact substitution _attn_apply makes on the single-chip path."""
    from triton_client_tpu.models import transformer as tr

    cfg = tr.TransformerConfig(
        n_layers=1, d_model=32, n_heads=2, head_dim=16, d_ff=64,
        vocab_size=64)
    B, H, S, D = 1, 2, 16, 16
    q = _rand((B, H, S, D), jnp.float32, 13)
    k = _rand((B, H, S, D), jnp.float32, 14)
    v = _rand((B, H, S, D), jnp.float32, 15)

    ring = _single_shard(
        lambda q, k, v: tr._ring_attention(q, k, v, cfg), q, k, v)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ring),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_scores_softmax_kernel_arm_matches_ring(monkeypatch, causal):
    """``_scores_softmax`` at sp=1: past ``_SCORES_ON_CHIP_BYTES`` it hands
    the served bf16 row to the kernel, and that arm agrees with the ring arm
    it replaces, at bf16's tolerance."""
    from triton_client_tpu import ops
    from triton_client_tpu.models import transformer as tr

    cfg = tr.TransformerConfig(n_heads=4, head_dim=64, causal=causal)
    shape = (1, 4, 384, 64)
    q, k, v = (_rand(shape, jnp.bfloat16, seed) for seed in (16, 17, 18))
    taken = []

    def kernel(q, k, v, *, causal):
        taken.append(causal)
        return flash_attention(q, k, v, causal=causal, interpret=True)

    monkeypatch.setattr(ops, "flash_attention", kernel)
    run = lambda: _single_shard(  # noqa: E731
        lambda q, k, v: tr._scores_softmax(q, k, v, cfg), q, k, v)

    ring = run()  # 2.25 MiB of scores: they stay on the chip, the ring's
    assert taken == []
    monkeypatch.setattr(tr, "_SCORES_ON_CHIP_BYTES", 1 << 20)
    got = run()
    assert taken == [causal]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ring, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("shape,dv,kernel", [
    ((1, 16, 384, 64), 64, "_row_kernel"),     # bert_large's served row
    ((32, 16, 384, 64), 64, "_row_kernel"),
    ((1, 16, 4096, 64), 64, "_loop_kernel"),   # longctx_tpu's
    ((1, 4, 384, 24), 16, "_loop_kernel"),     # short, but v is narrower
    ((2, 64, 8192, 192), 128, "_loop_kernel"),  # latent attention's prefill
], ids=["bert_large-1", "bert_large-32", "longctx", "short-dv", "mla"])
def test_the_form_follows_the_shape(monkeypatch, shape, dv, kernel):
    """``_flash_call`` hands a short row with one width to the whole-row
    form and everything else to the looped one (shapes only; nothing runs)."""
    import importlib

    # the package exports the function under the module's name
    fa = importlib.import_module("triton_client_tpu.ops.flash_attention")
    taken = []
    monkeypatch.setattr(fa, "_row_call",
                        lambda *args: taken.append("_row_kernel"))
    monkeypatch.setattr(fa, "_loop_call",
                        lambda *args: taken.append("_loop_kernel"))
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (dv,), jnp.bfloat16)
    fa._flash_call.__wrapped__(q, q, v, False, None, False)
    assert taken == [kernel]


def _pallas_operands(jaxpr, found):
    """The operand shapes of every ``pallas_call`` under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([v.aval.shape for v in eqn.invars])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_operands(sub, found)
    return found


@pytest.mark.parametrize("shape,dv,operands", [
    # the whole-row form reads [B,H,D,S], the sequence on the lanes
    ((1, 16, 384, 64), 64, [(1, 16, 64, 384)] * 3),
    ((32, 16, 384, 64), 64, [(32, 16, 64, 384)] * 3),
    # the looped form reads q and v as [B*H,S,D], v at its own width, and k
    # with the sequence on the lanes, as the whole-row form does (PR 31:
    # the MXU takes a key tile 8% sooner than through its transposing path)
    ((1, 16, 4096, 64), 64, [(16, 4096, 64), (16, 64, 4096), (16, 4096, 64)]),
    ((1, 4, 384, 24), 16, [(4, 384, 24), (4, 24, 384), (4, 384, 16)]),
    ((2, 64, 8192, 192), 128,
     [(128, 8192, 192), (128, 192, 8192), (128, 8192, 128)]),
], ids=["bert_large-1", "bert_large-32", "longctx", "short-dv", "mla"])
def test_the_traced_call_is_of_the_form_its_shape_asks_for(shape, dv,
                                                           operands):
    """The real trace, nothing mocked: ``bert_large``'s served row still
    reaches one whole-row kernel, a long row or a v of another width the
    looped one."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (dv,), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=False, interpret=True))(q, q, v)
    assert _pallas_operands(traced.jaxpr, []) == [operands]


@pytest.mark.parametrize("S", [384, 1024])
@pytest.mark.parametrize("group", [1, 8], ids=["heads-equal", "8-a-kv-head"])
@pytest.mark.parametrize("mask_block", [1, 4, 32])
def test_block_causal_mask_and_grouped_heads(mask_block, group, S):
    """A causal mask over blocks of rows (row ``i`` sees key ``j`` where ``j
    // B <= i // B``) and keys of fewer heads than the queries, read through
    the index map: the looped kernel against the reference that repeats the
    heads.  Blocks of 1 over equal heads are the call as it was, bit for
    bit."""
    H = 8
    q = _rand((2, H, S, 32), jnp.float32, 1)
    k = _rand((2, H // group, S, 32), jnp.float32, 2)
    v = _rand((2, H // group, S, 32), jnp.float32, 3)
    got = flash_attention(q, k, v, causal=True, mask_block=mask_block,
                          interpret=True)
    want = flash_attention_reference(q, k, v, causal=True,
                                     mask_block=mask_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if mask_block == 1 and group == 1:
        plain = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    else:
        # the mask is not the plain causal one, and the heads are not
        # the first of each group alone
        rep = (jnp.repeat(x, group, axis=1) for x in (k, v))
        plain = flash_attention_reference(q, *rep, causal=True)
        assert mask_block == 1 or not np.allclose(got, plain, atol=1e-3)
    # what a row may see: the last row of a block and the first see the same
    # keys, so with equal queries they give equal outputs
    if mask_block > 1:
        same = q.at[:, :, mask_block - 1].set(q[:, :, 0])
        out = flash_attention(same, k, v, causal=True, mask_block=mask_block,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out[:, :, 0]),
                                   np.asarray(out[:, :, mask_block - 1]),
                                   rtol=1e-6, atol=1e-6)


def test_grouped_heads_are_read_through_the_index_map():
    """k and v reach the kernel with their own head count: nothing repeats
    them in HBM; a short row with grouped heads or a block mask takes the
    looped form."""
    q = jax.ShapeDtypeStruct((2, 32, 1024, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4, 1024, 128), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, mask_block=4, interpret=True))(q, kv, kv)
    assert _pallas_operands(traced.jaxpr, []) == [
        [(64, 1024, 128), (8, 128, 1024), (8, 1024, 128)]]
    short = jax.ShapeDtypeStruct((1, 16, 384, 64), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, mask_block=4, interpret=True))(
            short, short, short)
    assert _pallas_operands(traced.jaxpr, []) == [
        [(16, 384, 64), (16, 64, 384), (16, 384, 64)]]


@pytest.mark.parametrize("bad", [3, 1024])
def test_a_mask_block_the_kernel_cannot_take_is_refused(bad):
    q = _rand((1, 2, 256, 16), jnp.float32, 0)
    with pytest.raises(ValueError, match="mask_block"):
        flash_attention(q, q, q, causal=True, mask_block=bad, interpret=True)


def test_grouped_head_gradients_match_reference():
    q = _rand((1, 4, 64, 16), jnp.float32, 1)
    k = _rand((1, 2, 64, 16), jnp.float32, 2)
    v = _rand((1, 2, 64, 16), jnp.float32, 3)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, mask_block=4,
                                          **kw) ** 2)

    got = jax.grad(loss(flash_attention, interpret=True), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(flash_attention_reference), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-4)
