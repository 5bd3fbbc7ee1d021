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
    "shape,causal,dtype,tol",
    [
        *[pytest.param(shape, causal, *_F32,
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
        pytest.param((1, 16, 384, 64), False, *_BF16,
                     id="bert_large-1x16x384x64-bf16-bidir"),
        pytest.param((2, 16, 384, 64), False, *_BF16,
                     id="bert_large-2x16x384x64-bf16-bidir"),
        pytest.param((1, 16, 128, 128), True, *_BF16,
                     id="llama-1x16x128x128-bf16-causal"),
        pytest.param((1, 4, 200, 64), False, *_BF16,
                     id="odd-1x4x200x64-bf16-bidir"),
        pytest.param((1, 4, 200, 64), True, *_BF16,
                     id="odd-1x4x200x64-bf16-causal"),
        # head blocks: 8 programs of 2 heads (a block holds _ROW_BLOCK_BYTES)
        pytest.param((1, 16, 384, 128), False, *_F32,
                     id="split-heads-1x16x384x128-f32-bidir"),
        # past _ROW_MAX_S: the looped form, S no multiple of its blocks
        pytest.param((1, 2, 600, 64), True, *_F32,
                     id="looped-1x2x600x64-f32-causal"),
        pytest.param((1, 2, 1100, 32), True, *_BF16,
                     id="looped-1x2x1100x32-bf16-causal"),
    ],
)
def test_kernel_matches_reference(shape, causal, dtype, tol):
    q = _rand(shape, dtype, 1)
    k = _rand(shape, dtype, 2)
    v = _rand(shape, dtype, 3)
    want = flash_attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert got.dtype == dtype and got.shape == shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


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


def test_custom_scale():
    shape = (1, 1, 64, 32)
    q = _rand(shape, jnp.float32, 7)
    k = _rand(shape, jnp.float32, 8)
    v = _rand(shape, jnp.float32, 9)
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


def test_gradients_match_reference():
    """custom_vjp: grads through the kernel equal grads through the
    reference (the training path at sp=1)."""
    shape = (1, 2, 32, 16)
    q = _rand(shape, jnp.float32, 20)
    k = _rand(shape, jnp.float32, 21)
    v = _rand(shape, jnp.float32, 22)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_reference(q, k, v, causal=True) ** 2)

    g_kernel = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gk, gr in zip(g_kernel, g_ref):
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
