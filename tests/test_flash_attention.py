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


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "shape",
    [
        (1, 2, 128, 64),   # block-aligned
        (2, 4, 384, 64),   # BERT-large serving shape (multi-block)
        (1, 2, 100, 32),   # padding path: S not a block multiple
        (1, 1, 8, 16),     # tiny: S smaller than any block
    ],
)
def test_kernel_matches_reference(shape, causal):
    q = _rand(shape, jnp.float32, 1)
    k = _rand(shape, jnp.float32, 2)
    v = _rand(shape, jnp.float32, 3)
    want = flash_attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


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

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("sp",))
    from jax.sharding import PartitionSpec as P

    ring = jax.shard_map(
        lambda q, k, v: tr._ring_attention(q, k, v, cfg),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False,
    )(q, k, v)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ring),
                               rtol=2e-5, atol=2e-5)
