"""Programs of the served path compiled at their real size for a TPU v5e that
is described, not attached (the chip's compiler is installed here): what it
refuses, or how much memory it plans, costs no chip time.  Nothing runs, so
nothing here is a timing.

Keep every such test in this one file: the worker that is given it loads the
TPU's library, and only one process may (the topology is described inside a
fixture, never while a module is imported).
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_looped_generation_holds_one_cache_of_temporaries(
        one_chip, no_compile_cache, monkeypatch):
    """``looped.generate`` at Ouro-2.6B's size for 16 sequences: 5.34 GB of
    arguments, and temporaries of one cache (3.62 GB) and the q/k/v matrices
    re-laid once (1.2 GB).  Without the layout constraint on the cache's
    writes the compiler re-lays the whole cache between the prefill and the
    decode loop: 8.25 GB."""
    import functools

    import triton_client_tpu.ops as ops
    from triton_client_tpu.models import looped

    # the kernel itself, though the default backend here is the CPU
    monkeypatch.setattr(ops, "flash_attention", functools.partial(
        ops.flash_attention, force=True))
    cfg = looped.OURO_2_6B
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: looped.init_params(cfg)))
    tokens = on_chip(jax.ShapeDtypeStruct((16, cfg.seq_len), jnp.int32))
    compiled = jax.jit(lambda p, t: looped.generate(p, t, cfg)).lower(
        params, tokens).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(5.336e9, rel=1e-3)
    cache = 2 * 4 * 48 * 144 * 16 * 16 * 128 * 2
    assert cache == 3_623_878_656
    assert cache < memory.temp_size_in_bytes < cache + 1.5e9
    assert "tpu_custom_call" in compiled.as_text()  # the row kernel is there


def test_the_hybrid_generation_fits_beside_its_weights(
        one_chip, no_compile_cache, monkeypatch):
    """``hybrid_conv.generate`` at LFM2-8B-A1B's cut for 16 sequences of 512:
    9.33 GB of arguments and under a gigabyte of temporaries (both kinds of
    state are 55 MB; the prefill's 32,768 pairs through an expert layer are
    the rest).  The attention kernel takes heads of 64 over 8 key/value
    heads, and every grouped matmul is the megablox kernel, a decode step's
    64 pairs as one tile of their own: ``ragged_dot`` would read every
    layer's experts."""
    import functools

    import triton_client_tpu.ops as ops
    from triton_client_tpu.models import hybrid_conv, latent_moe

    monkeypatch.setattr(ops, "flash_attention", functools.partial(
        ops.flash_attention, force=True))
    # the branch the chip takes, though the default backend here is the CPU
    monkeypatch.setattr(latent_moe.jax, "default_backend", lambda: "tpu")
    cfg = hybrid_conv.LFM2_8B_A1B_STAGE
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: hybrid_conv.init_params(cfg)))
    tokens = on_chip(jax.ShapeDtypeStruct((16, cfg.seq_len), jnp.int32))
    compiled = jax.jit(lambda p, t: hybrid_conv.generate(p, t, cfg)).lower(
        params, tokens).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(9.334e9, rel=1e-3)
    assert 0.3e9 < memory.temp_size_in_bytes < 1.2e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged" not in text


def test_the_sparse_latent_step_fits_beside_its_weights(
        one_chip, no_compile_cache, monkeypatch):
    """``sparse_latent.forward`` at Hy4-preview's EP32 share for two prompts
    of 8,192: 7.87 GB of arguments and 5.14 GB of temporaries (the four f32
    streams are 1.61 GB, q, k and v of a block 1.61 GB; 5.93 GB while the
    streams' mixing was plain ``jnp``), 13.0 GB together, under the 14.5 GB
    that leaves room for the one-prompt program.  The sparse-attention
    kernel is four ops (layer 0, layer 1, the scan over the shared layers,
    the MTP module) and each of the streams' two mixing kernels eight (the
    same four places, two sublayers each), named so that the trace's reader
    finds them; every grouped matmul is the megablox kernel."""
    import re

    from triton_client_tpu.models import latent_moe, sparse_latent

    monkeypatch.setattr(latent_moe.jax, "default_backend", lambda: "tpu")
    cfg = sparse_latent.HY4_PREVIEW_EP32_SHARE
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: sparse_latent.init_params(cfg)))
    tokens = on_chip(jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32))
    compiled = jax.jit(lambda p, t: sparse_latent.forward(p, t, cfg)).lower(
        params, tokens).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(7.874e9, rel=1e-3)
    assert 4.8e9 < memory.temp_size_in_bytes < 5.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5e9
    text = compiled.as_text()
    ops = set(re.findall(r"%(_dsa_call[.\d]*) = bf16\[128,8192,256\]", text))
    assert len(ops) == 4
    pre = set(re.findall(r"%(_hc_pre_call[.\d]*) = \(bf16\[16384,6144\]",
                         text))
    post = set(re.findall(
        r"%(_hc_post_call[.\d]*) = f32\[16384,24576\]", text))
    assert len(pre) == len(post) == 8
    assert "ragged" not in text
