"""Fused dynamic-quantize + int8 matmul as a pallas TPU kernel.

The int8 MXU serving path (``models/transformer.py``) dynamically
quantizes activations per token, runs s8xs8->s32 einsums, and rescales.
Under XLA that is three HBM passes per matmul: an amax reduce over the
activation, a quantize pass that writes the int8 copy, and the GEMM that
reads it back.  This kernel folds all three into the GEMM's own pipeline:
each activation tile is loaded once (bf16), amax-reduced and quantized in
VMEM, fed to the MXU int8 datapath, and the s32->bf16 scale epilogue is
applied before the tile is written — the quantized activation never
touches HBM.  benchmarks/BERT_PROFILE.md §5 named this fusion as the
remaining layout-level lever on the int8 encoder; §6 records what it
measured.

Grid: 2-D over (row blocks, col blocks) with the full contraction K
resident per program — the serving shapes (K = d_model 1024 or d_ff
4096) fit VMEM comfortably, which buys exact per-row amax (identical
numerics to the XLA path: same scale, same round/clip) without a
cross-block reduction.  Two schedules, selected by which operand should
stay VMEM-resident across the inner sweep: the default iterates N
innermost (activation block resident, weights stream; degenerates to a
weight-resident 1-D grid when block_n == N), and ``m_inner`` iterates M
innermost (weight block resident, activations stream and re-quantize per
visit — measured a loss on the BERT shapes, kept for other geometries;
benchmarks/BERT_PROFILE.md §6).

Like the flash kernel (``ops/flash_attention.py``): on a TPU backend the
kernel is compiled by Mosaic or the call raises — a shape the full-K
design cannot take (:func:`fits`) is a ``ValueError``, never a silent
switch to the reference, so the caller selects by shape up front
(``models/transformer.py`` does).  Off TPU, where the kernel cannot
compile, callers get the plain-jnp reference; ``interpret=True`` runs the
kernel itself in the pallas interpreter (tests only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Beyond this K the full-row design does not fit VMEM at any useful
# block_m; ``fits`` says so and the caller takes the XLA path instead.
_MAX_RESIDENT_K = 8192


def fits(k: int, n: int) -> bool:
    """Whether a [.., k] @ [k, n] matmul can take the kernel: both dims
    lane-aligned and the contraction small enough to stay VMEM-resident."""
    return k <= _MAX_RESIDENT_K and k % 128 == 0 and n % 128 == 0


def int8_matmul_reference(x, w_q, w_scale):
    """Plain-jnp dynamic-quantized matmul (the XLA serving path).

    x: [..., K] float; w_q: [K, N] int8; w_scale: [N] or [1, N] f32
    (per-output-channel).  Returns [..., N] in x.dtype.
    """
    xs = jnp.maximum(
        jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True),
        1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / xs),
                 -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        q, w_q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    ws = w_scale.reshape((1,) * (x.ndim - 1) + (-1,)).astype(jnp.float32)
    return (acc.astype(jnp.float32) * xs * ws).astype(x.dtype)


def _kernel(x_ref, w_ref, ws_ref, o_ref):
    """One (m-block, n-block) program: quantize the row block in VMEM,
    int8 MXU dot, fused dequant epilogue."""
    x = x_ref[:].astype(jnp.float32)                      # [bm, K]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)     # [bm, 1]
    xs = jnp.maximum(amax, 1e-12) / 127.0
    # true divide, not reciprocal-multiply: bit-identical codes to the
    # XLA path (_int8_quant) even on round-to-nearest ties
    q = jnp.clip(jnp.round(x / xs), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        q, w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                 # [bm, bn] s32
    o_ref[:] = (acc.astype(jnp.float32) * xs * ws_ref[:]).astype(o_ref.dtype)


def _vmem_limit_bytes(block_m: int, block_n: int, k: int,
                      itemsize: int) -> int:
    """Scoped-VMEM request for one program, from its shapes: the
    double-buffered x/w/out blocks plus the in-kernel f32 working copies,
    the int8 codes and the s32 accumulator, with a quarter of headroom.
    Mosaic's default scoped limit (16 MiB on v5e) refuses a shape the
    :func:`fits` gate admits — K=8192 at block_m 256 x block_n 512 asks
    for 16.50M — so the request is explicit."""
    blocks = 2 * (block_m * k * itemsize + k * block_n
                  + block_m * block_n * itemsize)
    working = block_m * k * (4 + 4 + 1) + 2 * block_m * block_n * 4
    return max(16 << 20, min(96 << 20, (blocks + working) * 5 // 4))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                              "m_inner", "interpret"))
def _call(x2d, w_q, ws_row, block_m, block_n, m_inner, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x2d.shape
    N = w_q.shape[1]
    pad_m = -M % block_m
    if pad_m:
        x2d = jnp.pad(x2d, ((0, pad_m), (0, 0)))
    Mp = M + pad_m
    if m_inner:
        # grid (n, m): the row index varies innermost, so each WEIGHT
        # block stays VMEM-resident across the full row sweep and the
        # activation streams N/bn times — the right trade when the weight
        # is the bigger stream (x re-reads cost less than w re-reads)
        grid = (N // block_n, Mp // block_m)
        x_map = lambda j, i: (i, 0)
        w_map = lambda j, i: (0, j)
        o_map = lambda j, i: (i, j)
    else:
        grid = (Mp // block_m, N // block_n)
        x_map = lambda i, j: (i, 0)
        w_map = lambda i, j: (0, j)
        o_map = lambda i, j: (i, j)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((Mp, N), x2d.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, K), x_map),
            pl.BlockSpec((K, block_n), w_map),
            pl.BlockSpec((1, block_n), w_map),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), o_map),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(
                block_m, block_n, K, x2d.dtype.itemsize)),
        interpret=interpret,
    )(x2d, w_q, ws_row)
    return out[:M] if pad_m else out


def int8_matmul(x, w_q, w_scale, *, block_m: int = 0, block_n: int = 0,
                m_inner: bool = False, interpret: bool = False,
                force: bool = False):
    """Dynamically-quantized int8 matmul: [..., K] @ [K, N] -> [..., N].

    On a TPU backend (or with ``force``) runs the compiled pallas kernel;
    ``interpret`` runs it in the pallas interpreter (tests).  Whenever the
    kernel is the selected path, a shape it cannot take (:func:`fits`:
    K > 8192 or K/N not lane-aligned) raises ``ValueError`` and a Mosaic
    refusal propagates — no silent switch to the reference.  Only off
    TPU, with neither flag, does the call return
    :func:`int8_matmul_reference` (the kernel cannot compile there).

    ``block_m``/``block_n`` of 0 pick measured defaults: the
    weight-resident schedule (bm=256, bn=N) when the whole weight fits
    VMEM at K>=2048, else output tiles sized to the VMEM budget
    (benchmarks/BERT_PROFILE.md §6 has the measured matrix).
    """
    K = x.shape[-1]
    N = w_q.shape[1]
    if not (interpret or force or jax.default_backend() == "tpu"):
        return int8_matmul_reference(x, w_q, w_scale)
    if not fits(K, N):
        raise ValueError(
            f"int8_matmul: [.., {K}] @ [{K}, {N}] does not fit the kernel "
            f"(needs K <= {_MAX_RESIDENT_K} and K, N multiples of 128); "
            "select by shape with ops.int8_matmul_fits()")
    import os
    blocks_env = os.environ.get("TRITON_TPU_INT8_BLOCKS", "")
    if blocks_env and block_m == 0 and block_n == 0:
        # experimentation knob (benchmarks): "bm:bn", bn may equal N for a
        # weight-resident 1-D grid
        bm_s, bn_s = blocks_env.split(":")
        block_m, block_n = int(bm_s), int(bn_s)
    sched_env = os.environ.get("TRITON_TPU_INT8_SCHED", "")
    if sched_env == "m_inner":
        m_inner = True
    elif sched_env:
        # same loud-rejection policy as TRITON_TPU_INT8_FUSED: a typo'd
        # schedule must not silently measure the default one
        raise ValueError(
            f"TRITON_TPU_INT8_SCHED={sched_env!r}: expected 'm_inner' "
            "or unset")
    if block_n and N % block_n:
        # the grid floors N/block_n — a non-dividing explicit block would
        # leave trailing output columns unwritten.  Explicitly-requested
        # configs fail loudly (a silent XLA fallback would mis-attribute
        # benchmark numbers to the kernel); auto selection below always
        # picks a divisor.
        raise ValueError(
            f"int8_matmul: block_n={block_n} does not divide N={N}")
    if block_m == 0 and block_n == 0 and K >= 2048 and K * N <= 4 * 2**20:
        # weight-resident schedule: the whole [K, N] int8 weight stays in
        # VMEM across the 1-D row grid, so it streams from HBM once per
        # matmul instead of once per row block — the config that beats
        # XLA's unfused path on the FFN-down shape (K=4096, N=1024:
        # 58.4 vs 59.8 ms/forward in-model, benchmarks/BERT_PROFILE.md §6)
        block_m, block_n = 256, N
    if block_m == 0:
        # VMEM budget: the program holds the x row block in bf16 + an f32
        # working copy + the int8 quantized tiles (~7 bytes/elem) plus the
        # w block and s32 accumulator inside the ~16 MB scoped limit —
        # 512 rows fits K<=2048; K=4096 needs 256
        block_m = 512 if K <= 2048 else 256
    if block_n == 0:
        # largest lane-aligned divisor of N up to 512 (N % 128 == 0 was
        # gated above, so 128 always qualifies)
        block_n = next(bn for bn in (512, 384, 256, 128) if N % bn == 0)
    lead = x.shape[:-1]
    M = 1
    for d in lead:
        M *= d
    x2d = x.reshape(M, K)
    # clamp to M, then round up to a sublane multiple: a small unaligned M
    # (e.g. 50) must not produce a Mosaic block like (50, K) — _call's
    # pad_m already covers M < block_m, so rounding up is always safe
    # (Mosaic takes the resulting 56-row block, int8 codes included:
    # compiled and bit-exact on v5e)
    block_m = min(block_m, max(8, M))
    block_m = -(-block_m // 8) * 8
    ws_row = w_scale.reshape(1, N).astype(jnp.float32)
    out = _call(x2d, w_q, ws_row, block_m, block_n, m_inner, interpret)
    return out.reshape(*lead, N)
