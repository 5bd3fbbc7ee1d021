"""Attention as a pallas TPU kernel: the scores never reach HBM.

The serving models' attention (``models/transformer.py:_scores_softmax``)
is the hottest non-matmul op in the framework: left to XLA, a layer's f32
``[B, H, S, S]`` scores go through HBM three times once they outgrow the
chip.  Two forms of one kernel, chosen from the shape (``_flash_call``):

* **whole-row** (padded S <= 512: ``bert_large`` and the short decoders):
  one program per (batch, head-block) holds every key of its heads, so
  QK^T and P·V run once each, both on the operands' own dtype (bf16 when
  serving) with f32 accumulation, and the softmax between them (max, exp,
  sum, normalisation: f32) needs no carry.  Operands are ``[B, H, D, S]``,
  the layout XLA gives the projections on either side.
* **looped** (longer rows: ``longctx_tpu``): one program per (batch·head,
  q-block) holds the head's full K/V in VMEM and walks k-blocks with a
  ``fori_loop`` carrying the online (m, l, acc) state — the in-VMEM mirror
  of the cross-device ring in ``_ring_attention`` (same math, one chip) —
  and skips the blocks a causal mask empties.

``flash_attention`` pads S to the block size and masks the padding away, so
any sequence length works. On a TPU backend the kernel is compiled by
Mosaic and whatever the compiler refuses is raised to the caller — there
is no fallback there. Off TPU, where the kernel cannot compile, callers get
the jnp reference; ``interpret=True`` runs the kernel itself in the pallas
interpreter (tests only — nothing under ``server/`` or ``models/`` passes
it) and ``force=True`` means "the compiled kernel or an error" anywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def flash_attention_reference(q, k, v, *, causal: bool = True, sm_scale=None):
    """Plain-jnp attention with the same signature/semantics as the kernel.

    q, k: [B, H, S, D]; v: [B, H, S, Dv]; returns [B, H, S, Dv] in q.dtype.
    """
    B, H, S, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        idx = jnp.arange(S)
        mask = idx[:, None] >= idx[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _loop_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, block_q,
                 block_k, seq_len, n_kblocks):
    """Long rows: one (batch·head, q-block) program. Refs carry a leading
    length-1 block dim; k/v refs hold the head's full (padded) sequence,
    walked in ``block_k`` steps under the online-softmax carry.  q and k
    share one width, v and the output another (latent attention's 192 and
    128; the same everywhere else)."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_start = _pl().program_id(1) * block_q

    # f32 operands here, unlike the whole-row kernel: Mosaic rounds them to
    # bf16 in the MXU's feed either way (the results agree bit for bit), and
    # bf16 refs with P packed before each P·V read 3% slower at S=4096
    # (0.788 against 0.765 ms a layer; my chip run, PR 27)
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, _pl().ds(j * block_k, block_k), :]  # [block_k, D]
        v_blk = v_ref[0, _pl().ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        q_idx = q_start + qi
        k_idx = j * block_k + ki
        valid = k_idx < seq_len  # mask the S-padding keys
        if causal:
            valid = jnp.logical_and(valid, q_idx >= k_idx)
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # a fully-masked row would exp(-inf - -inf)=exp(0); zero it instead
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[:, None] * acc + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)  # follows v
    if causal:
        # skip k-blocks that lie entirely above the diagonal: the last key
        # this q-block may attend to is q_start + block_q - 1, so only
        # ceil((q_start + block_q) / block_k) blocks carry any work — the
        # causal early exit that halves the FLOPs vs masking everything
        n_iter = (q_start + block_q + block_k - 1) // block_k
        n_iter = jnp.minimum(n_iter, n_kblocks)
    else:
        n_iter = n_kblocks
    _, l, acc = jax.lax.fori_loop(0, n_iter, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


def _row_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, seq_len):
    """Short rows: one (batch, head-block) program that sees every key, so
    QK^T and P·V run once each and the softmax needs no carry.

    Refs are ``[1, heads, D, S]`` — each head transposed, the sequence on
    the lanes: the layout XLA gives the projections on either side, so no
    copy stands between them and the kernel, and a head narrower than a
    vreg's 128 lanes pads nothing.  Only q and the output are turned in
    VMEM (``[D, S]``, a sixth of a score tile): P then streams through the
    MXU against v as the stationary operand, the faster way round on the
    chip (PERF.md §6, PR 27)."""
    heads, _, s_pad = q_ref.shape[1:]
    valid = None
    if causal or s_pad > seq_len:
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (s_pad, s_pad), 1)
        valid = k_idx < seq_len  # [queries, keys]
        if causal:
            q_idx = jax.lax.broadcasted_iota(jnp.int32, (s_pad, s_pad), 0)
            valid = jnp.logical_and(valid, q_idx >= k_idx)
    for h in range(heads):  # unrolled: head h+1's matmuls overlap h's softmax
        # operands reach the MXU in the dtype they arrive in (bf16 when
        # serving); the scores, their statistics and the accumulator are f32
        q = (q_ref[0, h] * scale).astype(q_ref.dtype).T  # [S, D]
        s = jnp.dot(q, k_ref[0, h], preferred_element_type=jnp.float32)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        # every query keeps at least key 0 (causal: its diagonal), so the
        # masked entries underflow to exactly 0 and l > 0
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, h],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / l  # [S, D]
        o_ref[0, h] = o.T.astype(o_ref.dtype)


def _pl():
    from jax.experimental import pallas as pl

    return pl


# a whole key row (padded) this short stays in VMEM as one f32 score tile
_ROW_MAX_S = 512
# four blocks, double-buffered, and an unrolled program's score tiles have
# to fit the 16 MiB of VMEM a kernel may scope
_ROW_BLOCK_BYTES = 768 * 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row_call(q, k, v, causal, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    s_pad = _round_up(S, 128)

    def lanes(x):  # [B, H, S, D] -> [B, H, D, s_pad]
        x = x.transpose(0, 1, 3, 2)
        return jnp.pad(x, [(0, 0)] * 3 + [(0, s_pad - S)]) if s_pad > S else x

    # as many heads a program as keep a block within _ROW_BLOCK_BYTES: all
    # 16 of bert_large's ([32,384]: 0.377 ms a layer against 0.384 at 8 and
    # 0.406 at 4; my chip run, PR 27), fewer for wider heads or f32
    per_head = D * s_pad * q.dtype.itemsize
    hb = max(d for d in range(1, H + 1)
             if H % d == 0 and (d == 1 or d * per_head <= _ROW_BLOCK_BYTES))
    block = pl.BlockSpec((1, hb, D, s_pad), lambda b, h: (b, h, 0, 0))
    out = pl.pallas_call(
        functools.partial(_row_kernel, scale=scale, causal=causal, seq_len=S),
        out_shape=jax.ShapeDtypeStruct((B, H, D, s_pad), q.dtype),
        grid=(B, H // hb),
        in_specs=[block, block, block],
        out_specs=block,
        interpret=interpret,
    )(lanes(q), lanes(k), lanes(v))
    return out[..., :S].transpose(0, 1, 3, 2)


def _loop_call(q, k, v, causal, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    Dv = v.shape[-1]
    bq, bk = (256, 512) if S >= 1024 else (128, 128)
    pad = -S % bk
    if pad:
        zeros = [(0, 0), (0, 0), (0, pad), (0, 0)]
        q, k, v = (jnp.pad(x, zeros) for x in (q, k, v))
    Sp = S + pad
    q, k, v = (x.reshape(B * H, Sp, x.shape[-1]) for x in (q, k, v))
    kernel = functools.partial(
        _loop_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_len=S, n_kblocks=Sp // bk)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, Dv), q.dtype),
        grid=(B * H, Sp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Sp, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, Sp, Dv), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda bh, qi: (bh, qi, 0)),
        interpret=interpret,
    )(q, k, v)
    out = out.reshape(B, H, Sp, Dv)
    return out[:, :, :S, :] if pad else out


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "interpret"))
def _flash_call(q, k, v, causal, sm_scale, interpret):
    """The form follows the shape: a key row short enough for one f32 score
    tile in VMEM takes the whole-row kernel, a longer one the looped, and so
    does a value narrower or wider than the keys."""
    S, D = q.shape[2:]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    if _round_up(S, 128) <= _ROW_MAX_S and v.shape[-1] == D:
        return _row_call(q, k, v, causal, scale, interpret)
    return _loop_call(q, k, v, causal, scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, interpret):
    return _flash_call(q, k, v, causal, sm_scale, interpret)


def _flash_fwd(q, k, v, causal, sm_scale, interpret):
    return _flash_call(q, k, v, causal, sm_scale, interpret), (q, k, v)


def _flash_bwd(causal, sm_scale, interpret, res, g):
    # Backward recomputes attention through the jnp reference and takes its
    # VJP — the standard flash trade (no stored [S,S] probabilities costs a
    # recompute); XLA fuses it into one fp32 pass.
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_reference(
            q_, k_, v_, causal=causal, sm_scale=sm_scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                    interpret: bool = False, force: bool = False):
    """Attention over [B, H, S, D] tensors without the scores in HBM;
    differentiable.  ``v`` may have another width than ``q`` and ``k``; the
    output and the VJP follow it.  Block shapes and the kernel's form
    (whole-row or looped) follow ``(S, D)``: see :func:`_flash_call`.

    On a TPU backend (or with ``force``) this runs the compiled pallas
    kernel, and a Mosaic refusal raises — no fallback.  Off TPU, with
    neither flag, it returns :func:`flash_attention_reference` (the
    kernel cannot compile there); ``interpret`` runs the kernel in the
    pallas interpreter — slow, for tests.
    """
    if not (interpret or force) and jax.default_backend() != "tpu":
        return flash_attention_reference(q, k, v, causal=causal,
                                         sm_scale=sm_scale)
    return _flash(q, k, v, causal, sm_scale, interpret)
