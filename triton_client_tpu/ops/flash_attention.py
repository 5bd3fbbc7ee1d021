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
* **looped** (longer rows, or a v of another width than the keys:
  ``longctx_tpu``, latent attention's prefill): one program per
  (batch·head, q-block) holds the head's full K/V in VMEM and walks
  k-blocks with ``fori_loop``s carrying the online (m, l, acc) state — the
  in-VMEM mirror of the cross-device ring in ``_ring_attention`` (same
  math, one chip).  It skips the blocks a causal mask empties, masks the
  block on the diagonal (or the one the padding starts in) and no other,
  and takes the blocks before it four at a time (``_loop_plan`` says what
  a shape walks).  Operands are ``[B·H, S, D]``, k ``[B·H, D, S]``.
* **windowed** (a causal mask that also hides the keys ``window`` or more
  positions back: the sliding layers of a model that mixes them with full
  ones): the looped form's walk cut to the window (``_window_call``, its
  own jitted name).  A program reads from HBM only the keys of its q-block's
  window, starts its walk there, takes the whole blocks in wide steps and
  masks the window's left edge and the diagonal alone (``_window_plan``).

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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def flash_attention_reference(q, k, v, *, causal: bool = True, sm_scale=None,
                              mask_block: int = 1,
                              window: Optional[int] = None):
    """Plain-jnp attention with the same signature/semantics as the kernel.

    q: [B, H, S, D]; k: [B, Hkv, S, D]; v: [B, Hkv, S, Dv] with ``H`` a
    multiple of ``Hkv`` (query head ``h`` reads key/value head ``h // (H //
    Hkv)``); returns [B, H, S, Dv] in q.dtype.  ``mask_block`` widens the
    causal mask to blocks: row ``i`` sees key ``j`` where ``j // mask_block
    <= i // mask_block`` (1 is the plain causal mask).  A ``window`` (causal
    only) hides the keys that lie ``window`` or more positions back: row
    ``i`` sees key ``j`` where ``i - window < j <= i``.
    """
    B, H, S, D = q.shape
    group = H // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        idx = jnp.arange(S)
        mask = (idx[:, None] | (mask_block - 1)) >= idx[None, :]
        if window is not None:
            mask = mask & (idx[:, None] - idx[None, :] < window)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


class _LoopPlan(NamedTuple):
    """What the looped form does at one shape: its blocks, and what a
    head's walk spends on the diagonal and the padding (a test pins it,
    PERF.md quotes it).  Blocks are counted ``block`` keys wide, whichever
    step takes them."""
    block: int          # query rows a program holds, and the keys of a
    #                     narrow step: the block on the diagonal is square
    wide: int           # keys of a wide step, a multiple of ``block``
    seq_pad: int        # S rounded up to the block
    walked: int         # k-blocks a head walks, all its q-blocks together
    masked: int         # of them, those that take the masked body
    dead_columns: int   # key columns computed that no row of their block
    #                     may see (padding; a square diagonal has none)
    vmem_bytes: int     # what the call asks the compiler to scope


def _whole_blocks(q_block, n_blocks: int, causal: bool, padded: bool):
    """k-blocks the ``q_block``-th program walks with no mask, from key 0:
    those below its diagonal, or all but the one the padding starts in.
    One masked block follows them, or none (no causal mask, no padding).
    ``q_block`` may be traced: the kernel and the plan share this."""
    return q_block if causal else n_blocks - int(padded)


def _loop_plan(S: int, D: int, Dv: int, dtype, causal: bool) -> _LoopPlan:
    """Blocks follow the shape alone: 512 rows from S = 1024 up (a key
    tile then stays in the MXU for 512 rows, and the diagonal block is
    square), 128 below, where padding a short row to 512 would cost more
    than it saves; a wide step is four blocks."""
    block = 512 if S >= 1024 else 128
    seq_pad = _round_up(S, block)
    n = seq_pad // block
    wide = block * min(4, n)
    padded = seq_pad > S
    n_masked = int(causal or padded)
    walked = sum(_whole_blocks(i, n, causal, padded) + n_masked
                 for i in range(n))
    dead = (seq_pad - S) * (1 if causal else n)
    # K and V of a head whole and q, o by the block, each double-buffered
    # in tiles of (16, 128); the f32 scores of a wide step, their
    # exponentials in f32 and packed, and as much again for the compiler
    itemsize = jnp.dtype(dtype).itemsize
    resident = 2 * itemsize * (
        _round_up(D, 16) * seq_pad + seq_pad * _round_up(Dv, 128)
        + block * (_round_up(D, 128) + _round_up(Dv, 128)))
    scores = block * wide * 4
    return _LoopPlan(block, wide, seq_pad, walked, n_masked * n, dead,
                     resident + 5 * scores + (4 << 20))


class _WindowPlan(NamedTuple):
    """What the windowed form does at one shape (a test pins it, PERF.md
    quotes it); blocks are counted ``block`` keys wide."""
    block: int          # query rows a program holds; keys of a narrow step
    wide: int           # keys of a wide step, a multiple of ``block``
    seq_pad: int        # S rounded up to the block
    span: int           # keys a program reads from HBM: its window's blocks
    walked: int         # k-blocks a head walks, all its q-blocks together
    masked: int         # of them, those that take the mask (edge, diagonal)
    vmem_bytes: int     # what the call asks the compiler to scope


def _window_blocks(q_block, window: int, block: int):
    """``(lo, whole_from)`` of the ``q_block``-th program: its walk covers
    k-blocks ``lo .. q_block``; ``lo .. whole_from - 1`` hold the window's
    left edge (keys some row of the block may not see), ``whole_from ..
    q_block - 1`` are whole, ``q_block`` is the diagonal.  ``q_block`` may
    be traced: the kernel, its index maps and the plan share this."""
    big, small = ((jnp.maximum, jnp.minimum) if isinstance(q_block, jax.Array)
                  else (max, min))
    lo = big(0, q_block - -(-window // block))
    return lo, small(big(lo, q_block + 1 - window // block), q_block)


def _window_plan(S: int, D: int, Dv: int, dtype, window: int) -> _WindowPlan:
    """The looped form's blocks; a wide step takes the whole blocks of a
    full window at once, up to four (three at a window of four blocks),
    and no more than S holds."""
    block = 512 if S >= 1024 else 128
    seq_pad = _round_up(S, block)
    n = seq_pad // block
    span = block * min(n, -(-window // block) + 1)
    wide = block * max(1, min(4, window // block - 1, n - 1))
    walked = masked = 0
    for i in range(n):
        lo, whole_from = _window_blocks(i, window, block)
        walked += i - lo + 1
        masked += whole_from - lo + 1
    # the window's K and V and q, o by the block, each double-buffered in
    # tiles of (16, 128); the scores of a wide step as the looped form has
    itemsize = jnp.dtype(dtype).itemsize
    resident = 2 * itemsize * (
        _round_up(D, 16) * span + span * _round_up(Dv, 128)
        + block * (_round_up(D, 128) + _round_up(Dv, 128)))
    scores = block * wide * 4
    return _WindowPlan(block, wide, seq_pad, span, walked, masked,
                       resident + 5 * scores + (4 << 20))


def _loop_kernel(q_ref, kt_ref, v_ref, o_ref, *, scale, causal, block, wide,
                 seq_len, n_blocks, mask_block=1):
    """Long rows: one (batch·head, q-block) program.  Refs carry a leading
    length-1 block dim; the k and v refs hold the head's full (padded)
    sequence, k turned (``[D, S]``, the sequence on the lanes, as the
    whole-row form reads it), walked under the online-softmax carry.  q
    and k share one width, v and the output another (latent attention's
    192 and 128; the same everywhere else).  A causal mask over blocks
    of ``mask_block`` rows (a power of two that divides ``block``) differs
    from the plain one inside the diagonal block alone: row ``i`` sees the
    keys up to ``i | (mask_block - 1)``.

    What decides the loop's shape (PERF.md §6, PR 31; one v5e, ms a call
    at ``[2,64,8192,192/128]`` bf16, the parent's loop 30.9):

    * **the mask is the last block's alone** (28.2).  Every block below
      the diagonal (without a causal mask: before the one the padding
      starts in) is whole, so the loops' body has no iota, compare or
      select; the one masked block is straight-line code after them, and
      square, so no column of it is computed for nothing.  A masked score
      underflows to exactly 0 against the finite maximum of its row,
      which key 0 guarantees, so nothing guards the probabilities either.
    * **operands as they arrive** (bf16 when serving), P packed once
      before P·V, accumulation and every statistic in f32.  Mosaic rounds
      f32 operands to bf16 in the MXU's feed, so this is the same
      arithmetic.  Alone it moves nothing at either width (31.0; at
      ``[1,16,4096,64]`` 0.756 against the parent's 0.735, as PR 27
      read it); it stays at both because the next lever needs a key
      tile that goes to the MXU as it lies (0.601 at 64 wide with it).
    * **k turned before the call** (26.1 from 28.3): ``q @ k_blk.T``
      pushes every key tile into the MXU through its transposing path,
      272 times a head; one XLA transpose a call is 0.5 ms.
    * **wide steps**: four blocks' scores in one dot while that many
      whole blocks are left, then single blocks up to the diagonal (23.5;
      single blocks alone 25.5, unrolled twice 23.9, eight a step 25.4;
      two score buffers with the next QK^T issued by hand 24.2 and
      twice the code).
    * **row sums spread over the lanes** (23.5 from 25.1): a wide step's
      cross-lane sum is as long as its cross-lane maximum, and only the
      maximum is needed before the next step."""
    pl = _pl()
    q_block = pl.program_id(1)
    # scaled in f32 and rounded once, as the MXU's feed would
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    padded = n_blocks * block > seq_len

    def step(carry, start, width, masked):
        start = pl.multiple_of(start, width)
        s = jnp.dot(q, kt_ref[0, :, pl.ds(start, width)],
                    preferred_element_type=jnp.float32)  # [block, width]
        if masked:
            k_idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = k_idx < seq_len if padded else None
            if causal:
                q_idx = q_block * block + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                if mask_block > 1:  # the end of the row's block of rows
                    q_idx = q_idx | (mask_block - 1)
                below = q_idx >= k_idx
                valid = below if valid is None else jnp.logical_and(
                    valid, below)
            s = jnp.where(valid, s, _NEG_INF)
        return _softmax_step(carry, s,
                             lambda: v_ref[0, pl.ds(start, width), :])

    carry = _softmax_start(block, v_ref.shape[-1])
    whole = _whole_blocks(q_block, n_blocks, causal, padded)
    per_wide = wide // block
    n_wide = whole // per_wide
    carry = jax.lax.fori_loop(
        0, n_wide, lambda j, c: step(c, j * wide, wide, False), carry)
    carry = jax.lax.fori_loop(
        n_wide * per_wide, whole,
        lambda j, c: step(c, j * block, block, False), carry)
    if causal or padded:
        carry = step(carry, whole * block, block, True)
    _softmax_end(carry, o_ref)


def _softmax_start(block: int, dv: int):
    """The online softmax's carry before the first step: the row maxima,
    the row sums spread over 128 lanes, the accumulator (as wide as v)."""
    return (jnp.full((block, 1), _NEG_INF, jnp.float32),
            jnp.zeros((block, 128), jnp.float32),
            jnp.zeros((block, dv), jnp.float32))


def _softmax_step(carry, s, values):
    """The carry after a step's f32 scores ``s [block, width]``;
    ``values()`` loads the step's ``[width, Dv]`` values, after the
    exponentials."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    # the row sums stay spread over the 128 lanes: one cross-lane
    # reduction a program, at the end, and not one a step
    spread = p[:, :128]
    for t in range(1, s.shape[1] // 128):
        spread = spread + p[:, t * 128:(t + 1) * 128]
    v_blk = values()
    acc_new = corr * acc + jnp.dot(
        p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)
    return m_new, corr * l + spread, acc_new


def _softmax_end(carry, o_ref):
    _, l, acc = carry
    out = acc / jnp.sum(l, axis=-1, keepdims=True)
    o_ref[0] = out.astype(o_ref.dtype)


def _window_kernel(q_ref, kt_ref, v_ref, o_ref, *, scale, block, wide,
                   window, seq_len, n_blocks):
    """The windowed form: one (batch·key/value head, q-block, query head of
    the group) program.  ``kt_ref [D, span]`` (k turned) and ``v_ref [span,
    Dv]`` hold the keys of the q-block's window alone, from k-block ``lo``
    on (``_window_blocks``); the query heads of a group run one after the
    other over the same window, which is fetched once for them.  The walk
    is the looped form's: the whole blocks first (``wide`` keys a step while
    that many are left, then single blocks), each without iota, compare or
    select; then the window's left edge and the diagonal, each masked by
    ``i - window < j <= i`` (and ``j < S`` where S was padded).  A row that
    sees no key of an edge block takes exponentials of 0 there, which the
    diagonal's finite maximum (its own key) multiplies by exactly 0."""
    pl = _pl()
    q_block = pl.program_id(1)
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    padded = n_blocks * block > seq_len
    lo, whole_from = _window_blocks(q_block, window, block)

    def step(carry, j, width, masked):
        start = pl.multiple_of((j - lo) * block, block)
        s = jnp.dot(q, kt_ref[:, pl.ds(start, width)],
                    preferred_element_type=jnp.float32)  # [block, width]
        if masked:
            k_idx = j * block + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            q_idx = q_block * block + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            valid = jnp.logical_and(k_idx <= q_idx, q_idx - k_idx < window)
            if padded:
                valid = jnp.logical_and(valid, k_idx < seq_len)
            s = jnp.where(valid, s, _NEG_INF)
        return _softmax_step(carry, s,
                             lambda: v_ref[pl.ds(start, width), :])

    carry = _softmax_start(block, v_ref.shape[-1])
    per_wide = wide // block
    n_wide = (q_block - whole_from) // per_wide
    carry = jax.lax.fori_loop(
        0, n_wide,
        lambda t, c: step(c, whole_from + t * per_wide, wide, False), carry)
    carry = jax.lax.fori_loop(
        whole_from + n_wide * per_wide, q_block,
        lambda j, c: step(c, j, block, False), carry)
    carry = jax.lax.fori_loop(
        lo, whole_from, lambda j, c: step(c, j, block, True), carry)
    carry = step(carry, q_block, block, True)
    _softmax_end(carry, o_ref)


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


def _loop_call(q, k, v, causal, scale, interpret, mask_block=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Dv = v.shape[-1]
    # query heads a key/value head serves: the programs of one group read
    # the same k and v block (``bh // group``), which is fetched once for
    # them all and never repeated in HBM
    group = H // k.shape[1]
    plan = _loop_plan(S, D, Dv, q.dtype, causal)
    blk, Sp = plan.block, plan.seq_pad
    if mask_block & (mask_block - 1) or blk % mask_block:
        raise ValueError(f"mask_block {mask_block}: a power of two that "
                         f"divides the kernel's block of {blk}")
    if Sp > S:
        zeros = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        q, k, v = (jnp.pad(x, zeros) for x in (q, k, v))
    q, k, v = (x.reshape(-1, Sp, x.shape[-1]) for x in (q, k, v))
    kernel = functools.partial(
        _loop_kernel, scale=scale, causal=causal, block=blk, wide=plan.wide,
        seq_len=S, n_blocks=Sp // blk,
        mask_block=mask_block if causal else 1)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, Dv), q.dtype),
        grid=(B * H, Sp // blk),
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, D, Sp), lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, Sp, Dv), lambda bh, qi: (bh // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, Dv), lambda bh, qi: (bh, qi, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_bytes),
        interpret=interpret,
    )(q, k.transpose(0, 2, 1), v)
    out = out.reshape(B, H, Sp, Dv)
    return out[:, :, :S, :] if Sp > S else out


@functools.partial(jax.jit, static_argnames=("window", "sm_scale",
                                             "interpret"))
def _window_call(q, k, v, window, sm_scale, interpret):
    """Causal attention in which row ``i`` sees keys ``i - window < j <=
    i``: the windowed form at any shape, under a name of its own so that a
    trace tells it from ``_flash_call``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[-1]
    if H % Hkv or Hkv != v.shape[1]:
        raise ValueError(f"{H} query heads over {Hkv} key and "
                         f"{v.shape[1]} value heads")
    if window < 1:
        raise ValueError(f"window {window}: at least 1")
    group = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    plan = _window_plan(S, D, Dv, q.dtype, window)
    blk, Sp, span = plan.block, plan.seq_pad, plan.span
    if Sp > S:
        zeros = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        q, k, v = (jnp.pad(x, zeros) for x in (q, k, v))
    q, k, v = (x.reshape(-1, Sp, x.shape[-1]) for x in (q, k, v))

    def first_key(qi):  # where the program's window starts, in keys
        return _window_blocks(qi, window, blk)[0] * blk

    heads = lambda b, qi, g: (b * group + g, qi, 0)  # noqa: E731
    kernel = functools.partial(
        _window_kernel, scale=scale, block=blk, wide=plan.wide,
        window=window, seq_len=S, n_blocks=Sp // blk)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, Dv), q.dtype),
        grid=(B * Hkv, Sp // blk, group),
        in_specs=[
            pl.BlockSpec((1, blk, D), heads),
            pl.BlockSpec((None, pl.Element(D), pl.Element(span)),
                         lambda b, qi, g: (b, 0, first_key(qi))),
            pl.BlockSpec((None, pl.Element(span), pl.Element(Dv)),
                         lambda b, qi, g: (b, first_key(qi), 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, Dv), heads),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_bytes),
        interpret=interpret,
    )(q, k.transpose(0, 2, 1), v)
    out = out.reshape(B, H, Sp, Dv)
    return out[:, :, :S, :] if Sp > S else out


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "interpret",
                                             "mask_block"))
def _flash_call(q, k, v, causal, sm_scale, interpret, mask_block=1):
    """The form follows the shape: a key row short enough for one f32 score
    tile in VMEM takes the whole-row kernel, a longer one the looped, and so
    does a value narrower or wider than the keys, keys of fewer heads than
    the queries, or a causal mask over blocks of rows."""
    H, S, D = q.shape[1:]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    if H % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{H} query heads over {k.shape[1]} key and "
                         f"{v.shape[1]} value heads")
    plain = k.shape[1] == H and (mask_block == 1 or not causal)
    if _round_up(S, 128) <= _ROW_MAX_S and v.shape[-1] == D and plain:
        return _row_call(q, k, v, causal, scale, interpret)
    return _loop_call(q, k, v, causal, scale, interpret, mask_block)


def _call(q, k, v, causal, sm_scale, interpret, mask_block, window):
    if window is None:
        return _flash_call(q, k, v, causal, sm_scale, interpret, mask_block)
    return _window_call(q, k, v, window, sm_scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, interpret, mask_block, window):
    return _call(q, k, v, causal, sm_scale, interpret, mask_block, window)


def _flash_fwd(q, k, v, causal, sm_scale, interpret, mask_block, window):
    return (_call(q, k, v, causal, sm_scale, interpret, mask_block, window),
            (q, k, v))


def _flash_bwd(causal, sm_scale, interpret, mask_block, window, res, g):
    # Backward recomputes attention through the jnp reference and takes its
    # VJP — the standard flash trade (no stored [S,S] probabilities costs a
    # recompute); XLA fuses it into one fp32 pass.
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_reference(
            q_, k_, v_, causal=causal, sm_scale=sm_scale,
            mask_block=mask_block, window=window),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                    mask_block: int = 1, window: Optional[int] = None,
                    interpret: bool = False, force: bool = False):
    """Attention over [B, H, S, D] tensors without the scores in HBM;
    differentiable.  ``v`` may have another width than ``q`` and ``k``; the
    output and the VJP follow it.  ``k`` and ``v`` may have fewer heads than
    ``q`` (grouped queries: head ``h`` reads ``h // (H // Hkv)``), and a
    causal mask may run over blocks of ``mask_block`` rows (a power of two;
    row ``i`` sees key ``j`` where ``j // mask_block <= i // mask_block``)
    or be cut to a ``window`` (row ``i`` sees key ``j`` where ``i - window <
    j <= i``: the windowed form, :func:`_window_call`).  Block shapes and
    the kernel's form (whole-row or looped) follow the operands: see
    :func:`_flash_call`.

    On a TPU backend (or with ``force``) this runs the compiled pallas
    kernel, and a Mosaic refusal raises — no fallback.  Off TPU, with
    neither flag, it returns :func:`flash_attention_reference` (the
    kernel cannot compile there); ``interpret`` runs the kernel in the
    pallas interpreter — slow, for tests.
    """
    if window is not None and (not causal or mask_block != 1):
        raise ValueError("a window cuts the plain causal mask: causal, "
                         "mask_block 1")
    if not (interpret or force) and jax.default_backend() != "tpu":
        return flash_attention_reference(q, k, v, causal=causal,
                                         sm_scale=sm_scale,
                                         mask_block=mask_block, window=window)
    return _flash(q, k, v, causal, sm_scale, interpret, mask_block, window)
