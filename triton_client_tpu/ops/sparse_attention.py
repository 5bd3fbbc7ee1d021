"""Attention over a chosen set of keys a query, with a learned sink.

Learned sparse attention (``models/sparse_latent.py``) lets each query ``t``
read only the keys ``S_t`` an indexer chose.  The choice travels as a
bit mask, one bit a (query, key) pair, laid out in **bit planes**: a row of
``W`` int32 words a query, key ``s`` at bit ``s // W`` of word ``s % W``
(:func:`words`).  Keys ``c·W .. c·W + W - 1`` are then bit ``c`` of every
word of the row: a chunk of ``W`` keys is ``(bits >> c) & 1`` over the row,
elementwise, with no lane moved.

Each head ``h`` has a learned sink ``z_h``, a logit with no value:
``p_{t,s} = exp(l_{t,s}) / (exp(z_h) + sum_{s' in S_t} exp(l_{t,s'}))``.

The kernel is the looped form of ``ops/flash_attention.py`` with the mask
read from the bits: one program per (batch·head, query block) holds the
head's keys and values in VMEM and walks the planes up to its block's last
row, four planes to a step while four are left, every plane masked
(``_dsa_plan`` says what a shape walks).  The online softmax starts from
the sink (``m = z_h``, ``l = 1``), so the sink is in the denominator and a
row whose first planes hold no chosen key adds exact zeros.  It does the
causal half of the dense work, whatever the selection: a masked form.  Off
TPU the plain form below runs (tests pass ``interpret``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def words(seq_len: int) -> int:
    """int32 words a query's bit row holds: at least 128 (a vreg's lanes),
    and enough that 32 bit planes cover ``seq_len`` keys."""
    return max(128, _round_up(-(-seq_len // 32), 128))


def pack(chosen):
    """``chosen [..., S]`` bool -> ``[..., words(S)]`` int32 bit planes."""
    S = chosen.shape[-1]
    W = words(S)
    planes = -(-S // W)
    chosen = jnp.pad(chosen, [(0, 0)] * (chosen.ndim - 1)
                     + [(0, planes * W - S)])
    chosen = chosen.reshape(chosen.shape[:-1] + (planes, W)).astype(jnp.int32)
    shifts = jnp.arange(planes, dtype=jnp.int32)[:, None]
    # distinct bits: the sum is their union, bit 31 included
    return jnp.sum(jnp.left_shift(chosen, shifts), axis=-2, dtype=jnp.int32)


def unpack(bits, seq_len: int):
    """``[..., W]`` int32 bit planes -> ``[..., seq_len]`` bool."""
    W = bits.shape[-1]
    s = jnp.arange(seq_len)
    return jnp.right_shift(bits[..., s % W], (s // W).astype(jnp.int32)) & 1 \
        != 0


def sparse_attention_reference(q, k, v, bits, sink, *, sm_scale):
    """Plain-jnp form: q, k ``[B,H,S,D]``, v ``[B,H,S,Dv]``, bits
    ``[B,S,W]``, sink ``[H]`` -> ``[B,H,S,Dv]`` in q's dtype."""
    S = q.shape[2]
    chosen = unpack(bits, S)[:, None]                       # [B,1,S,S]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(chosen, s, _NEG_INF)
    z = sink.astype(jnp.float32)[None, :, None, None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), z)
    p = jnp.exp(s - m)
    denom = jnp.exp(z - m) + jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / denom, v.astype(jnp.float32))
    return o.astype(q.dtype)


class _DsaPlan(NamedTuple):
    """What the kernel walks at one shape (a test pins it, PERF.md quotes
    it).  A plane is ``W`` keys: bit ``c`` of every word of a bit row."""
    block: int          # query rows a program holds
    wide: int           # bit planes a wide step takes in one dot
    n_planes: int       # bit planes a row holds, ``ceil(S / W)``
    walked: int         # planes a head walks, all its query blocks together
    wide_steps: int     # of a head's steps, the wide ones
    single_steps: int   # and the single planes that finish a block
    vmem_bytes: int     # what the call asks the compiler to scope


def _planes_to_row(q_block, block: int, width: int):
    """Bit planes from plane 0 that hold a key at or before the
    ``q_block``-th block's last row; past the row's planes for a padded
    block, so the kernel and the plan clamp it to ``n_planes``."""
    return ((q_block + 1) * block + width - 1) // width


def _dsa_plan(S: int, D: int, Dv: int, W: int, dtype) -> _DsaPlan:
    """Blocks of 512 rows from S = 1024 up, 128 below, as the looped flash
    form takes them; a wide step is four planes (1,024 keys at W = 256)."""
    block, wide = (512 if S >= 1024 else 128), 4
    n_planes = -(-S // W)
    walks = [min(_planes_to_row(i, block, W), n_planes)
             for i in range(-(-S // block))]
    # K and V of a head whole, q, o and the block's bit rows by the block,
    # each double-buffered; the f32 scores of a wide step, their
    # exponentials in f32 and packed, and as much again for the compiler
    itemsize = jnp.dtype(dtype).itemsize
    s_keys = n_planes * W
    resident = 2 * itemsize * (
        _round_up(D, 16) * s_keys + s_keys * _round_up(Dv, 128)
        + block * (_round_up(D, 128) + _round_up(Dv, 128))) + 2 * 4 * block * W
    scores = block * wide * W * 4
    return _DsaPlan(block, wide, n_planes, sum(walks),
                    sum(n // wide for n in walks),
                    sum(n % wide for n in walks),
                    resident + 5 * scores + (4 << 20))


def _dsa_kernel(sink_ref, q_ref, kt_ref, v_ref, bits_ref, o_ref, *, scale,
                block, width, wide, n_planes):
    """One (batch·head, query block) program.  ``kt_ref`` holds the head's
    keys turned (``[D, S]``), ``v_ref`` its values, ``bits_ref`` the block's
    rows of bit planes ``[block, width]``, ``sink_ref`` a tile filled with
    the head's sink.  It walks the planes up to its block's last row, the
    same planes and pairs whatever the bits choose.

    What decides the loop's shape (PERF.md §6): the looped flash form's
    levers carried over to the bits, each with its chip number, ms a call
    at ``[2,64,8192,256]`` with 2,048 keys chosen a query on one v5e:
    39.93 before them, 32.55 now.  Each lever was taken out (→ kept) of
    the kernel that also tested the plane's bit at the sign (33.01):

    * **wide steps**: four planes, contiguous keys ``c·W .. (c+4)·W - 1``,
      in one dot and one softmax update while that many are left below the
      block's last row, then single planes; a plane's mask is a shift of
      the same ``[block, W]`` bits tile, so four planes' masks lie side by
      side on the lanes with no data moved.  40.29 → 33.01.
    * **row sums spread over the 128 lanes**, summed across them once a
      program: only the maximum is needed across lanes before the next
      step.  The sink's ``exp(z - m)`` starts in lane 0 alone.  34.09 →
      33.01.

    Not taken: the plane's bit tested at the sign (``bits << (31 - c) <
    0``), two VPU ops a score for three, yet 33.01 against the shift and
    mask's 32.55; blocks of 1,024 rows, 34.43.  The mask stays: an
    unchosen score never sets ``m``, which starts at the sink, so the
    arithmetic is the plain form's."""
    pl = _pl()
    qi = pl.program_id(1)
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    bits = bits_ref[0]

    def step(carry, c, planes):
        """``planes`` planes from plane ``c`` in one dot."""
        m, l, acc = carry
        start = pl.multiple_of(c * width, width)
        keys = planes * width
        s = jnp.dot(q, kt_ref[0, :, pl.ds(start, keys)],
                    preferred_element_type=jnp.float32)   # [block, keys]
        # plane c + j's bit of its lanes' word, four planes side by side
        on = [jnp.right_shift(bits, c + j) & 1 for j in range(planes)]
        on = on[0] if planes == 1 else jnp.concatenate(on, axis=1)
        s = jnp.where(on != 0, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        spread = p[:, :128]
        for t in range(1, keys // 128):
            spread = spread + p[:, t * 128:(t + 1) * 128]
        v_blk = v_ref[0, pl.ds(start, keys), :]
        acc = corr * acc + jnp.dot(p.astype(v_blk.dtype), v_blk,
                                   preferred_element_type=jnp.float32)
        return m_new, corr * l + spread, acc

    z = jnp.max(sink_ref[0], axis=1, keepdims=True)[:1]     # [1, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, 128), 1)
    carry = (jnp.broadcast_to(z, (block, 1)),
             jnp.where(lane == 0, 1.0, 0.0).astype(jnp.float32),
             jnp.zeros((block, v_ref.shape[-1]), jnp.float32))
    last = jnp.minimum(_planes_to_row(qi, block, width), n_planes)
    n_wide = last // wide
    if n_planes >= wide:        # else a wide slice outruns the row's keys
        carry = jax.lax.fori_loop(
            0, n_wide, lambda j, cr: step(cr, j * wide, wide), carry)
    carry = jax.lax.fori_loop(
        n_wide * wide, last, lambda c, cr: step(cr, c, 1), carry)
    _, l, acc = carry
    o_ref[0] = (acc / jnp.sum(l, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _pl():
    from jax.experimental import pallas as pl

    return pl


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _dsa_call(q, k, v, bits, sink, sm_scale, interpret):
    """The kernel over ``[B,H,S,·]`` operands; the trace names its ops
    after this function."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Dv = v.shape[-1]
    width = bits.shape[-1]
    plan = _dsa_plan(S, D, Dv, width, q.dtype)
    block = plan.block
    s_keys, s_rows = plan.n_planes * width, _round_up(S, block)

    def pad(x, rows):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, rows - S), (0, 0)])

    q = pad(q, s_rows).reshape(B * H, s_rows, D)
    kt = pad(k, s_keys).reshape(B * H, s_keys, D).transpose(0, 2, 1)
    v = pad(v, s_keys).reshape(B * H, s_keys, Dv)
    bits = pad(bits, s_rows)               # padded rows choose no key
    tiles = jnp.broadcast_to(sink.astype(jnp.float32)[:, None, None],
                             (H, 8, 128))
    out = pl.pallas_call(
        functools.partial(_dsa_kernel, scale=sm_scale, block=block,
                          width=width, wide=plan.wide,
                          n_planes=plan.n_planes),
        out_shape=jax.ShapeDtypeStruct((B * H, s_rows, Dv), q.dtype),
        grid=(B * H, s_rows // block),
        in_specs=[
            pl.BlockSpec((1, 8, 128), lambda bh, qi: (bh % H, 0, 0)),
            pl.BlockSpec((1, block, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, D, s_keys), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s_keys, Dv), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, block, width), lambda bh, qi: (bh // H, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, Dv), lambda bh, qi: (bh, qi, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_bytes),
        interpret=interpret,
    )(tiles, q, kt, v, bits)
    return out.reshape(B, H, s_rows, Dv)[:, :, :S]


def sparse_attention(q, k, v, bits, sink, *, sm_scale=None,
                     interpret: bool = False):
    """Attention of each query over the keys its bit row chooses, with a
    sink a head.  q, k ``[B,H,S,D]``; v ``[B,H,S,Dv]``; bits ``[B,S,W]``
    int32 (:func:`pack`); sink ``[H]``.  The kernel on a TPU backend
    (``interpret`` runs it in the pallas interpreter), the plain form
    elsewhere."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if not interpret and jax.default_backend() != "tpu":
        return sparse_attention_reference(q, k, v, bits, sink, sm_scale=scale)
    return _dsa_call(q, k, v, bits, sink, scale, interpret)
