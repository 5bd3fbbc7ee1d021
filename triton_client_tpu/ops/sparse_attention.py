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
head's keys and values in VMEM and walks the chunks of keys up to its
block's last row, every chunk masked.  The online softmax starts from the
sink (``m = z_h``, ``l = 1``), so the sink is in the denominator and a row
whose first chunks hold no chosen key adds exact zeros.  It does the causal
half of the dense work, whatever the selection: a masked form.  Off TPU the
plain form below runs (tests pass ``interpret``).
"""

from __future__ import annotations

import functools
import math

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


def _dsa_kernel(sink_ref, q_ref, kt_ref, v_ref, bits_ref, o_ref, *, scale,
                block, width, n_chunks):
    """One (batch·head, query block) program.  ``kt_ref`` holds the head's
    keys turned (``[D, S]``), ``v_ref`` its values, ``bits_ref`` the block's
    rows of bit planes ``[block, width]``, ``sink_ref`` a tile filled with
    the head's sink."""
    pl = _pl()
    qi = pl.program_id(1)
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    bits = bits_ref[0]

    def step(c, carry):
        m, l, acc = carry
        start = pl.multiple_of(c * width, width)
        s = jnp.dot(q, kt_ref[0, :, pl.ds(start, width)],
                    preferred_element_type=jnp.float32)   # [block, width]
        chosen = jnp.bitwise_and(jnp.right_shift(bits, c), 1) != 0
        s = jnp.where(chosen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        v_blk = v_ref[0, pl.ds(start, width), :]
        acc = corr * acc + jnp.dot(p.astype(v_blk.dtype), v_blk,
                                   preferred_element_type=jnp.float32)
        return m_new, corr * l + jnp.sum(p, axis=-1, keepdims=True), acc

    z = jnp.max(sink_ref[0], axis=1, keepdims=True)[:1]     # [1, 1]
    carry = (jnp.broadcast_to(z, (block, 1)),
             jnp.ones((block, 1), jnp.float32),
             jnp.zeros((block, v_ref.shape[-1]), jnp.float32))
    # the chunks that hold a key at or before the block's last row
    last = jnp.minimum(((qi + 1) * block + width - 1) // width, n_chunks)
    _, l, acc = jax.lax.fori_loop(0, last, step, carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


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
    block = 512 if S >= 1024 else 128
    n_chunks = -(-S // width)
    s_keys, s_rows = n_chunks * width, _round_up(S, block)

    def pad(x, rows):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, rows - S), (0, 0)])

    q = pad(q, s_rows).reshape(B * H, s_rows, D)
    kt = pad(k, s_keys).reshape(B * H, s_keys, D).transpose(0, 2, 1)
    v = pad(v, s_keys).reshape(B * H, s_keys, Dv)
    bits = pad(bits, s_rows)               # padded rows choose no key
    tiles = jnp.broadcast_to(sink.astype(jnp.float32)[:, None, None],
                             (H, 8, 128))
    itemsize = q.dtype.itemsize
    vmem = (2 * itemsize * (D * s_keys + s_keys * _round_up(Dv, 128)
                            + block * (_round_up(D, 128) + _round_up(Dv, 128)))
            + 2 * 4 * block * width + 6 * 4 * block * max(width, Dv)
            + (4 << 20))
    out = pl.pallas_call(
        functools.partial(_dsa_kernel, scale=sm_scale, block=block,
                          width=width, n_chunks=n_chunks),
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
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
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
