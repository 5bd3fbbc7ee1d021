"""The residual streams' mixing around a sublayer, one pass over them each
side.

``models/sparse_latent.py`` keeps ``n`` f32 residual streams a token and mixes
them around every sublayer F (the mHC form).  Here the streams travel as
``X [T, n·D]``: a token's row holds stream 0's ``D`` values, then stream 1's,
and so on, so a stream is a lane-aligned slice of the row and a tile of
tokens is one contiguous run of HBM.

* :func:`hc_pre` reads a tile of ``X`` once: ``x = X / rms(X)`` over the
  token's ``n·D`` values, rounded to ``Phi``'s dtype; ``a = x Phi`` on the MXU
  (``Phi`` stays in VMEM, its block never moves); ``H_pre = sigmoid(alpha_0
  a_pre + b_pre)``; ``u = sum_i H_pre_i X_i`` (streams in order) and ``h =
  RMSNorm(u)`` in the sublayer's dtype.  It writes ``h`` and ``a``.
* :func:`hc_post` reads a tile of ``X`` and of ``y = F(h)`` once and writes
  ``X_i <- sum_j H_res_ij X_j + H_post_i y`` over ``X`` itself
  (``input_output_aliases``).

``H_post`` and the Sinkhorn of ``H_res`` are formed between the two from
``a``, ``n (n + 2)`` numbers a token (``sparse_latent``).  A kernel walks its
tile a chunk of lanes at a time.  Off TPU the model runs its plain form (tests
pass ``interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: VMEM the pipelined blocks of one kernel may take
_BLOCK_BYTES = 24 << 20
_LANES = 128


def _chunk(d: int) -> int:
    """Lanes of a stream handled at a time: 512 where they divide ``d``."""
    if d % _LANES:
        raise ValueError(f"a stream of {d} values is not lane-aligned")
    return next(c for c in (512, 256, _LANES) if d % c == 0)


def _rows(tokens: int, per_token: int, fixed: int = 0) -> int:
    """Tokens a tile: the largest power of two, from 16 (a bf16 tile's rows)
    up to 512, that divides ``tokens`` and whose buffers, ``per_token`` bytes
    a token beside ``fixed``, fit ``_BLOCK_BYTES``."""
    if tokens % 16:
        raise ValueError(f"{tokens} tokens are not a multiple of 16")
    rows = 16
    while (rows < 512 and tokens % (2 * rows) == 0
           and fixed + 2 * rows * per_token <= _BLOCK_BYTES):
        rows *= 2
    return rows


def _hc_pre_kernel(x_ref, phi_ref, ab_ref, ln_ref, h_ref, a_ref, u_ref, *, n,
                   d, chunk, hc_eps, eps):
    """One tile of tokens: four walks over its lanes (the streams' sum of
    squares; ``a``; ``u`` and its sum of squares; ``h``)."""
    pl = _pl()
    rows = x_ref.shape[0]
    steps = d // chunk
    f32 = jnp.float32

    def lanes(c, i=0):
        return pl.ds(pl.multiple_of(i * d + c * chunk, chunk), chunk)

    def squares(c, acc):
        for i in range(n):
            v = x_ref[:, lanes(c, i)]
            acc = acc + v * v
        return acc

    zero = jnp.zeros((rows, chunk), f32)
    ss = lax.fori_loop(0, steps, squares, zero)
    r = lax.rsqrt(jnp.sum(ss, axis=-1, keepdims=True) / (n * d) + hc_eps)

    def project(c, a):
        for i in range(n):
            x = (x_ref[:, lanes(c, i)] * r).astype(phi_ref.dtype)
            a = a + jnp.dot(x, phi_ref[lanes(c, i), :],
                            preferred_element_type=f32)
        return a

    a = lax.fori_loop(0, steps, project,
                      jnp.zeros((rows, phi_ref.shape[1]), f32))
    a_ref[...] = a
    ab = ab_ref[...]
    pre = jax.nn.sigmoid(ab[0:1] * a[:, :n] + ab[1:2])          # [rows, n]

    def mix(c, acc):
        u = sum(pre[:, i:i + 1] * x_ref[:, lanes(c, i)] for i in range(n))
        u_ref[:, lanes(c)] = u
        return acc + u * u

    ss = lax.fori_loop(0, steps, mix, zero)
    ru = lax.rsqrt(jnp.sum(ss, axis=-1, keepdims=True) / d + eps)

    def norm(c, carry):
        h = u_ref[:, lanes(c)] * ru
        h_ref[:, lanes(c)] = (h * ln_ref[:, lanes(c)].astype(f32)
                              ).astype(h_ref.dtype)
        return carry

    lax.fori_loop(0, steps, norm, None)


def _hc_post_kernel(x_ref, y_ref, c_ref, o_ref, *, n, d, chunk):
    """One tile of tokens: ``X_i <- sum_j H_res_ij X_j + H_post_i y``, a
    chunk of lanes of every stream at a time."""
    pl = _pl()
    coef = c_ref[...]                       # [rows, n + n·n]: post, then res
    post = [coef[:, i:i + 1] for i in range(n)]
    res = [[coef[:, n + i * n + j:n + i * n + j + 1] for j in range(n)]
           for i in range(n)]

    def lanes(c, i=0):
        return pl.ds(pl.multiple_of(i * d + c * chunk, chunk), chunk)

    def step(c, carry):
        y = y_ref[:, lanes(c)]
        xs = [x_ref[:, lanes(c, j)] for j in range(n)]
        for i in range(n):
            o_ref[:, lanes(c, i)] = (
                sum(res[i][j] * xs[j] for j in range(n)) + post[i] * y)
        return carry

    lax.fori_loop(0, d // chunk, step, None)


def _pl():
    from jax.experimental import pallas as pl

    return pl


def _vmem(buffers: int) -> int:
    """The scoped VMEM a kernel asks for: its buffers and room for the
    values of a chunk."""
    return buffers + (8 << 20)


@functools.partial(jax.jit, static_argnames=("n", "hc_eps", "eps", "dt",
                                             "interpret"))
def _hc_pre_call(X, phi, ab, ln, n, hc_eps, eps, dt, interpret):
    """The ``hc.pre`` kernel over ``X [T, n·D]``; the trace names its op
    after this function."""
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu

    T, width = X.shape
    d = width // n
    k = phi.shape[1]
    chunk = _chunk(d)
    dt = jnp.dtype(dt)
    # Phi held once, its k columns padded to a vreg's lanes; ``ln``
    fixed = width * _LANES * phi.dtype.itemsize + 2 * 16 * d * 2
    # X in, h and a out, two buffers each; u's scratch
    per_token = 2 * (4 * width + dt.itemsize * d + 4 * _LANES) + 4 * d
    rows = _rows(T, per_token, fixed)
    return pl.pallas_call(
        functools.partial(_hc_pre_kernel, n=n, d=d, chunk=chunk,
                          hc_eps=hc_eps, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((T, d), dt),
                   jax.ShapeDtypeStruct((T, k), jnp.float32)),
        grid=(T // rows,),
        in_specs=[
            pl.BlockSpec((rows, width), lambda t: (t, 0)),
            pl.BlockSpec((width, k), lambda t: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((2, n), lambda t: (0, 0)),
            pl.BlockSpec((1, d), lambda t: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((rows, d), lambda t: (t, 0)),
                   pl.BlockSpec((rows, k), lambda t: (t, 0))),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem(fixed + rows * per_token)),
        interpret=interpret,
    )(X, phi, ab, ln.reshape(1, d))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _hc_post_call(X, y, coef, n, interpret):
    """The ``hc.post`` kernel: ``X [T, n·D]`` rewritten in place; the trace
    names its op after this function."""
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu

    T, width = X.shape
    d = width // n
    chunk = _chunk(d)
    # X in and out, y and the coefficients in, two buffers each
    per_token = 2 * 4 * (2 * width + d + _LANES)
    rows = _rows(T, per_token)
    return pl.pallas_call(
        functools.partial(_hc_post_kernel, n=n, d=d, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((T, width), X.dtype),
        grid=(T // rows,),
        in_specs=[
            pl.BlockSpec((rows, width), lambda t: (t, 0)),
            pl.BlockSpec((rows, d), lambda t: (t, 0)),
            pl.BlockSpec((rows, coef.shape[1]), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((rows, width), lambda t: (t, 0)),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem(rows * per_token)),
        interpret=interpret,
    )(X, y, coef)


def hc_pre(X, phi, alpha, bias, ln, *, n: int, hc_eps: float, eps: float,
           dt, interpret: bool = False):
    """``X [T, n·D]`` f32, ``phi [n·D, n (n + 2)]``, ``alpha [3]``, ``bias
    [n (n + 2)]``, ``ln [D]`` -> ``(h [T, D] in dt, a [T, n (n + 2)] f32)``:
    the sublayer's normed input and every token's ``x Phi``."""
    ab = jnp.stack([jnp.broadcast_to(alpha[0], (n,)), bias[:n]]
                   ).astype(jnp.float32)
    return _hc_pre_call(X, phi, ab, ln, n, hc_eps, eps, jnp.dtype(dt).name,
                        interpret)


def hc_post(X, y, post, res, *, interpret: bool = False):
    """``X [T, n·D]`` f32, ``y [T, D]`` f32, ``post [T, n]``, ``res [T, n,
    n]`` -> ``X`` mixed and ``y`` added, written over ``X``."""
    T, n = post.shape
    coef = jnp.concatenate([post, res.reshape(T, n * n)], axis=1)
    return _hc_post_call(X, y, coef, n, interpret)
