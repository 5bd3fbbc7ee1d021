"""What the drawn blocks share: their weights' draw, stacking and int8
store, and the parts of a layer two or more of them run.

A drawn block (``latent_moe``, ``block_diffusion``, ``looped``,
``hybrid_conv``, ``sparse_latent``) holds weights made here in bfloat16 on
the device, leaf by leaf: leaf ``name`` of layer ``l`` is a normal draw from
``fold_in(fold_in(PRNGKey(weights_seed), l), LEAF_KEYS[name])``, a routed
expert's under ``fold_in(that, expert id)``, and the embedding, final norm
and head are the "layer" ``OUTER``.  The bf16 values are the checkpoint: a
reference draws the same numbers from the same keys.  A new block adds its
leaves' indices to ``LEAF_KEYS`` (and its matrices to ``INT8_CONTRACT``)
and keeps its shapes, its norms and its stacks' layout to itself.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

OUTER = 1 << 16  # the "layer" of embedding, final norm and head

#: every matrix of a layer: (leaf, key index).  The index, not the order
#: here, decides a leaf's key, so a new leaf never moves an old one's values.
LEAF_KEYS = {
    # latent attention, the FFNs, the router, the experts, the outer leaves
    "w_qa": 0, "w_qb_nope": 1, "w_qb_rope": 2, "w_kva": 3, "w_kb": 4,
    "w_vb": 5, "w_o": 6, "w_gate": 7, "w_up": 8, "w_down": 9, "router": 10,
    "router_bias": 11, "we_gate": 12, "we_up": 13, "we_down": 14,
    "ws_gate": 15, "ws_up": 16, "ws_down": 17, "embed": 18, "head": 19,
    # grouped-query attention
    "w_q": 20, "w_k": 21, "w_v": 22,
    # the looped block's exit gate
    "exit_gate": 23, "exit_gate_bias": 24,
    # the gated short convolution
    "w_in": 25, "conv_w": 26, "w_out": 27,
    # the attention's gate and sinks, the indexer, the four streams' mixing,
    # the multi-token-prediction module's input
    "w_g": 28, "sink": 29, "w_qi": 30, "w_ki": 31, "w_wi": 32,
    "hc_phi": 33, "hc_alpha": 34, "hc_bias": 35, "eh_proj": 36,
}

#: the matrices ``TRITON_TPU_QUANT=int8`` stores as int8, with the axes each
#: contracts over: one scale an output channel
INT8_CONTRACT = {
    "w_qa": (0,), "w_qb_nope": (0,), "w_qb_rope": (0,), "w_kva": (0,),
    "w_kb": (0,), "w_vb": (0,), "w_o": (0, 1),
    "w_q": (0,), "w_k": (0,), "w_v": (0,),
    "w_in": (0,), "w_out": (0,),
    "w_g": (0,), "w_qi": (0,), "w_ki": (0,), "eh_proj": (0,),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "we_gate": (1,), "we_up": (1,), "we_down": (1,),
    "ws_gate": (0,), "ws_up": (0,), "ws_down": (0,),
}

#: a routed expert's leaves: drawn an expert at a time, under its id
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def draw(key, shape, scale):
    """An f32 normal draw times ``scale``, rounded to bfloat16 once."""
    return (jax.random.normal(key, shape, jnp.float32)
            * scale).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def draw_experts(key, ids, shape, scale):
    """An expert's weights follow its id, whichever chip holds it."""
    return jax.vmap(lambda e: draw(jax.random.fold_in(key, e), shape,
                                   scale))(ids)


def draw_layer(seed: int, layer: int, shapes: Dict[str, tuple],
               norms: Dict[str, int], expert_ids=None,
               f32_leaves=()) -> Dict[str, jax.Array]:
    """One layer's leaves in bfloat16, drawn on the default device leaf by
    leaf: ``shapes`` is ``{leaf: (shape, scale of the normal draw)}`` (an
    expert leaf's shape is one expert's, drawn for each of ``expert_ids``),
    ``norms`` ``{leaf: width}`` of ones; the leaves named in ``f32_leaves``
    are bfloat16 values upcast to f32."""
    root = jax.random.fold_in(jax.random.PRNGKey(seed), layer)
    out = {name: jnp.ones((n,), jnp.bfloat16) for name, n in norms.items()}
    for name, (shape, scale) in shapes.items():
        key = jax.random.fold_in(root, LEAF_KEYS[name])
        if name in EXPERT_LEAVES:
            out[name] = draw_experts(key, expert_ids, shape, scale)
        elif name in f32_leaves:
            out[name] = draw(key, shape, scale).astype(jnp.float32)
        else:
            out[name] = draw(key, shape, scale)
    return out


def outer_params(cfg, tied: bool = False, **extra) -> Dict[str, jax.Array]:
    """``{"embed" [V,D], "final_ln" [D], "head" [D,V]}`` (no head where it
    is ``tied`` to the embedding), and a leaf of each ``extra`` shape, drawn
    from the "layer" ``OUTER`` at scale 0.02."""
    outer = jax.random.fold_in(jax.random.PRNGKey(cfg.weights_seed), OUTER)
    V, D = cfg.vocab_size, cfg.hidden_size
    shapes = dict({"embed": (V, D)}, **({} if tied else {"head": (D, V)}),
                  **extra)
    out = {name: draw(jax.random.fold_in(outer, LEAF_KEYS[name]), shape, 0.02)
           for name, shape in shapes.items()}
    out["final_ln"] = jnp.ones((D,), jnp.bfloat16)
    return out


@functools.partial(jax.jit, donate_argnums=0)
def put(stack, leaf, at):
    """``stack[at : at + len(leaf)] = leaf``, in place."""
    return lax.dynamic_update_slice_in_dim(stack, leaf, at, 0)


def stack(stacks: Dict[str, jax.Array], layer: Dict[str, jax.Array], i: int,
          count: int, flat: bool = False) -> None:
    """Layer ``i`` of ``count`` into ``stacks``, in place: each leaf at
    ``stacks[name][i]`` of ``[count, ...]``, or, ``flat`` (a layer's experts
    ``[E, ...]``, every layer's one stack), at rows ``i*E ..`` of ``[count*E,
    ...]``.  A stack is zeros until its layers are written, so a layer can be
    drawn, written and let go before the next one exists: the stacks are
    never held twice."""
    for name, leaf in layer.items():
        if not flat:
            leaf = leaf[None]
        if name not in stacks:
            stacks[name] = jnp.zeros((count * leaf.shape[0],) + leaf.shape[1:],
                                     leaf.dtype)
        stacks[name] = put(stacks[name], leaf, i * leaf.shape[0])


def quantize_weights(layer: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Weight-only int8 storage of a layer's matrices (``INT8_CONTRACT``;
    symmetric, one f32 scale an output channel, as
    ``transformer.quantize_layer_weights``); norms, router and bias stay as
    drawn."""
    out = dict(layer)
    for name, axes in INT8_CONTRACT.items():
        if name not in layer:
            continue
        m = layer[name].astype(jnp.float32)
        amax = jnp.max(jnp.abs(m), axis=axes, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        out[name] = jnp.clip(jnp.round(m / scale), -127, 127).astype(jnp.int8)
        out[name + "_scale"] = scale
    return out


def w(blk, name):
    """A matrix as it is held (bfloat16 when serving), dequantised on the
    fly where it is stored as int8 (``decode._w``'s form)."""
    leaf = blk[name]
    scale = blk.get(name + "_scale")
    if scale is None:
        return leaf
    return leaf.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Parts of a layer
# ---------------------------------------------------------------------------

@jax.named_scope("norm")
def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * r).astype(x.dtype) * scale.astype(x.dtype)


def rotary(dim: int, theta: float, positions):
    """``(cos, sin)`` as ``[len(positions), dim / 2]`` f32: the default
    rotary at ``theta`` over ``dim`` values."""
    half = dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """Half-split pairs, the zoo's layout: ``x[..., :h]`` with ``x[..., h:]``;
    ``cos``/``sin`` broadcast against ``x[..., :h]``."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def gated(g, u, limit: Optional[float]):
    """``silu(g) * u`` in f32; with a ``limit``, ``silu(min(g, limit)) *
    clip(u, -limit, limit)``."""
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jax.nn.silu(g) * u


def swiglu(h, gate, up, down, limit: Optional[float] = None):
    g = jnp.dot(h, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(h, up, preferred_element_type=jnp.float32)
    a = gated(g, u, limit).astype(h.dtype)
    return jnp.dot(a, down, preferred_element_type=jnp.float32)


def embed(params: Dict[str, Any], tokens, cfg):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"],
                        jnp.clip(tokens, 0, cfg.vocab_size - 1), axis=0)


def head(params: Dict[str, Any], x, cfg):
    """The final norm and the untied head: ``x [..., D]`` -> logits
    ``[..., V]`` f32."""
    with jax.named_scope("head"):
        h = rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
        return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def touched(rows):
    """``rows [b,L,E]`` -> experts with at least one row, counted over the
    batch's rows ``0 .. r`` for every ``r`` ``[b]`` and summed over the
    layers: the host takes the entry of its last row that is not padding."""
    return jnp.sum(jnp.cumsum(rows, axis=0) > 0, axis=(1, 2),
                   dtype=jnp.int32)
