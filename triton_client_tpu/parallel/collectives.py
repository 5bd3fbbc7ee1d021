"""Hand-rolled shard_map collectives.

These run INSIDE ``jax.shard_map`` — every array is a per-device local
shard and cross-device communication is explicit (``ppermute`` / ``psum``
over named mesh axes, riding ICI).
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def ring_attention(q, k, v, axis_name: str, causal: bool = True):
    """Causal ring attention over a sequence-parallel mesh axis.

    q, k, v: ``[B, H_local, S_chunk, K]`` local sequence chunks.  K/V
    circulate the ring via ``ppermute`` while a flash-style online softmax
    accumulates partials, so the full sequence never materializes on one
    device — the TPU-native long-context mechanism (ICI ring instead of the
    reference's server-side sequence offload; SURVEY.md §5).
    """
    sp = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, Hl, Sc, Kd = q.shape
    scale = 1.0 / math.sqrt(Kd)
    qpos = me * Sc + jnp.arange(Sc)
    q32 = q.astype(jnp.float32)

    def body(r, carry):
        k_c, v_c, m, l, o = carry
        src = (me - r) % sp  # original owner of the chunk currently held
        s = jnp.einsum("bhqk,bhsk->bhqs", q32, k_c.astype(jnp.float32)) * scale
        if causal:
            kpos = src * Sc + jnp.arange(Sc)
            mask = (qpos[:, None] >= kpos[None, :]).astype(jnp.float32)
            s = jnp.where(mask > 0, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = p * mask
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        o_new = (corr[..., None] * o
                 + jnp.einsum("bhqs,bhsk->bhqk", p, v_c.astype(jnp.float32)))
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        k_n = lax.ppermute(k_c, axis_name, perm)
        v_n = lax.ppermute(v_c, axis_name, perm)
        return k_n, v_n, m_new, l_new, o_new

    m0 = jnp.full((B, Hl, Sc), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hl, Sc), jnp.float32)
    o0 = jnp.zeros((B, Hl, Sc, Kd), jnp.float32)
    # constants entering the loop carry become axis-varying inside the body;
    # mark them so strict shard_map (check_vma=True) accepts the carry types
    m0, l0, o0 = (lax.pcast(x, (axis_name,), to="varying")
                  for x in (m0, l0, o0))
    _, _, _, l, o = lax.fori_loop(0, sp, body, (k, v, m0, l0, o0))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def replicated_axes(spec: P, mesh_axes: Sequence[str]) -> Tuple[str, ...]:
    """Mesh axes over which an array with PartitionSpec ``spec`` is
    replicated (= the axes its gradient must be psum-synced over)."""
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return tuple(a for a in mesh_axes if a not in used)


def sync_replicated_grads(
    grads: Dict[str, jax.Array],
    specs: Dict[str, P],
    mesh_axes: Sequence[str],
) -> Dict[str, jax.Array]:
    """psum each gradient leaf over exactly the axes its parameter is
    replicated on (sharded axes already hold disjoint shards)."""
    out = {}
    for k, g in grads.items():
        axes = replicated_axes(specs[k], mesh_axes)
        out[k] = lax.psum(g, axes) if axes else g
    return out
