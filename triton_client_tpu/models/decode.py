"""KV-cache incremental decoding for the flagship transformer stack.

The generation ensemble (BASELINE row 5) re-runs the full 128-token window
for every produced token — O(S·cost) per token. This module adds the
TPU-native decode path: **prefill** runs the window once and records every
layer's rotated K/V into a device-resident cache; each **decode step** then
processes exactly one new token against the cache — O(cost) per token, with
8 bytes of H2D per step.

Semantics: positions are absolute and the context GROWS (true KV
continuation) rather than sliding, so step t equals a full forward over the
whole accumulated sequence (proven by ``tests/test_decode.py``); the
window-recompute path instead re-bases positions every step. The first
generated token is bit-identical between the two.

Sharded serving: the decode math is written single-device and partitioned
by **GSPMD** — params and the KV cache are committed to ``NamedSharding``s
over the serve mesh (``TRITON_TPU_SERVE_MESH``: tensor parallel over heads,
data parallel over slots) and XLA inserts the collectives under ``jit``.
No hand-rolled psums here; the explicitly-collective training/forward path
stays in ``transformer.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import transformer as tr
from .parts import rmsnorm



# shared with the encoder serving path (transformer.py owns it now: the
# decode stack dequantizes on the fly via _w, the encoder forward runs the
# int8 MXU path on the same quantized params)
quantize_layer_weights = tr.quantize_layer_weights


def _stale_error(model_name: str):
    from ..server.types import InferError

    return InferError(
        f"model '{model_name}': generation slot was reclaimed before it "
        "executed")


def decode_mesh(cfg: tr.TransformerConfig, n_slots: int = 1,
                model_name=None, slots_desc=None):
    """Serve mesh for the decode stack, from ``TRITON_TPU_SERVE_MESH``.

    Decode shards over **tp** (attention heads / FFN hidden) and **dp**
    (cache slots, batched mode); the pipeline/expert/sequence axes don't
    apply to a single-token step, so greedy specs ("all", an integer) put
    their devices on tp then dp, and explicit shape specs must keep
    pp=ep=sp=1.  Returns a full 5-axis mesh (trivial extra axes) so
    ``tr.param_specs`` placements apply unchanged."""
    from .. import parallel

    spec, var = tr.resolve_serve_spec(model_name)
    spec = spec.strip().lower()
    devices = jax.devices()
    explicit = tr.parse_serve_shape(spec, var)
    if explicit is not None:
        bad = [a for a in ("pp", "ep", "sp") if explicit[a] > 1]
        if bad:
            raise ValueError(
                f"{var}={spec!r}: decode serving shards "
                f"over tp/dp only; {','.join(bad)} must be 1")
        # config-time divisibility so a bad spec is a readable error, not
        # a jax.device_put crash at the first request
        if explicit["tp"] > 1 and cfg.n_heads % explicit["tp"] != 0:
            raise ValueError(
                f"{var}={spec!r}: tp={explicit['tp']} "
                f"must divide n_heads={cfg.n_heads}")
        if explicit["dp"] > 1 and n_slots % explicit["dp"] != 0:
            raise ValueError(
                f"{var}={spec!r}: dp={explicit['dp']} must divide "
                + (slots_desc or f"the {n_slots} decode slots "
                                 "(TRITON_TPU_DECODE_SLOTS)"))
        n = math.prod(explicit.values())
        if n > len(devices):
            raise ValueError(
                f"{var}={spec!r} needs {n} devices, "
                f"have {len(devices)}")
        return parallel.build_mesh(explicit, tr.MESH_AXES, devices[:n])
    n = tr.resolve_serve_count(spec, len(devices), var)
    # largest power-of-two head split, then slots onto dp
    tp = 1
    while tp * 2 <= n and cfg.n_heads % (tp * 2) == 0:
        tp *= 2
    dp = 1
    while dp * 2 <= n // tp and n_slots % (dp * 2) == 0:
        dp *= 2
    shape = {a: 1 for a in tr.MESH_AXES}
    shape["tp"], shape["dp"] = tp, dp
    return parallel.build_mesh(shape, tr.MESH_AXES, devices[:tp * dp])


def place_decode_params(params, mesh, cfg: tr.TransformerConfig):
    """Commit decode weights to the serve mesh: standard leaves follow
    ``tr.param_specs`` (tp over heads / FFN hidden; pp trivially 1 here),
    int8 ``*_scale`` siblings replicate (tiny, and their singleton reduced
    dims can't shard)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    specs = tr.param_specs(cfg)
    return {k: jax.device_put(v, NamedSharding(mesh, specs.get(k, P())))
            for k, v in params.items()}


def _layer_blocks(params, cfg: tr.TransformerConfig):
    """Stacked per-layer leaves for the scan, including any int8
    ``*_scale`` siblings produced by quantize_layer_weights."""
    out = {}
    for k in tr._layer_keys(cfg):
        out[k] = params[k]
        if k + "_scale" in params:
            out[k + "_scale"] = params[k + "_scale"]
    return out


def _w(blk, name, dtype):
    """Weight leaf, dequantized on the fly when a ``<name>_scale`` sibling
    is present (weight-only int8: HBM reads stay int8; the convert+scale is
    a cheap elementwise producer fused into the consuming matmul, applied
    per layer inside the scan so no dequantized stack ever materializes)."""
    w = blk[name].astype(dtype)
    s = blk.get(name + "_scale")
    return w * s.astype(dtype) if s is not None else w


def _project_qkv(blk, x, cfg: tr.TransformerConfig):
    h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bhsk", h, _w(blk, "wq", h.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", h, _w(blk, "wk", h.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", h, _w(blk, "wv", h.dtype))
    return q, k, v


def _ffn(blk, x, cfg: tr.TransformerConfig):
    """FFN for the decode stack: ``tr._ffn_apply``'s math minus the mesh
    psums (single shard; GSPMD re-inserts collectives when the serve mesh
    shards the hidden/expert dims). Dense SiLU or routed MoE top-k."""
    h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
    if cfg.moe:
        gate = jnp.einsum("bsd,de->bse", h.astype(jnp.float32),
                          _w(blk, "router", jnp.float32))
        top, _ = lax.top_k(gate, cfg.moe_top_k)
        thresh = top[..., -1:]
        probs = jax.nn.softmax(
            jnp.where(gate >= thresh, gate, -1e30), axis=-1)
        if h.shape[0] == 1 and h.shape[1] == 1:
            # single-token decode step: gather the ROUTED experts before
            # dequant/compute, so HBM weight reads scale with top_k, not
            # n_experts (decode is weight-bandwidth-bound; the dense path
            # below would pull every expert's stack each step)
            _, idx = lax.top_k(gate[0, 0], cfg.moe_top_k)      # [k]

            def take_w(name):
                w = jnp.take(blk[name], idx, axis=0)
                s = blk.get(name + "_scale")
                if s is not None:
                    return (w.astype(h.dtype)
                            * jnp.take(s, idx, axis=0).astype(h.dtype))
                return w.astype(h.dtype)

            he = jnp.einsum("bsd,edf->ebsf", h, take_w("we1"))
            he = jax.nn.silu(he)
            oe = jnp.einsum("ebsf,efd->ebsd", he, take_w("we2"))
            p_sel = jnp.take(probs[0, 0], idx)[None, None, :]   # [1,1,k]
            out = jnp.einsum("ebsd,bse->bsd", oe, p_sel.astype(oe.dtype))
        else:
            he = jnp.einsum("bsd,edf->ebsf", h, _w(blk, "we1", h.dtype))
            he = jax.nn.silu(he)
            oe = jnp.einsum("ebsf,efd->ebsd", he, _w(blk, "we2", h.dtype))
            out = jnp.einsum("ebsd,bse->bsd", oe, probs.astype(oe.dtype))
    else:
        he = jnp.einsum("bsd,df->bsf", h, _w(blk, "w1", h.dtype))
        he = jax.nn.silu(he)
        out = jnp.einsum("bsf,fd->bsd", he, _w(blk, "w2", h.dtype))
    return x + out


def _attn_out(blk, x, o):
    out = jnp.einsum("bhsk,hkd->bsd", o, _w(blk, "wo", o.dtype))
    return x + out


def _prefill_layer(blk, x, cfg: tr.TransformerConfig):
    """Full causal attention over the prompt; returns rotated K/V."""
    S = x.shape[1]
    q, k, v = _project_qkv(blk, x, cfg)
    positions = jnp.arange(S)
    q, k = tr._rope(q, k, positions, cfg.rope_theta)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = positions[:, None] >= positions[None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bhsk->bhqk", p, v.astype(jnp.float32)).astype(x.dtype)
    x = _attn_out(blk, x, o)
    return _ffn(blk, x, cfg), k, v


def _decode_layer(blk, x, kc, vc, pos, cfg: tr.TransformerConfig):
    """One token at absolute position ``pos`` against the cache.

    x: [B, 1, D]; kc/vc: [B, H, S_max, K]."""
    q, k, v = _project_qkv(blk, x, cfg)
    positions = pos[None] if pos.ndim == 0 else pos
    q, k = tr._rope(q, k, positions, cfg.rope_theta)
    kc = lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos, axis=2)
    vc = lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos, axis=2)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    valid = jnp.arange(kc.shape[2]) <= pos
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bhsk->bhqk", p, vc.astype(jnp.float32)).astype(x.dtype)
    x = _attn_out(blk, x, o)
    return _ffn(blk, x, cfg), kc, vc


def _head(params, x, cfg: tr.TransformerConfig):
    h = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                      params["head"].astype(jnp.float32))


def make_prefill(cfg: tr.TransformerConfig, s_max: int):
    """jitted (params, tokens [B,S]) -> (last-position logits [B,V], cache)."""

    @jax.jit
    def prefill(params, tokens):
        B, S = tokens.shape
        x = jnp.take(params["embed"].astype(cfg.dtype), tokens, axis=0)
        blocks = _layer_blocks(params, cfg)

        def layer(x, blk):
            x, k, v = _prefill_layer(blk, x, cfg)
            return x, (k, v)

        x, (ks, vs) = lax.scan(layer, x, blocks)
        pad = s_max - S
        cache = {
            "k": jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
            "v": jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))),
            "pos": jnp.asarray(S, jnp.int32),
        }
        return _head(params, x, cfg)[:, -1], cache

    return prefill


def make_decode_step(cfg: tr.TransformerConfig):
    """jitted (params, cache, tokens [B,1]) -> (logits [B,V], cache')."""

    @jax.jit
    def step(params, cache, tokens):
        x = jnp.take(params["embed"].astype(cfg.dtype), tokens, axis=0)
        blocks = _layer_blocks(params, cfg)
        pos = cache["pos"]

        def layer(x, xs):
            blk, kc, vc = xs
            x, kc, vc = _decode_layer(blk, x, kc, vc, pos, cfg)
            return x, (kc, vc)

        x, (ks, vs) = lax.scan(layer, x, (blocks, cache["k"], cache["v"]))
        new_cache = {"k": ks, "v": vs, "pos": pos + 1}
        return _head(params, x, cfg)[:, -1], new_cache

    return step


# ---------------------------------------------------------------------------
# Slot-batched continuous decoding: one preallocated cache of N slots, every
# concurrent sequence's next-token step merged into ONE batched device step
# (and one fused readback) per tick — the aggregate-throughput path.
# ---------------------------------------------------------------------------


def _rope_at(x, pos, theta):
    """RoPE for single-position queries/keys with PER-SLOT positions.

    x: [B, H, 1, K]; pos: [B] int32 (each slot at its own absolute
    position). Mirrors tr._rope's rotate-halves layout exactly."""
    Kd = x.shape[-1]
    half = Kd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]      # [B, half]
    cos = jnp.cos(ang)[:, None, None, :]                          # [B,1,1,half]
    sin = jnp.sin(ang)[:, None, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def parse_cache_buckets(spec, n_slots: int, s_max: int, prompt_len: int):
    """Slab-size buckets for the batched slot cache.

    ``TRITON_TPU_DECODE_BUCKETS="48x640,16x1280"`` = 48 slots capped at 640
    tokens each plus 16 at 1280.  Capacity scaling the TPU-native way: where
    CUDA serving stacks reach for block-table paging (dynamic gathers XLA
    can't tile well without a custom kernel), a small static set of slab
    sizes keeps every shape compile-time constant — short generations stop
    paying a full-length HBM slab, so the same cache budget holds several
    times more concurrent generations, and the per-tick attention over a
    small bucket reads proportionally fewer bytes.

    Unset → one bucket ``[(n_slots, s_max)]``: exactly the previous fixed
    layout.  Returns ``[(count, cap), ...]`` ascending by cap; every cap
    must exceed the prefill window (a slab must at least hold the prompt
    plus one generated token).

    REPEATED caps are kept as SEPARATE pools (``"64x160,64x160"`` = two
    independent 64-slot buckets): each bucket is its own static-shape
    device step, and a tick only steps buckets holding active work — so
    splitting a large same-size pool bounds the per-tick batch width and
    cache read at the pool size.  One 256-wide bucket pays a 256-wide
    step (and reads the whole 256-slab cache) even with 64 live slots;
    4×64 at the same capacity ticks one bucket.  Allocation fills pools
    in spec order, keeping live slots packed in the fewest buckets
    (measured: benchmarks/GEN_CAPACITY.json).
    """
    if not spec:
        return [(n_slots, s_max)]
    out = []
    for part in spec.split(","):
        try:
            cnt_s, cap_s = part.strip().lower().split("x")
            cnt, cap = int(cnt_s), int(cap_s)
        except ValueError:
            raise ValueError(
                f"TRITON_TPU_DECODE_BUCKETS part {part.strip()!r}: expected "
                "<count>x<tokens> (e.g. '48x640')")
        if cnt <= 0:
            raise ValueError(
                f"TRITON_TPU_DECODE_BUCKETS: count must be positive in "
                f"{part.strip()!r}")
        if cap <= prompt_len:
            raise ValueError(
                f"TRITON_TPU_DECODE_BUCKETS: cap {cap} must exceed the "
                f"{prompt_len}-token prefill window (prompt + >=1 token)")
        out.append((cnt, cap))
    out.sort(key=lambda t: t[1])  # stable: same-cap pools keep spec order
    return out


def kv_quant_enabled() -> bool:
    """``TRITON_TPU_KV_QUANT=int8`` stores the shared slot cache as int8
    with per-(head, position) vector scales — cache HBM roughly halves, so
    the same budget holds ~2x decode slots/longer slabs.  Unknown values
    fail loudly (same convention as TRITON_TPU_QUANT)."""
    import os

    v = os.environ.get("TRITON_TPU_KV_QUANT", "")
    if v in ("", "none"):
        return False
    if v == "int8":
        return True
    raise ValueError(
        f"TRITON_TPU_KV_QUANT={v!r}: expected 'int8' or unset")


def _kv_quantize(x):
    """[..., K] f-point -> (int8 [..., K], f32 scale [...]): symmetric
    per-vector absmax over the head dim."""
    a = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(a > 0, a / 127.0, 1.0)
    q = jnp.round(
        x.astype(jnp.float32) / scale[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _cache_read_f32(c):
    """Cache leaf -> f32 values.  ``c`` is either a plain array (bf16
    cache) or the int8 dict {"q": int8 [..., S, K], "s": f32 [..., S]};
    the structure is static under jit, so this branch traces away.  The
    dequant is a cheap elementwise producer XLA fuses into the consuming
    attention einsum — HBM reads stay int8."""
    if isinstance(c, dict):
        return c["q"].astype(jnp.float32) * c["s"][..., None]
    return c.astype(jnp.float32)


def _cache_row_write(cache_row, new_row, p, a):
    """Write ``new_row`` [H, 1, K] at position ``p`` of one slot's cache
    row [H, S, K] (plain or int8-dict), keeping the current entry when the
    slot is inactive."""
    if isinstance(cache_row, dict):
        q_new, s_new = _kv_quantize(new_row)
        cur_q = lax.dynamic_slice(
            cache_row["q"], (0, p, 0),
            (cache_row["q"].shape[0], 1, cache_row["q"].shape[2]))
        cur_s = lax.dynamic_slice(
            cache_row["s"], (0, p), (cache_row["s"].shape[0], 1))
        return {
            "q": lax.dynamic_update_slice(
                cache_row["q"], jnp.where(a, q_new, cur_q), (0, p, 0)),
            "s": lax.dynamic_update_slice(
                cache_row["s"], jnp.where(a, s_new, cur_s), (0, p)),
        }
    cur = lax.dynamic_slice(
        cache_row, (0, p, 0), (cache_row.shape[0], 1, cache_row.shape[2]))
    val = jnp.where(a, new_row.astype(cache_row.dtype), cur)
    return lax.dynamic_update_slice(cache_row, val, (0, p, 0))


def _cache_block_write(cache, values, idx4, idx5):
    """Write a [L, 1, H, S', K] block of values into the cache at the
    5-dim index (full-slot or chunked prefill)."""
    if isinstance(cache, dict):
        q, s = _kv_quantize(values)
        return {
            "q": lax.dynamic_update_slice(cache["q"], q, idx5),
            "s": lax.dynamic_update_slice(cache["s"], s, idx4),
        }
    return lax.dynamic_update_slice(cache, values.astype(cache.dtype), idx5)


def _cache_slot_slice(cache, slot):
    """One slot's [1, H, S, K]-shaped view of a [B, H, S, K] cache."""
    if isinstance(cache, dict):
        return {
            "q": lax.dynamic_slice(cache["q"], (slot, 0, 0, 0),
                                   (1,) + cache["q"].shape[1:]),
            "s": lax.dynamic_slice(cache["s"], (slot, 0, 0),
                                   (1,) + cache["s"].shape[1:]),
        }
    return lax.dynamic_slice(cache, (slot, 0, 0, 0), (1,) + cache.shape[1:])


def _cache_seq_len(c) -> int:
    return (c["q"] if isinstance(c, dict) else c).shape[-2]


def _greedy_head(logits):
    """Greedy head shared by the slot kernels: f32 cast, argmax token, max
    logit, and the token's log-probability under the raw-logit softmax
    (one definition so step/prefill/chunk can never drift apart)."""
    l32 = logits.astype(jnp.float32)
    nxt = jnp.argmax(l32, axis=-1).astype(jnp.int32)
    best = jnp.max(l32, axis=-1).astype(jnp.float32)
    lp = best - jax.nn.logsumexp(l32, axis=-1)
    return nxt, best, lp


def _pen_head(logits, counts, fp, pp):
    """Penalized greedy head: the token is argmax of the penalized logits
    (OpenAI frequency/presence semantics — ``fp*count + pp*(count>0)``
    subtracted per token), while ``best``/``lp`` report the CHOSEN token
    under the RAW distribution, matching the per-request chain
    (logprobs describe the model's distribution, not the sampler's).
    logits [B, V]; counts [B, V] int32; fp/pp [B] f32 (0 ⇒ identity)."""
    l32 = logits.astype(jnp.float32)
    c = counts.astype(jnp.float32)
    pen = l32 - fp[:, None] * c - pp[:, None] * (c > 0)
    nxt = jnp.argmax(pen, axis=-1).astype(jnp.int32)
    best = jnp.take_along_axis(l32, nxt[:, None], axis=-1)[:, 0]
    lp = best - jax.nn.logsumexp(l32, axis=-1)
    return nxt, best, lp


def _slot_decode_layer(blk, x, kc, vc, pos, active,
                       cfg: tr.TransformerConfig):
    """One token per slot, each at its own position.

    x: [B, 1, D]; kc/vc: [B, H, S_max, K] (plain bf16 or int8 dict —
    see kv_quant_enabled); pos: [B]; active: [B] bool.
    Only ACTIVE slots write their K/V — an inactive slot (no pending
    request this tick, or mid-chunked-prefill) must not clobber cache
    entries at its stale position (a chunked prefill interleaves decode
    ticks between chunks; a stale write at pos 0 would corrupt the entry
    chunk 0 wrote)."""
    q, k, v = _project_qkv(blk, x, cfg)
    q = _rope_at(q, pos, cfg.rope_theta)
    k = _rope_at(k, pos, cfg.rope_theta)

    kc = jax.vmap(_cache_row_write)(kc, k, pos, active)
    vc = jax.vmap(_cache_row_write)(vc, v, pos, active)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                   _cache_read_f32(kc)) * scale
    valid = jnp.arange(_cache_seq_len(kc))[None, :] <= pos[:, None]  # [B, S]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bhsk->bhqk", p, _cache_read_f32(vc)).astype(x.dtype)
    x = _attn_out(blk, x, o)
    return _ffn(blk, x, cfg), kc, vc


def _slot_forward(params, blocks, k, v, tokens, pos, active,
                  cfg: tr.TransformerConfig):
    """ONE slot-batched decode step — the shared per-step transformer
    body (embed → per-layer cached-attention scan → final head) behind
    :func:`make_slot_step` AND both fused multi-step kernels, so the
    fused ticks' bit-identity to the single-step path is one
    implementation, not hand-synced copies.  tokens [B] int32; returns
    (k', v', raw logits [B, V])."""
    x = jnp.take(params["embed"].astype(cfg.dtype),
                 tokens[:, None], axis=0)                         # [B,1,D]

    def layer(x, xs):
        blk, kc, vc = xs
        x, kc, vc = _slot_decode_layer(blk, x, kc, vc, pos, active, cfg)
        return x, (kc, vc)

    x, (k, v) = lax.scan(layer, x, (blocks, k, v))
    return k, v, _head(params, x, cfg)[:, -1]                     # [B, V]


def make_slot_step(cfg: tr.TransformerConfig):
    """jitted (params, k [L,B,H,S,K], v, tokens [B], prev [B], pos [B],
    active [B] bool, auto [B] bool) -> (greedy tokens [B] int32, best
    logits [B] f32, k', v').

    Every slot computes, but only ACTIVE slots write K/V — inactive slots
    (no pending request this tick, or mid-chunked-prefill) leave the cache
    untouched; callers ignore their outputs and do not advance their
    host-side pos.

    AUTO slots take their input token from ``prev`` — the previous tick's
    device-resident output — instead of the host ``tokens`` array: the
    server-side continuous-batching generation path, where the greedy
    feedback loop never leaves the device (no host round trip per token).

    k/v are DONATED: without donation XLA cannot alias the cache output to
    its input buffer and every tick pays a full cache copy (hundreds of MB
    at serving presets) on top of the one-position update.  The worker is
    the single owner and reassigns the returned arrays; a failed call
    rebuilds the bucket's cache (see _rebuild_bucket_cache)."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def step(params, k, v, tokens, prev, pos, active, auto):
        tokens = jnp.where(auto, prev, tokens)
        blocks = _layer_blocks(params, cfg)
        ks, vs, logits = _slot_forward(params, blocks, k, v, tokens, pos,
                                       active, cfg)
        nxt, best, lp = _greedy_head(logits)
        return nxt, best, lp, ks, vs

    return step


def resolve_decode_steps() -> int:
    """``TRITON_TPU_DECODE_STEPS``: decode steps fused into ONE device
    dispatch by the batched worker (the T of the multi-step tick).

    Default 4: the PR 7 tick profiler put single-step tick assembly +
    dispatch overhead at a large fraction of a decode tick at high
    concurrency, and T=4 amortizes the per-dispatch host work (job
    collection, one fused readback resolve, queue round trips) across 4
    tokens while keeping admission/cancellation latency bounded at 4
    steps (prefill/admit still runs between dispatches).  ``1`` restores
    the single-step tick exactly; raise it on hosts where dispatch
    overhead dominates (token streams are bit-identical at any T by
    construction — the fused kernel runs the same per-step math)."""
    import os

    v = os.environ.get("TRITON_TPU_DECODE_STEPS", "")
    if v in ("", "auto"):
        return 4
    try:
        n = int(v)
    except ValueError:
        raise ValueError(
            f"TRITON_TPU_DECODE_STEPS={v!r}: expected a positive integer "
            "or 'auto'")
    if n < 1:
        raise ValueError(f"TRITON_TPU_DECODE_STEPS={n} must be >= 1")
    return n


def start_readback(arr):
    """Begin the device->host transfer for ``arr`` WITHOUT blocking the
    caller (jax async dispatch: the copy overlaps whatever the device
    and host do next).  Pairs with :func:`finish_readback` — the
    double-buffer pattern every decode readback shares: the dispatching
    thread starts the copy, and by the time a resolver thread (or the
    next protocol step) lands in finish_readback the bytes are usually
    already host-side."""
    if hasattr(arr, "copy_to_host_async"):
        arr.copy_to_host_async()
    return arr


def finish_readback(arr):
    """Resolve a previously-started readback to a numpy array — the ONE
    deliberate blocking sync point of the decode double buffer (resolver
    threads block here so the worker/dispatch thread never does)."""
    import numpy as np

    # tpu-lint: disable=DEVICE-SYNC the ONE double-buffer resolve point
    return np.asarray(arr)


def _new_decode_state(cnt: int):
    """Device-resident per-slot control state for one cache bucket.

    The batched worker used to re-upload tokens/active/auto/pos (and the
    penalty rows) from host arrays on EVERY tick; this dict lives on
    device, is DONATED through the fused step kernel, and is updated by
    the kernel itself — steady-state generation re-crosses the
    host<->device boundary only for the one fused token readback.

    * ``tokens``: last client-supplied token per slot (client-driven
      sequence steps; auto slots ignore it),
    * ``prev``: the slot's previous greedy output — the self-feeding
      loop's device-resident feedback,
    * ``pos``: absolute decode position (host keeps an exact mirror for
      admission/eviction decisions — see ``_worker_loop``),
    * ``active``: slot computes-and-writes this step,
    * ``auto``: slot self-feeds (server-side generation),
    * ``remaining``: tokens left for an auto slot before it deactivates
      on device."""
    return {
        "tokens": jnp.zeros(cnt, jnp.int32),
        "prev": jnp.zeros(cnt, jnp.int32),
        "pos": jnp.zeros(cnt, jnp.int32),
        "active": jnp.zeros(cnt, bool),
        "auto": jnp.zeros(cnt, bool),
        "remaining": jnp.zeros(cnt, jnp.int32),
    }


@jax.jit
def _state_admit(state, li, prev_tok, pos, self_feed, remaining):
    """Prefill finished for bucket-local slot ``li``: seed the device-side
    feedback token and position.  ``self_feed`` activates the slot (a
    server-side generation that will tick itself); client-driven
    sequence slots stay inactive — their steps arrive per tick via the
    dispatch's step mask."""
    return {
        "tokens": state["tokens"],
        "prev": state["prev"].at[li].set(prev_tok),
        "pos": state["pos"].at[li].set(pos),
        "active": state["active"].at[li].set(self_feed),
        "auto": state["auto"].at[li].set(self_feed),
        "remaining": state["remaining"].at[li].set(remaining),
    }


@jax.jit
def _state_deactivate(state, li):
    """Cancellation/reap: stop a self-feeding slot on device (the kernel
    deactivates completed slots itself; this is for consumers that went
    away mid-generation)."""
    return dict(state,
                active=state["active"].at[li].set(False),
                auto=state["auto"].at[li].set(False))


def _fused_tick_frame(n_steps: int):
    """Shared scaffolding for the fused multi-step tick kernels: merge
    the dispatch's client-step mask into the resident state, run
    ``body_step`` under a ``lax.while_loop`` with the on-device
    all-inactive early exit, and stack per-step outputs into the
    ``[rows, T, B]`` readback block."""

    def run(k, v, state, step_mask, step_tokens, extra, body_step, rows):
        B = step_mask.shape[0]
        st0 = dict(
            state,
            tokens=jnp.where(step_mask, step_tokens, state["tokens"]),
            active=state["active"] | step_mask,
        )
        out0 = jnp.zeros((rows, n_steps, B), jnp.float32)

        def cond(carry):
            t, _k, _v, st, _out, _extra = carry
            # early exit: a draining cohort (every slot done/deactivated)
            # stops paying steps the host would discard
            return (t < n_steps) & jnp.any(st["active"])

        def body(carry):
            t, k, v, st, out, extra = carry
            k, v, row, nxt, extra = body_step(k, v, st, extra)
            out = lax.dynamic_update_slice(
                out, row[:, None, :], (0, t, 0))
            act, auto = st["active"], st["auto"]
            rem = st["remaining"] - (act & auto)
            pos = st["pos"] + act
            done = auto & act & ((rem <= 0) | (pos >= _cache_seq_len(k)))
            st = {
                "tokens": st["tokens"],
                # client-driven slots ran their ONE step — deactivate;
                # auto slots deactivate when drained or at the slab cap
                "prev": jnp.where(act, nxt, st["prev"]),
                "pos": pos,
                "active": act & auto & ~done,
                "auto": auto & ~done,
                "remaining": rem,
            }
            return (t + 1, k, v, st, out, extra)

        t, k, v, st, out, extra = lax.while_loop(
            cond, body, (jnp.int32(0), k, v, st0, out0, extra))
        return k, v, st, out, t, extra

    return run


def make_fused_slot_step(cfg: tr.TransformerConfig, n_steps: int):
    """jitted (params, k, v, state, step_mask, step_tokens) ->
    (k', v', state', out [3, T, B] f32, steps_run).

    Runs up to ``n_steps`` (T) decode steps in ONE device dispatch,
    carrying cache AND control state on device:

    * ``state`` (see :func:`_new_decode_state`) is DONATED and updated
      by the kernel itself — a steady-state generation tick uploads
      nothing host->device;
    * ``step_mask``/``step_tokens`` merge this dispatch's client-driven
      sequence steps in: their slots run exactly ONE step (step 0) and
      deactivate — the closed-loop client owns their next token;
    * self-feeding (auto) slots consume their own previous output and
      deactivate ON DEVICE when ``remaining`` runs out or the slab cap
      is hit; the loop exits early once every slot is inactive;
    * ``out[0]`` = greedy tokens, ``out[1]`` = best raw logits,
      ``out[2]`` = chosen-token logprobs, per (step, slot); rows at or
      past ``steps_run`` are zeros the host never reads.

    Per-step math is EXACTLY :func:`make_slot_step`'s — token streams
    are bit-identical to the single-step tick at any T by construction.
    k/v/state donated (see make_slot_step)."""

    frame = _fused_tick_frame(n_steps)

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def fused(params, k, v, state, step_mask, step_tokens):
        blocks = _layer_blocks(params, cfg)

        def body_step(k, v, st, extra):
            toks = jnp.where(st["auto"], st["prev"], st["tokens"])
            k, v, logits = _slot_forward(params, blocks, k, v, toks,
                                         st["pos"], st["active"], cfg)
            nxt, best, lp = _greedy_head(logits)
            row = jnp.stack([nxt.astype(jnp.float32), best, lp])
            return k, v, row, nxt, extra

        k, v, st, out, t, _ = frame(k, v, state, step_mask, step_tokens,
                                    jnp.int32(0), body_step, 3)
        return k, v, st, out, t

    return fused


def make_fused_slot_step_pen(cfg: tr.TransformerConfig, n_steps: int):
    """Penalized variant of :func:`make_fused_slot_step`: per-slot
    OpenAI frequency/presence penalties (``fp*count + pp*(count>0)``
    subtracted at the greedy head) applied each step, with the count
    matrix carried on device across the fused steps — only active AUTO
    slots add their chosen token to counts (client-driven steps consume
    the CLIENT's token; penalties are a generation-path feature).
    ``fp``/``pp`` are device-resident per-slot vectors, updated at
    admission/release rather than per tick; zero entries degenerate to
    the plain head, and the worker compiles this kernel only for buckets
    actually holding a penalized generation.

    ``counts`` is deliberately NOT donated: the penalty head READS the
    buffer the scatter update would write in place, and with donation
    the CPU backend was observed starting the in-place write before the
    read finished (flaky last-token corruption, 6-8/40 runs; an explicit
    lax.optimization_barrier did not close it).  The copy this costs is
    one [B, V] int32 per dispatch — noise against the tick's matmuls."""

    frame = _fused_tick_frame(n_steps)

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def fused(params, k, v, state, step_mask, step_tokens, counts, fp, pp):
        blocks = _layer_blocks(params, cfg)

        def body_step(k, v, st, counts):
            toks = jnp.where(st["auto"], st["prev"], st["tokens"])
            k, v, logits = _slot_forward(params, blocks, k, v, toks,
                                         st["pos"], st["active"], cfg)
            nxt, best, lp = _pen_head(logits, counts, fp, pp)
            take = (st["active"] & st["auto"]).astype(jnp.int32)
            counts = counts.at[jnp.arange(counts.shape[0]), nxt].add(take)
            row = jnp.stack([nxt.astype(jnp.float32), best, lp])
            return k, v, row, nxt, counts

        k, v, st, out, t, counts = frame(
            k, v, state, step_mask, step_tokens, counts, body_step, 3)
        return k, v, st, out, t, counts

    return fused


def make_slot_prefill(cfg: tr.TransformerConfig):
    """jitted (params, k, v, tokens [1,S], slot) -> (next tok, best logit,
    k', v') — prefills ONE slot of the shared cache in a single forward.

    The cache length comes from the cache leaf itself (``_cache_seq_len`` —
    ``k`` is a plain array or an int8 {q, s} dict), so one returned
    function serves every slab bucket — jit retraces per distinct cache
    shape.  k/v donated (see make_slot_step)."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def prefill(params, k, v, tokens, slot):
        B, S = tokens.shape
        x = jnp.take(params["embed"].astype(cfg.dtype), tokens, axis=0)
        blocks = _layer_blocks(params, cfg)

        def layer(x, blk):
            x, kl, vl = _prefill_layer(blk, x, cfg)
            return x, (kl, vl)

        x, (ks, vs) = lax.scan(layer, x, blocks)                  # [L,1,H,S,K]
        pad = _cache_seq_len(k) - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        k = _cache_block_write(k, ks, (0, slot, 0, 0), (0, slot, 0, 0, 0))
        v = _cache_block_write(v, vs, (0, slot, 0, 0), (0, slot, 0, 0, 0))
        logits = _head(params, x, cfg)[:, -1]
        nxt, best, lp = _greedy_head(logits)
        return nxt[0], best[0], lp[0], k, v

    return prefill


def make_slot_prefill_pen(cfg: tr.TransformerConfig):
    """Penalized variant of make_slot_prefill: the FIRST token must
    already respect the prompt's token counts (the per-request chain
    does), so the head takes the slot's seeded count row and fp/pp
    scalars; the chosen token is added to the row for tick 1.  Returns
    the updated [V] count row alongside the cache."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def prefill(params, k, v, tokens, slot, counts_row, fp, pp):
        B, S = tokens.shape
        x = jnp.take(params["embed"].astype(cfg.dtype), tokens, axis=0)
        blocks = _layer_blocks(params, cfg)

        def layer(x, blk):
            x, kl, vl = _prefill_layer(blk, x, cfg)
            return x, (kl, vl)

        x, (ks, vs) = lax.scan(layer, x, blocks)                  # [L,1,H,S,K]
        pad = _cache_seq_len(k) - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        k = _cache_block_write(k, ks, (0, slot, 0, 0), (0, slot, 0, 0, 0))
        v = _cache_block_write(v, vs, (0, slot, 0, 0), (0, slot, 0, 0, 0))
        logits = _head(params, x, cfg)[:, -1]
        nxt, best, lp = _pen_head(logits, counts_row[None, :],
                                  fp[None], pp[None])
        counts_row = counts_row.at[nxt[0]].add(1)
        return nxt[0], best[0], lp[0], k, v, counts_row

    return prefill


def make_slot_chunk_prefill(cfg: tr.TransformerConfig, s_max: int):
    """jitted (params, k, v, chunk [1,C], slot, pos0) -> (next tok, best
    logit, k', v') — prefills ONE CHUNK of a slot's prompt.

    Chunked prefill is what lets new prompts interleave with decode ticks
    instead of stalling the whole cohort for a full-prompt forward (the
    genai-perf c=8 contention BASELINE row 8 measured): each chunk attends
    to the cache prefix written by earlier chunks (positions < pos0) plus
    causally within itself, exactly reproducing full-prompt prefill.  The
    returned token/logit are meaningful on the FINAL chunk only.  k/v
    donated (see make_slot_step)."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def chunk_prefill(params, k, v, chunk, slot, pos0):
        B, C = chunk.shape
        S = _cache_seq_len(k)
        x = jnp.take(params["embed"].astype(cfg.dtype), chunk, axis=0)
        blocks = _layer_blocks(params, cfg)
        positions = pos0 + jnp.arange(C)
        # [C, S] mask: chunk position i sees cache entries j <= pos0 + i
        valid = jnp.arange(S)[None, :] <= positions[:, None]
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def layer(x, xs):
            blk, kc, vc = xs              # [n_slots, H, S, K]
            q, kk, vv = _project_qkv(blk, x, cfg)
            q, kk = tr._rope(q, kk, positions, cfg.rope_theta)
            kc = _cache_block_write(kc, kk, (slot, 0, pos0),
                                    (slot, 0, pos0, 0))
            vc = _cache_block_write(vc, vv, (slot, 0, pos0),
                                    (slot, 0, pos0, 0))
            kcs = _cache_slot_slice(kc, slot)
            vcs = _cache_slot_slice(vc, slot)
            s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                           _cache_read_f32(kcs)) * scale
            s = jnp.where(valid[None, None, :, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqs,bhsk->bhqk", p,
                           _cache_read_f32(vcs)).astype(x.dtype)
            x = _attn_out(blk, x, o)
            return _ffn(blk, x, cfg), (kc, vc)

        x, (ks, vs) = lax.scan(layer, x, (blocks, k, v))
        logits = _head(params, x, cfg)[:, -1]
        nxt, best, lp = _greedy_head(logits)
        return nxt[0], best[0], lp[0], ks, vs

    return chunk_prefill


def make_cache_block_ops(block_tokens: int):
    """jitted ``(extract, insert)`` pair for the prefix/KV block cache
    (server/kvcache.py) over the shared ``[L, B, H, S, K]`` cache layout
    (slot-slab buckets AND independent per-sequence caches — ``slot``
    indexes axis 1 either way).

    ``extract(k, v, slot, pos)`` slices one ``block_tokens``-deep block
    into INDEPENDENT device buffers — committed blocks never alias the
    (donated) slab, so a failed dispatch or chaos deletion of the slab
    leaves the store's bytes intact.  ``insert(k, v, kb, vb, slot, pos)``
    writes a stored block back verbatim (no quantize round trip): a hit
    restores the exact bytes a cold prefill would have written, which is
    what the hit-vs-cold bit-identity contract rests on.  k/v donated on
    insert (in-place slab update, same convention as the step kernels)."""

    def _slice_one(c, slot, pos):
        if isinstance(c, dict):
            L, _, H, _, K = c["q"].shape
            return {
                "q": lax.dynamic_slice(c["q"], (0, slot, 0, pos, 0),
                                       (L, 1, H, block_tokens, K)),
                "s": lax.dynamic_slice(c["s"], (0, slot, 0, pos),
                                       (L, 1, H, block_tokens)),
            }
        L, _, H, _, K = c.shape
        return lax.dynamic_slice(c, (0, slot, 0, pos, 0),
                                 (L, 1, H, block_tokens, K))

    def _write_one(c, blk, slot, pos):
        if isinstance(c, dict):
            return {
                "q": lax.dynamic_update_slice(c["q"], blk["q"],
                                              (0, slot, 0, pos, 0)),
                "s": lax.dynamic_update_slice(c["s"], blk["s"],
                                              (0, slot, 0, pos)),
            }
        return lax.dynamic_update_slice(c, blk, (0, slot, 0, pos, 0))

    def _concat(blks):
        if isinstance(blks[0], dict):
            return {"q": jnp.concatenate([b["q"] for b in blks], axis=3),
                    "s": jnp.concatenate([b["s"] for b in blks], axis=3)}
        return jnp.concatenate(blks, axis=3)

    @jax.jit
    def extract(k, v, slot, pos):
        return _slice_one(k, slot, pos), _slice_one(v, slot, pos)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def insert(k, v, kb, vb, slot, pos):
        return _write_one(k, kb, slot, pos), _write_one(v, vb, slot, pos)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def insert_run(k, v, kbs, vbs, slot, pos):
        # the whole matched chain in ONE dispatch (concat + one
        # contiguous write) — a per-block insert loop pays a dispatch
        # round trip per 64 tokens, which is most of the warm-TTFT win
        # given back on deep chains.  jit specializes per chain length;
        # chains are short (≤ s_max/block_tokens), so the variant count
        # is bounded and each program is a trivial update-slice.
        return (_write_one(k, _concat(kbs), slot, pos),
                _write_one(v, _concat(vbs), slot, pos))

    return extract, insert, insert_run


def make_prefill_tail(cfg: tr.TransformerConfig, s_max: int):
    """jitted (params, k, v, tail [1,T], pos0) -> (last logits [1,V],
    cache) — completes an INDEPENDENT-mode prefill whose first ``pos0``
    cache positions were restored from the prefix cache.

    The tail attends to the restored prefix (positions < pos0) plus
    causally within itself — the same math as make_slot_chunk_prefill,
    so together with the verbatim block restore it exactly reproduces
    ``make_prefill`` on the full prompt.  Returns the same
    ``(logits, {"k", "v", "pos"})`` contract as make_prefill so the
    decode-step path is oblivious to how the cache was filled.  k/v
    donated (freshly allocated per admission)."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def tail(params, k, v, chunk, pos0):
        B, C = chunk.shape
        x = jnp.take(params["embed"].astype(cfg.dtype), chunk, axis=0)
        blocks = _layer_blocks(params, cfg)
        positions = pos0 + jnp.arange(C)
        valid = jnp.arange(s_max)[None, :] <= positions[:, None]
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def layer(x, xs):
            blk, kc, vc = xs              # [B, H, s_max, K]
            q, kk, vv = _project_qkv(blk, x, cfg)
            q, kk = tr._rope(q, kk, positions, cfg.rope_theta)
            kc = _cache_block_write(kc, kk, (0, 0, pos0), (0, 0, pos0, 0))
            vc = _cache_block_write(vc, vv, (0, 0, pos0), (0, 0, pos0, 0))
            s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                           _cache_read_f32(kc)) * scale
            s = jnp.where(valid[None, None, :, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqs,bhsk->bhqk", p,
                           _cache_read_f32(vc)).astype(x.dtype)
            x = _attn_out(blk, x, o)
            return _ffn(blk, x, cfg), (kc, vc)

        x, (ks, vs) = lax.scan(layer, x, (blocks, k, v))
        cache = {"k": ks, "v": vs,
                 "pos": jnp.asarray(pos0 + C, jnp.int32)}
        return _head(params, x, cfg)[:, -1], cache

    return tail


def _worker_socket(decode: "DecodeModel", alias: str, name: str):
    """``__getattr__`` of the two ``Model`` adapters below: the core's
    ``attach_*`` sockets (``InferenceCore._wire``) are the shared decode
    worker's, where the ticks, slot admission, cost attribution and faults
    of every name it serves happen.  The fault manager also learns the
    adapter's model name as an alias, so a quarantine (and a probe's
    release) covers the sequence and the generate surface together."""
    if not name.startswith("attach_"):
        raise AttributeError(name)
    attach = getattr(decode, name)
    if name == "attach_device_faults":
        return lambda mgr: attach(mgr, alias)
    return attach


class DecodeModel:
    """``llama_decode``: sequence-stateful greedy decoding over a shared
    SLOT cache with continuous batching.

    Protocol (sequence semantics, same wire as ``simple_sequence``):

    * ``sequence_start`` request carries TOKENS ``[1, prompt_len]`` — the
      prompt is PREFILLED in one forward into a free slot of the shared
      cache and the first greedy token returns.
    * every following request carries TOKENS ``[1, 1]`` — usually the token
      the server just returned (closed-loop generation) — and pays one
      single-token decode step.
    * ``sequence_end`` frees the slot; idle sequences evict on TTL.

    Continuous batching: a single worker thread owns the cache; while one
    batched step's readback is in flight, newly arriving steps queue, and
    the next tick merges them — one device step and ONE fused D2H per tick
    regardless of how many sequences advanced (the per-stream serial rate
    stays RTT-bound, but aggregate throughput scales with concurrency
    instead of serializing per token).

    Shares the ``llama_tpu`` preset/seed, so it decodes the same weights the
    window-recompute ensemble serves."""

    def __init__(self, name="llama_decode", prompt_len=None, s_max=None,
                 n_slots=None):
        import os
        import threading

        from ..server.model import Model, make_config
        from . import language

        self._language = language
        self._prompt_len = prompt_len or language.LLAMA_SEQ_LEN
        self._s_max = s_max or 2 * self._prompt_len
        if n_slots is None:
            n_slots = int(os.environ.get("TRITON_TPU_DECODE_SLOTS", "8"))
        self._n_slots = n_slots
        # "independent": each sequence owns its cache; steps run (and their
        # readbacks overlap) on the server's executor threads, one device
        # dispatch per sequence per token. "batched": shared slot cache +
        # continuous batching — one device step and one readback per tick
        # regardless of how many sequences advanced, which is what should
        # win on a co-located chip where readback is sub-millisecond and
        # per-step dispatch dominates.  Both run on the chip
        # (chip_smoke.py); which is faster there has not been measured, so
        # the default stays what it was until ROADMAP S1 decides it.
        self._mode = os.environ.get("TRITON_TPU_DECODE_MODE", "independent")
        if self._mode not in ("independent", "batched"):
            raise ValueError(
                f"TRITON_TPU_DECODE_MODE={self._mode!r}: expected "
                "'independent' or 'batched'")
        # slab-size buckets (batched mode): short generations take a short
        # slab, so the same HBM budget holds more concurrent generations
        bucket_spec = os.environ.get("TRITON_TPU_DECODE_BUCKETS")
        if bucket_spec and self._mode != "batched":
            # fail loudly, not silently-reshape the independent-mode cache
            raise ValueError(
                "TRITON_TPU_DECODE_BUCKETS requires "
                "TRITON_TPU_DECODE_MODE=batched (independent mode has no "
                "shared slot cache to bucket)")
        self._buckets = parse_cache_buckets(
            bucket_spec, n_slots, self._s_max, self._prompt_len)
        # int8 KV storage for the shared slot cache (kv_quant_enabled
        # validates the value; batched-only, like the buckets)
        self._kv_quant = kv_quant_enabled()
        if self._kv_quant and self._mode != "batched":
            raise ValueError(
                "TRITON_TPU_KV_QUANT requires TRITON_TPU_DECODE_MODE="
                "batched (independent mode has no shared slot cache)")
        # multi-step fused ticks: T decode steps per device dispatch
        # (batched mode; validated eagerly so a bad value fails at
        # registration, not at the first generation)
        self._decode_steps = resolve_decode_steps()
        n_slots = sum(c for c, _ in self._buckets)
        self._n_slots = n_slots
        self._s_max = max(cap for _, cap in self._buckets)
        off = 0
        self._bucket_off = []
        for cnt, _cap in self._buckets:
            self._bucket_off.append(off)
            off += cnt
        cfg = make_config(
            name,
            inputs=[("TOKENS", "INT32", [-1])],
            outputs=[("NEXT_TOKEN", "INT32", [1]),
                     ("NEXT_LOGIT", "FP32", [1])],
            sequence_batching=True,
            instance_kind="KIND_TPU",
            # advertised so load tools (genai_perf) can size the prefill
            # window without out-of-band knowledge
            parameters={"prompt_tokens": str(self._prompt_len)},
        )
        outer = self

        class _Impl(Model):  # noqa: N801 — adapter onto the abstract Model
            def execute(inner, inputs, parameters):
                return outer._execute(inputs, parameters)

            def unload(inner):
                outer._shutdown()

            device_loop = True

            def __getattr__(inner, name):
                return _worker_socket(outer, inner.config.name, name)

        self._model = _Impl(cfg)
        # device/scheduler observability sink (attach_device_stats): the
        # worker records one nv_tpu_tick_* row per fused dispatch into it
        self._device_stats = None
        # byte-admission sink (attach_memory_governor): slot admission
        # gates on projected KV bytes vs live HBM headroom when attached
        self._memory_governor = None
        # per-tenant attribution sink (attach_cost_ledger): the worker
        # charges each slot its share of every tick's compute window
        self._cost_ledger = None
        # device-fault containment sink (attach_device_faults): failed
        # dispatches and recoveries report into the core's manager, which
        # runs the quarantine state machine.  The shared worker serves
        # both the sequence-protocol name and the generate alias —
        # _fault_names carries every attached alias so a fault
        # quarantines (and a probe releases) both together.
        self._fault_mgr = None
        self._fault_names: list = [name]
        # seeded chaos injector (attach_chaos): consulted at dispatch
        # boundaries for device_error drills
        self._chaos = None
        self._probe_fn = None
        # bounded per-sequence recovery budget: re-prefill attempts per
        # generation before it gets the pre-containment typed 500
        self._recovery_budget = int(os.environ.get(
            "TRITON_TPU_RECOVERY_BUDGET", "3"))
        # tick-stall watchdog (armed in _ensure_fns when
        # TRITON_TPU_TICK_STALL_MS / --tick-stall-ms is set): in-flight
        # readbacks register here; one that resolves too slowly is
        # reported as a device fault (see _watchdog_loop for the honest
        # limits of what the host can do about a wedged dispatch)
        self._stall_s = 0.0
        self._watch_lock = threading.Lock()
        self._watched: Dict[int, list] = {}
        self._watch_seq = 0
        # slot -> tenant / governor KV-pin handle for every busy slot
        # (written under self._lock at admission, popped at release);
        # bucket -> fused-dispatch SignatureCost, False once analysis
        # was attempted and came back unavailable (absent, never faked)
        self._slot_tenant: Dict[int, str] = {}
        self._slot_kv_pin: Dict[int, int] = {}
        self._bucket_cost: Dict[int, Any] = {}
        self._state: Dict[Any, int] = {}      # seq_id -> slot
        self._free = set(range(n_slots))
        self._touched: Dict[Any, float] = {}
        self._seq_locks: Dict[Any, Any] = {}
        self._idle_s = (
            cfg.sequence_batching.max_sequence_idle_microseconds / 1e6)
        self._lock = threading.Lock()
        self._init_lock = threading.Lock()
        self._threading = threading
        self._fns = None
        self._fns_ind = None
        self._params = None
        self._mesh = None
        self._prefill_chunk = 0
        self._chunk_fn = None
        # prefix/KV block cache (server/kvcache.py): resolved lazily with
        # the compiled functions — None keeps every path on the legacy
        # cold-prefill behavior (budget 0 / int8 KV quant)
        self._kv_cache = None
        self._cache_extract_fn = None
        self._cache_insert_fn = None
        self._cache_insert_run_fn = None
        self._cache_tail_fn = None
        self._ind_tail_fn = None
        self._jobs = None
        self._worker = None
        self._closed = False
        # per-slot generation: bumped on every release/evict so jobs from a
        # dead sequence can never touch the slot's next occupant
        self._slot_gen = [0] * n_slots
        # worker-owned (single writer): slot cache + per-slot position
        self._k = self._v = None
        self._pos = None
        # worker-owned monotonic fused-dispatch id: stamped on every
        # tick-profiler row AND on each traced sequence's tick entries —
        # the join key between "my request" and "the cohort dispatch it
        # rode" in the trace viewer
        self._tick_seq = 0

    @property
    def model(self):
        return self._model

    def attach_device_stats(self, ds) -> None:
        """Attach the serving core's ``DeviceStatsCollector`` (idempotent;
        the core stamps it on first execution, tests attach directly).
        The batched worker then records one tick row per fused dispatch:
        steps-per-dispatch, control uploads, and the single fused D2H
        sync — the counters that prove the fast path stays fast."""
        self._device_stats = ds

    def attach_memory_governor(self, gov) -> None:
        """Attach the serving core's ``MemoryGovernor`` (idempotent
        attribute stamp, like ``attach_device_stats``).  Slot admission
        then gates on projected KV bytes vs live HBM headroom — a long
        prompt degrades to a typed 429 instead of an allocator abort
        that takes the running cohort down.  Inert on backends without
        memory gauges (CPU)."""
        self._memory_governor = gov

    def attach_cost_ledger(self, ledger) -> None:
        """Attach the serving core's ``CostLedger`` (idempotent attribute
        stamp, like ``attach_device_stats``).  The batched worker then
        attributes every fused tick's compute window to the live slots'
        tenants (equal shares — each slot rode exactly one lane of the
        dispatch) plus generated tokens and KV byte-seconds; the shares
        sum to the tick window by construction, so the ledger reconciles
        with the duty-cycle compute total."""
        self._cost_ledger = ledger

    def attach_device_faults(self, mgr, name: str = None) -> None:
        """Attach the serving core's ``DeviceFaultManager`` (idempotent
        attribute stamp, like ``attach_device_stats``).  Every failed
        dispatch then reports a fault (K-in-window → quarantine), every
        recovered generation a recovery, and the manager gets a probe
        callback that issues a real dispatch against the rebuilt cache
        to un-quarantine.  ``name`` registers an alias (the generate
        wrapper serves the same worker under its own model name): faults
        quarantine every alias together."""
        if name and name not in self._fault_names:
            self._fault_names.append(name)
        self._fault_mgr = mgr
        for alias in self._fault_names:
            mgr.register_probe(alias, self._probe_dispatch)

    def attach_chaos(self, injector) -> None:
        """Attach the seeded chaos injector (idempotent attribute stamp).
        The worker then consults ``maybe_device_fault`` at its dispatch
        boundaries: a drawn ``device_error`` genuinely invalidates the
        donated bucket buffers and raises a synthetic XLA-shaped error,
        so drills exercise the real rebuild/recovery path from a seed."""
        self._chaos = injector

    def _report_fault(self, kind: str, reason: str = "",
                      force_quarantine: bool = False) -> None:
        """One device fault on every attached alias (no-op unattached)."""
        mgr = self._fault_mgr
        if mgr is None:
            return
        for alias in self._fault_names:
            mgr.record_fault(alias, kind, reason=reason,
                             force_quarantine=force_quarantine)

    def _report_recovered(self, n: int = 1) -> None:
        mgr = self._fault_mgr
        if mgr is None:
            return
        for alias in self._fault_names:
            mgr.record_recovered(alias, n)

    def _report_aborted(self, n: int = 1) -> None:
        mgr = self._fault_mgr
        if mgr is None:
            return
        for alias in self._fault_names:
            mgr.record_aborted(alias, n)

    def _probe_dispatch(self) -> bool:
        """Quarantine probe: one real (tiny) device dispatch, resolved
        synchronously.  Success means the device executes and reads back
        again — the manager un-quarantines every alias.  Runs on the
        manager's probe thread; it deliberately avoids the donated slot
        caches (a probe must never consume live state) and its blocking
        resolve belongs here — the probe IS a synchronous health check,
        not a tick.  An armed chaos injector is consulted first so a
        seeded persistent-fault drill fails probes deterministically
        until its fault budget runs dry."""
        try:
            if self._closed:
                return False
            chaos = self._chaos
            if chaos is not None and chaos.maybe_device_fault(
                    self._fault_names[0]):
                return False
            import jax
            import jax.numpy as jnp

            fn = getattr(self, "_probe_fn", None)
            if fn is None:
                fn = jax.jit(lambda x: x + 1)
                self._probe_fn = fn
            return int(fn(jnp.int32(1))) == 2
        except Exception:  # noqa: BLE001 — a raising probe is a failed probe
            return False

    # -- tick-stall watchdog -----------------------------------------------
    def _watch_readback(self, kind: str):
        """Register one in-flight readback with the stall watchdog.
        Returns the watch id the resolver hands back to
        ``_unwatch_readback`` when the resolve completes; None (a no-op
        id) when the watchdog is unarmed."""
        if self._stall_s <= 0.0:
            return None
        import time

        with self._watch_lock:
            self._watch_seq += 1
            wid = self._watch_seq
            # [start, kind, reported] — reported keeps a single wedged
            # readback from re-firing the fault every sweep
            self._watched[wid] = [time.monotonic(), kind, False]
        return wid

    def _unwatch_readback(self, wid) -> None:
        if wid is None:
            return
        with self._watch_lock:
            self._watched.pop(wid, None)

    def _watchdog_loop(self) -> None:
        """Daemon sweep: any registered readback whose resolve exceeds
        ``--tick-stall-ms`` is reported as a ``tick_stall`` device fault
        with forced quarantine.

        HONEST LIMIT: a wedged device dispatch cannot be killed from the
        host — no JAX/XLA API cancels an in-flight execution, so the
        watchdog cannot unwedge the tick or recover its generations.
        What it guarantees is that the stall does not fail silently: the
        forced quarantine flips the model not-ready (503 with pushback,
        so clients route to healthy replicas) and fires the
        ``device_fault`` incident capture WHILE the dispatch is still
        stuck — the evidence window an operator otherwise loses to a
        hang that only surfaces as distant client timeouts."""
        import time

        while not self._closed:
            time.sleep(min(0.25, self._stall_s / 2.0))
            now = time.monotonic()
            stalled = []
            with self._watch_lock:
                for ent in self._watched.values():
                    if not ent[2] and now - ent[0] >= self._stall_s:
                        ent[2] = True
                        stalled.append((ent[1], now - ent[0]))
            for kind, age in stalled:
                self._report_fault(
                    "tick_stall",
                    reason=(f"{kind} readback stalled {age * 1e3:.0f}ms "
                            f"(tick-stall-ms={self._stall_s * 1e3:.0f}); "
                            "a wedged device dispatch cannot be killed "
                            "from the host — quarantining so traffic "
                            "reroutes while it is stuck"),
                    force_quarantine=True)

    # -- device-fault injection + recovery ---------------------------------
    def _maybe_inject_device_fault(self, b: int) -> None:
        """Dispatch-boundary chaos consult (``device_error`` kind): when
        the seeded draw fires, genuinely invalidate the bucket's donated
        buffers — exactly the wreckage a failed donated dispatch leaves —
        then raise the synthetic XLA-shaped error.  Everything downstream
        (rebuild, generation recovery, quarantine escalation) is the REAL
        containment path; nothing is mocked."""
        chaos = self._chaos
        if chaos is None:
            return
        if not chaos.maybe_device_fault(self._fault_names[0]):
            return
        from ..server.chaos import ChaosDeviceError

        def _delete(arr):
            if isinstance(arr, dict):  # int8 cache: {"q", "s"} pair
                for v in arr.values():
                    _delete(v)
                return
            try:
                arr.delete()
            except Exception:  # noqa: BLE001 — already-deleted is fine
                pass

        _delete(self._k[b])
        _delete(self._v[b])
        for leaf in jax.tree_util.tree_leaves(self._dstate[b]):
            _delete(leaf)
        raise ChaosDeviceError(self._fault_names[0])

    def _recover_handoff(self, sink) -> None:
        """Hand one live server-side generation to the recovery queue
        after a device fault invalidated its bucket (worker thread).

        The ``prompt + emitted_so_far`` snapshot must contain exactly the
        tokens the consumer already received, so it is taken ON the
        ordered gen-reader thread: every token the resolvers delivered
        before the fault has already run its ``emitted.append`` there,
        in-flight readbacks from the dying dispatch resolve (or fail,
        setting ``sink.failed``) ahead of this submission, and nothing
        appends afterwards — the bucket rebuild bumped the slot
        generations, so no further resolution for this stream exists.
        Combined with the worker's host mirror (``_pos`` and
        ``remaining`` advance only on successful dispatch), the snapshot
        equals the stream state at the last successful dispatch, which
        is what makes the greedy resume bit-identical."""
        from ..server.types import InferError

        def snapshot():
            if getattr(sink, "cancelled", False):
                # consumer already left: nothing to resume, end cleanly
                self._close_decode_span(sink)
                sink.put(None)
                return
            if getattr(sink, "failed", False):
                # an in-flight readback from the dying dispatch already
                # surfaced the error on this stream; re-admitting would
                # splice tokens after an exception the consumer saw
                self._report_aborted()
                return
            if sink.recoveries >= self._recovery_budget:
                sink.failed = True
                self._report_aborted()
                st = getattr(sink, "trace", None)
                if st is not None and st.flight is not None:
                    st.flight.fault = "device_error"
                sink.put(InferError(
                    f"model '{self._model.name}': decode cache was "
                    "rebuilt after a device error and the generation's "
                    f"recovery budget ({self._recovery_budget}) is "
                    "exhausted; generation aborted", 500))
                return
            sink.recoveries += 1
            st = getattr(sink, "trace", None)
            if st is not None and st.flight is not None:
                st.flight.fault = "device_error"
            self._jobs.put(("recover", (sink, list(sink.emitted)), None))

        self._gen_reader.submit(snapshot)

    def _stamp_cache_hit(self, completion, hit: int, phash) -> None:
        """Worker-side: record a generation's prefix-cache outcome on its
        sink (usage backchannel), its stream trace context, and its
        flight record — the observability trio the PREFILL-collapse
        surfaces read.  Sequence-protocol completions carry no sink and
        are visible through the counter families only."""
        if completion[0] != "gen":
            return
        sink = completion[2]
        sink.cache_hit_tokens = int(hit)
        sink.prefix_hash = phash
        st = getattr(sink, "trace", None)
        if st is not None:
            st.cache_hit_tokens = int(hit)
            st.prefix_hash = phash
            if st.flight is not None:
                st.flight.cache_hit_tokens = int(hit)
                st.flight.prefix_hash = phash

    def _cache_commit(self, win, hit: int, b: int, li: int,
                      tenant: str) -> None:
        """Worker-side, after a cold/partial prefill wrote the slab:
        extract the window's uncommitted complete blocks (positions
        ``[hit, floor((len-1)/B)*B)``) into independent device buffers
        and commit them to the block store.  Best-effort — a full store
        simply declines.  The extraction is an async ``dynamic_slice``
        dispatch, never a blocking sync."""
        kvc = self._kv_cache
        if kvc is None:
            return
        digs = kvc.chain_digests(win[0])
        bt = kvc.block_tokens
        for i in range(hit // bt, len(digs)):
            d = digs[i]
            if kvc.has(d):
                continue
            kb, vb = self._cache_extract_fn(self._k[b], self._v[b],
                                            li, i * bt)
            kvc.put(d, digs[i - 1] if i else b"", kb, vb, tenant)

    def _kv_pin_slot(self, slot: int, tokens: int, tenant: str) -> None:
        """Open the memory governor's KV byte-seconds integrator for an
        admitted slot (attribution only — HBM admission gating already
        ran).  Inert without a governor.  If a concurrent cache rebuild
        freed the slot between allocation and this pin, the pin is
        closed immediately instead of leaking."""
        gov = self._memory_governor
        if gov is None:
            return
        nbytes = int(tokens) * self._kv_bytes_per_token()
        if nbytes <= 0:
            return
        handle = gov.kv_pin(self._model.name, nbytes, tenant)
        with self._lock:
            if slot in self._free:
                released = True
            else:
                self._slot_kv_pin[slot] = handle
                released = False
        if released:
            self._kv_unpin_charge(handle)

    def _kv_unpin_charge(self, handle) -> None:
        """Close an admitted slot's KV integrator and charge the tenant
        with exactly the byte-seconds the governor integrated — the
        nv_cost_kv_byte_seconds_total / governor-ledger reconciliation
        holds by construction, not by sampling.  Safe under self._lock
        (governor and ledger locks are leaves)."""
        gov = self._memory_governor
        if handle is None or gov is None:
            return
        tenant, byte_s = gov.kv_unpin(handle)
        ledger = self._cost_ledger
        if ledger is not None and ledger.enabled and byte_s > 0:
            ledger.charge(self._model.name, tenant,
                          kv_byte_seconds=byte_s)

    def _kv_bytes_per_token(self) -> int:
        """Analytic KV-cache footprint of ONE cached token position:
        layers x (k + v) x heads x head_dim x cache itemsize (int8 KV
        quantization halves bf16's 2 bytes).  The projection the HBM
        admission gate multiplies by a request's token need."""
        if self._params is None:
            return 0
        _, cfg = self._params
        per = cfg.n_layers * 2 * cfg.n_heads * cfg.head_dim
        return per * (1 if self._kv_quant else 2)

    def _gate_hbm(self, need_s: int) -> None:
        """HBM-headroom admission (server/memory.py) for allocations that
        are genuinely NEW device memory: independent mode's fresh
        per-sequence cache.  Runs BEFORE the allocation so a refused
        request touches no cache state."""
        gov = self._memory_governor
        if gov is None:
            return
        gov.admit_hbm(self._model.name,
                      int(need_s) * self._kv_bytes_per_token())

    def _gate_hbm_slab(self) -> None:
        """Slot-mode HBM gate: the shared slab cache is preallocated ONCE
        (lazily, at the first request's ``_ensure_fns``), so THAT
        allocation — the full every-bucket footprint — is what must fit
        the live headroom.  Once the slab is resident, admitting a
        request into a free slot pins no new device memory and the gate
        is inert: a per-admission projection would double-count bytes
        already inside ``bytes_in_use`` and spuriously shed all traffic
        on a well-sized device."""
        gov = self._memory_governor
        if gov is None or self._fns is not None:
            return
        # config only (weights load either way at _ensure_fns; the slab
        # arrays are what this gate keeps off a too-full device)
        self._ensure_params()
        slab_tokens = sum(cnt * cap for cnt, cap in self._buckets)
        gov.admit_hbm(self._model.name,
                      slab_tokens * self._kv_bytes_per_token())

    # -- lazy init ---------------------------------------------------------
    def _ensure_params(self):
        """Shared weight init (same seed/config for both modes).

        ``TRITON_TPU_QUANT=int8`` applies weight-only int8 quantization to
        the layer matmul weights (see quantize_layer_weights) — both the
        decode and generate paths then serve the quantized model."""
        if self._params is None:
            cfg = self._language._llama_cfg()
            params = tr.init_params(jax.random.PRNGKey(3), cfg)
            # resolve_quant: per-model TRITON_TPU_QUANT_<MODEL> override,
            # unknown names fail loudly, not silently-fp
            quant = tr.resolve_quant(self._model.name)
            if quant == "int8":
                params = quantize_layer_weights(params, cfg)
            else:
                # serving-grade storage: init_params returns f32 master
                # weights (training-grade), but decode is weight-bandwidth-
                # bound — storing the compute dtype (bf16) halves the bytes
                # every step pulls from HBM.  Every kept leaf is already
                # cast to cfg.dtype at compute time, so values are
                # unchanged; 'head' stays f32 because _head's matmul runs
                # in f32 (preserves first-token bit-identity with the
                # llama_tpu window model — tests/test_decode.py).
                params = {k: (v.astype(cfg.dtype)
                              if k != "head"
                              and getattr(v, "dtype", None) == jnp.float32
                              else v)
                          for k, v in params.items()}
            # commit to the serve mesh: GSPMD partitions the jitted
            # prefill/step from these shardings (tp over heads; one-device
            # mesh when TRITON_TPU_SERVE_MESH is unset)
            # dp shards the slot axis of EVERY bucket's cache array, so the
            # divisibility constraint is the gcd of the bucket counts (=
            # n_slots when unbucketed)
            div = 0
            for cnt, _cap in self._buckets:
                div = math.gcd(div, cnt)
            desc = None
            if len(self._buckets) > 1:
                desc = ("every cache bucket's slot count "
                        f"(gcd {div} of {self._n_slots} slots; "
                        "TRITON_TPU_DECODE_BUCKETS)")
            mesh = decode_mesh(cfg, n_slots=div,
                               model_name=self._model.name,
                               slots_desc=desc)
            params = place_decode_params(params, mesh, cfg)
            self._mesh = mesh
            self._params = (params, cfg)
        return self._params

    def _ensure_fns(self):
        # double-checked: concurrent cold-start sequences must not each
        # init a full parameter set (gigabytes at the 1b preset)
        if self._fns is None:
            with self._init_lock:
                if self._fns is None:
                    import os
                    import queue as _queue

                    import numpy as np

                    params, cfg = self._ensure_params()
                    # slot cache on the serve mesh: slots over dp, heads
                    # over tp (mirrors the K/V the tp-sharded wk/wv produce
                    # so the cache write needs no resharding); one array
                    # (or int8 {q,s} pair) per slab bucket — every shape
                    # stays static.  dp divides every bucket count by
                    # construction: decode_mesh was built against the gcd
                    self._k, self._v, self._dstate = [], [], []
                    self._zero_mask, self._zero_tok = [], []
                    for cnt, cap in self._buckets:
                        kb, vb = self._new_cache_arrays(cnt, cap, cfg)
                        self._k.append(kb)
                        self._v.append(vb)
                        # device-resident control state (tokens/prev/pos/
                        # active/auto/remaining): donated through the
                        # fused tick and updated by the kernel itself, so
                        # steady-state generation uploads nothing per tick
                        self._dstate.append(_new_decode_state(cnt))
                        # cached zeros for pure-generation dispatches: a
                        # tick with no client-driven steps reuses these
                        # device arrays instead of paying an H2D upload
                        self._zero_mask.append(jnp.zeros(cnt, bool))
                        self._zero_tok.append(jnp.zeros(cnt, jnp.int32))
                    # worker-owned self-feeding slot registry
                    self._auto_slots = {}
                    # (slot, gen) pairs whose sink resolution failed; the
                    # worker reaps them (lock-guarded: resolvers write)
                    self._dead_gens = set()
                    # bound device dispatch ahead of readbacks
                    self._tick_budget = self._threading.Semaphore(4)
                    self._pos = np.zeros(self._n_slots, np.int32)
                    self._jobs = _queue.Queue()
                    import concurrent.futures as _cf

                    self._readers = _cf.ThreadPoolExecutor(
                        max_workers=4,
                        thread_name_prefix=f"{self._model.name}-readback")
                    # generation sinks REQUIRE per-slot ordering (a token
                    # landing after the end sentinel would be dropped), so
                    # their resolutions serialize on one dedicated thread
                    self._gen_reader = _cf.ThreadPoolExecutor(
                        max_workers=1,
                        thread_name_prefix=f"{self._model.name}-gen")
                    self._worker = self._threading.Thread(
                        target=self._worker_loop, daemon=True,
                        name=f"{self._model.name}-decode-worker")
                    # chunked prefill (TRITON_TPU_PREFILL_CHUNK tokens per
                    # tick; 0 = whole-prompt): lets a new prompt interleave
                    # with decode ticks instead of stalling the cohort
                    chunk = int(os.environ.get("TRITON_TPU_PREFILL_CHUNK",
                                               "0"))
                    if chunk < 0 or (chunk and self._prompt_len % chunk):
                        raise ValueError(
                            f"TRITON_TPU_PREFILL_CHUNK={chunk} must be 0 "
                            f"or a divisor of prompt_len="
                            f"{self._prompt_len}")
                    self._prefill_chunk = chunk
                    self._chunk_fn = (
                        make_slot_chunk_prefill(cfg, self._s_max)
                        if chunk else None)
                    # penalty state (lazy: allocated when a penalized
                    # generation is first admitted; unpenalized buckets
                    # keep the legacy kernels and pay nothing)
                    self._pen_counts = [None] * len(self._buckets)
                    self._pen_fp = [np.zeros(c, np.float32)
                                    for c, _ in self._buckets]
                    self._pen_pp = [np.zeros(c, np.float32)
                                    for c, _ in self._buckets]
                    # device-resident penalty scalars (per slot, updated
                    # at admission/release — the per-tick fp/pp uploads
                    # are gone with the rest of the control state)
                    self._pen_fp_dev = [jnp.zeros(c, jnp.float32)
                                        for c, _ in self._buckets]
                    self._pen_pp_dev = [jnp.zeros(c, jnp.float32)
                                        for c, _ in self._buckets]
                    self._pen_n = [0] * len(self._buckets)
                    self._slot_pen_seed = {}  # slot -> (fp, pp, row np)
                    self._prefill_pen_fn = make_slot_prefill_pen(cfg)
                    # the fused multi-step tick kernels (T from
                    # TRITON_TPU_DECODE_STEPS; T=1 == the legacy
                    # single-step tick, same math either way)
                    self._fused_fn = make_fused_slot_step(
                        cfg, self._decode_steps)
                    self._fused_pen_fn = make_fused_slot_step_pen(
                        cfg, self._decode_steps)
                    # content-addressed prefix cache: active only when a
                    # byte budget is configured AND the KV store is exact
                    # (int8 KV quant attends over DEQUANTIZED prefix reads
                    # on the tail path, which cannot reproduce the cold
                    # full-prefill's exact attention bit-for-bit — the
                    # cache stays off rather than breaking the hit-vs-cold
                    # bit-identity contract)
                    self._setup_prefix_cache(cfg)
                    fns = (make_slot_prefill(cfg), params, cfg)
                    self._fns = fns
                    self._worker.start()
                    # tick-stall watchdog: armed only when the operator
                    # set --tick-stall-ms (env TRITON_TPU_TICK_STALL_MS);
                    # unarmed, _watch_readback returns None and the hot
                    # path pays a single float compare per dispatch
                    self._stall_s = float(os.environ.get(
                        "TRITON_TPU_TICK_STALL_MS", "0")) / 1e3
                    if self._stall_s > 0.0:
                        self._threading.Thread(
                            target=self._watchdog_loop,
                            name=("tc-tpu-stall-watch-"
                                  f"{self._model.name}"),
                            daemon=True).start()
        return self._fns

    def _setup_prefix_cache(self, cfg) -> None:
        """Resolve the model's prefix/KV block-store wiring (both modes
        call this under _init_lock).  No-op when the cache is disabled:
        budget 0, or int8 KV quantization (whose dequantized prefix reads
        would break hit-vs-cold bit-identity — see _ensure_fns)."""
        from ..server import kvcache

        if self._kv_quant:
            return
        cache = kvcache.for_model(self._model.name,
                                  governor=self._memory_governor,
                                  ledger=self._cost_ledger)
        if cache is None:
            return
        self._kv_cache = cache
        ext, ins, ins_run = make_cache_block_ops(cache.block_tokens)
        self._cache_extract_fn = ext
        self._cache_insert_fn = ins
        self._cache_insert_run_fn = ins_run
        # the tail prefill after a hit is exactly a chunk prefill at
        # pos0 = hit_tokens (jit re-specializes per tail width); reuse
        # the chunked-prefill kernel when the operator enabled it
        self._cache_tail_fn = (self._chunk_fn
                               or make_slot_chunk_prefill(cfg, self._s_max))

    def _shutdown(self):
        from ..server import kvcache

        with self._lock:
            self._closed = True
        if self._jobs is not None:
            self._jobs.put(None)
        # the store's governor reservation must not outlive the model
        kvcache.drop(self._model.name)
        self._kv_cache = None

    def _ensure_fns_independent(self):
        if self._fns_ind is None:
            with self._init_lock:
                if self._fns_ind is None:
                    params, cfg = self._ensure_params()
                    self._setup_prefix_cache(cfg)
                    if self._kv_cache is not None:
                        self._ind_tail_fn = make_prefill_tail(
                            cfg, self._s_max)
                    self._fns_ind = (make_prefill(cfg, self._s_max),
                                     make_decode_step(cfg), params, cfg)
        return self._fns_ind

    # -- slot bookkeeping (under self._lock) -------------------------------
    def _slot_bucket(self, slot: int):
        """Global slot id -> (bucket index, bucket-local index)."""
        for b in range(len(self._buckets) - 1, -1, -1):
            off = self._bucket_off[b]
            if slot >= off:
                return b, slot - off
        raise ValueError(f"slot {slot} out of range")

    def _slot_cap(self, slot: int) -> int:
        return self._buckets[self._slot_bucket(slot)[0]][1]

    def _alloc_slot_locked(self, need_s: int, prefer_large: bool = False):
        """Pop a free slot whose slab holds ``need_s`` tokens, or None.

        Generations (known length) fill smallest-fitting-first so short
        requests never burn a long slab; sequences (open-ended length)
        prefer the largest CAP so they keep maximum headroom before the
        cap error asks for sequence_end — but among same-cap pools both
        break ties toward the FIRST pool, keeping live slots packed in
        the fewest buckets (each active bucket is its own device step
        per tick; see parse_cache_buckets)."""
        order = range(len(self._buckets))
        if prefer_large:
            order = sorted(order,
                           key=lambda i: (-self._buckets[i][1], i))
        for b in order:
            cnt, cap = self._buckets[b]
            if cap < need_s:
                continue
            off = self._bucket_off[b]
            for slot in range(off, off + cnt):
                if slot in self._free:
                    self._free.discard(slot)
                    return slot
        return None

    def _evict_idle_locked(self, now: float) -> None:
        stale = [k for k, t in self._touched.items()
                 if now - t > self._idle_s]
        for key in stale:
            self._release_entry_locked(key)

    def _release_locked(self, seq_id) -> None:
        self._release_entry_locked(seq_id)

    def _release_entry_locked(self, seq_id) -> None:
        slot = self._state.pop(seq_id, None)
        if isinstance(slot, int):  # independent mode stores caches, not slots
            self._free.add(slot)
            # invalidate any job still queued for this slot: the worker
            # checks the generation and fails stale steps instead of
            # writing a dead sequence's K/V into the slot's next occupant
            self._slot_gen[slot] += 1
            self._slot_tenant.pop(slot, None)
            self._kv_unpin_charge(self._slot_kv_pin.pop(slot, None))
        self._touched.pop(seq_id, None)
        self._seq_locks.pop(seq_id, None)

    # -- worker: single owner of the cache ---------------------------------
    # accumulation window per tick; small vs a ~100 ms batched step but
    # enough for a whole response cohort's next requests to arrive
    TICK_ACCUMULATE_S = 0.004

    def _worker_loop(self):
        import queue as _queue
        import time

        import numpy as np

        prefill, params, cfg = self._fns

        def fail_stale(fut):
            from ..server.types import InferError

            fut.set_exception(InferError(
                f"model '{self._model.name}': sequence was evicted or "
                "ended before this request executed"))

        def deliver_error(completion, err):
            """Route a failure to a prefill completion: futures directly,
            generation sinks through the ordered gen reader (an error put
            racing ahead of an already-queued token would truncate the
            stream)."""
            if completion[0] == "fut":
                completion[1].set_exception(err)
            else:
                self._gen_reader.submit(completion[2].put, err)

        def drain_and_fail():
            from ..server.types import InferError

            err = InferError(
                f"model '{self._model.name}' is unloading", 503)
            while True:
                try:
                    j = self._jobs.get_nowait()
                except _queue.Empty:
                    break
                if j is None:
                    continue
                if j[0] in ("prefill", "prefill_cont"):
                    deliver_error(j[1][-1], err)
                elif j[0] == "recover":
                    self._gen_reader.submit(j[1][0].put, err)
                elif j[0] == "step":
                    j[2].set_exception(err)
            for slot, info in self._auto_slots.items():
                self._gen_reader.submit(info["sink"].put, err)
            self._auto_slots.clear()

        def begin_prefill_trace(completion):
            """Gen-path lifecycle spans (host-side, worker thread): the
            moment this generation's prefill starts closes its SLOT_WAIT
            stage (submit -> worker pickup, including slot allocation).
            Idempotent across chunked-prefill continuations — only the
            FIRST chunk opens the prefill window."""
            if completion[0] != "gen":
                return
            sink = completion[2]
            tr = getattr(sink, "trace", None)
            if tr is None or getattr(sink, "t_prefill0", None) is not None:
                return
            now = time.monotonic_ns()
            tr.add_span("SLOT_WAIT", sink.t_submit, now)
            sink.t_prefill0 = now

        def finish_prefill(slot, gen, win_len, nxt_dev, best_dev, lp_dev,
                           completion):
            """Prefill finished: deliver the first token.  Sequence path
            resolves the client future; generation path streams the token
            (with its logprob), seeds the device-side feedback for tick 1,
            and registers the slot as self-feeding."""
            self._pos[slot] = win_len
            b, li = self._slot_bucket(slot)
            if completion[0] == "fut":
                # sequence slot: seed the device-side position (its
                # client-driven steps advance it in-kernel from here);
                # stays inactive — each step arrives via the dispatch mask
                self._dstate[b] = _state_admit(
                    self._dstate[b], li, nxt_dev, win_len, False, 0)
                pair = start_readback(
                    jnp.stack([nxt_dev.astype(jnp.float32), best_dev]))
                # pipelined like step readbacks: the blocking D2H must not
                # stall the tick loop for a device round trip
                self._readers.submit(self._resolve_prefill, pair,
                                     completion[1])
                return
            _tag, n_tokens, sink = completion
            if getattr(sink, "_recovering", False):
                # a recovery re-prefill just landed: the resumed stream
                # is live again.  Count the sequence recovered, stamp the
                # flight record, and charge the re-prefill's wall window
                # to the owning tenant — attribution is the ledger's
                # contract, and these are the tenant's tokens recomputed
                # (operators see the fault itself via nv_device_fault).
                sink._recovering = False
                self._report_recovered()
                st = getattr(sink, "trace", None)
                if st is not None and st.flight is not None:
                    st.flight.recovered = True
                ledger = self._cost_ledger
                if ledger is not None and ledger.enabled:
                    dt_us = (time.monotonic() - sink._recover_t0) * 1e6
                    ledger.charge(self._model.name,
                                  getattr(sink, "tenant", ""),
                                  device_us=dt_us, tokens=0)
            tr = getattr(sink, "trace", None)
            if tr is not None:
                now = time.monotonic_ns()
                if getattr(sink, "t_prefill0", None) is not None:
                    # covers every chunk of a chunked prefill: opened at
                    # the first chunk, closed when the final chunk's
                    # dispatch returned
                    span = tr.add_span("PREFILL", sink.t_prefill0, now)
                    hit = getattr(sink, "cache_hit_tokens", 0)
                    if hit:
                        # the prefix-cache collapse, visible per sequence:
                        # trace_summary/Perfetto read this to show how much
                        # of the prompt the span did NOT recompute
                        span.set_attr("cached_tokens", int(hit))
                # the DECODE stage opens here and closes when the last
                # token resolves (or the consumer cancels)
                sink.t_decode0 = now
            # self-feeding generation: activate the slot on device with
            # its feedback token and remaining budget — the fused tick
            # deactivates it on device when the budget drains
            self._dstate[b] = _state_admit(
                self._dstate[b], li, nxt_dev, win_len, n_tokens > 1,
                n_tokens - 1)
            pair = start_readback(
                jnp.stack([nxt_dev.astype(jnp.float32), lp_dev]))
            self._gen_reader.submit(self._resolve_gen_token, pair,
                                    sink, n_tokens == 1, slot, gen,
                                    self._watch_readback("prefill"))
            if n_tokens > 1:
                self._auto_slots[slot] = {
                    "remaining": n_tokens - 1, "sink": sink, "gen": gen}
            else:
                self._release_gen_slot(slot)

        def reap_dead_gens():
            """Drop self-feeding slots whose sink resolution failed — the
            consumer already got the error; without this the worker would
            tick a dead generation to completion while new submissions 429
            against its slot."""
            with self._lock:
                dead = list(self._dead_gens)
                self._dead_gens.clear()
            for slot, gen in dead:
                info = self._auto_slots.get(slot)
                if info is not None and info["gen"] == gen:
                    self._auto_slots.pop(slot)
                    self._deactivate_slot(slot)
                    self._release_gen_slot(slot)

        def retire_cancelled(slot, sink):
            """One place for cancelled-generation bookkeeping: free the slot
            (stopping its device-side self-feed) and end the (departed)
            consumer's sink stream."""
            self._deactivate_slot(slot)
            self._release_gen_slot(slot)
            # close the DECODE stage at the cancel point.  Best effort:
            # the stream envelope's cancelled record usually emits before
            # the worker notices the disconnect (the trace then shows the
            # stage open-ended — its extent is still readable from the
            # token timeline); the close matters when the reap wins the
            # race, and it always keeps t_decode0 from leaking into a
            # later occupant of the sink object
            self._close_decode_span(sink)
            self._gen_reader.submit(sink.put, None)

        def gen_was_cancelled(slot, completion) -> bool:
            """A queued prefill whose consumer already left: retire it
            before spending device time."""
            if (completion[0] == "gen"
                    and getattr(completion[2], "cancelled", False)):
                retire_cancelled(slot, completion[2])
                return True
            return False

        def reap_cancelled_gens():
            """Free self-feeding slots whose consumer went away (client
            disconnect, stop-sequence hit): the sink carries a ``cancelled``
            flag set by the generate layer; ticking such a slot to
            completion would burn device steps nobody reads while new
            submissions 429 against it."""
            for slot in list(self._auto_slots):
                info = self._auto_slots[slot]
                if getattr(info["sink"], "cancelled", False):
                    self._auto_slots.pop(slot)
                    retire_cancelled(slot, info["sink"])

        while True:
            if self._dead_gens:
                reap_dead_gens()
            if self._auto_slots:
                reap_cancelled_gens()
            if self._auto_slots:
                # self-feeding generations in flight: never block — tick
                # them even when no client job is queued
                try:
                    job = self._jobs.get_nowait()
                except _queue.Empty:
                    job = ("tick", None, None)
            else:
                job = self._jobs.get()
            if job is None:
                drain_and_fail()
                return
            kind, payload, fut = job
            # One prefill flow serves both completions: ("fut", future) for
            # the sequence protocol, ("gen", n_tokens, sink) for the
            # self-feeding generation path.
            if kind == "prefill":
                slot, gen, win, completion = payload
                if gen != self._slot_gen[slot]:
                    if completion[0] == "gen":
                        # a queued generation only goes stale via a bucket
                        # rebuild (gen slots carry no seq id, so idle
                        # eviction never touches them): recover it instead
                        # of failing a stream the fault didn't have to kill
                        self._recover_handoff(completion[2])
                    else:
                        deliver_error(completion,
                                      _stale_error(self._model.name))
                    continue
                if gen_was_cancelled(slot, completion):
                    continue
                begin_prefill_trace(completion)
                C = self._prefill_chunk
                b, li = self._slot_bucket(slot)
                with self._lock:
                    seed = self._slot_pen_seed.pop(slot, None)
                kvc = self._kv_cache
                tenant = (getattr(completion[2], "tenant", "")
                          if completion[0] == "gen"
                          else self._slot_tenant.get(slot, ""))
                hit, blocks, phash = 0, None, None
                try:
                    self._maybe_inject_device_fault(b)
                    if seed is not None:
                        # penalized generation: first token must respect
                        # the prompt counts (full prefill — chunking would
                        # need a penalized final-chunk head; capacity, not
                        # contention, is what penalties ride the tick for)
                        fp, pp, row = seed
                        self._ensure_pen_bucket(b)
                        (nxt, best, lp, self._k[b], self._v[b],
                         new_row) = self._prefill_pen_fn(
                            params, self._k[b], self._v[b],
                            jnp.asarray(win), li, jnp.asarray(row),
                            jnp.float32(fp), jnp.float32(pp))
                        self._pen_counts[b] = \
                            self._pen_counts[b].at[li].set(new_row)
                        # device-resident penalty scalars: written ONCE at
                        # admission (and zeroed at release) instead of
                        # re-uploaded every tick
                        self._pen_fp_dev[b] = \
                            self._pen_fp_dev[b].at[li].set(fp)
                        self._pen_pp_dev[b] = \
                            self._pen_pp_dev[b].at[li].set(pp)
                        with self._lock:
                            self._pen_fp[b][li] = fp
                            self._pen_pp[b][li] = pp
                            self._pen_n[b] += 1
                        finish_prefill(slot, gen, win.shape[1], nxt, best,
                                       lp, completion)
                        continue
                    if kvc is not None:
                        # longest cached block chain for this window
                        # (host-side hashing over the already-host array;
                        # matched blocks stay refcounted until their
                        # slab inserts are dispatched below).  Penalized
                        # admissions bypass the cache: their first token
                        # rides the penalized full-prefill kernel.
                        hit, blocks, phash = kvc.match(win[0])
                        self._stamp_cache_hit(completion, hit, phash)
                    if hit:
                        # restore the cached prefix verbatim into this
                        # slot's slab lane, then prefill ONLY the tail —
                        # the chunk-prefill contract (exactly reproducing
                        # full-prompt prefill) makes the stream
                        # bit-identical to a cold run
                        self._k[b], self._v[b] = self._cache_insert_run_fn(
                            self._k[b], self._v[b],
                            tuple(blk.k for blk in blocks),
                            tuple(blk.v for blk in blocks), li, 0)
                        (nxt, best, lp, self._k[b],
                         self._v[b]) = self._cache_tail_fn(
                            params, self._k[b], self._v[b],
                            jnp.asarray(win[:, hit:]), li, hit)
                        kvc.release(blocks)
                        blocks = None
                        self._cache_commit(win, hit, b, li, tenant)
                        finish_prefill(slot, gen, win.shape[1], nxt, best,
                                       lp, completion)
                        continue
                    if C and win.shape[1] > C:
                        # chunked: run the first chunk now, re-enqueue the
                        # continuation at the queue tail so pending decode
                        # steps tick in between (no cohort-wide stall)
                        _, _, _, self._k[b], self._v[b] = self._chunk_fn(
                            params, self._k[b], self._v[b],
                            jnp.asarray(win[:, :C]), li, 0)
                        self._jobs.put(("prefill_cont",
                                        (slot, gen, win, C, completion),
                                        None))
                        continue
                    nxt, best, lp, self._k[b], self._v[b] = prefill(
                        params, self._k[b], self._v[b], jnp.asarray(win), li)
                    self._cache_commit(win, 0, b, li, tenant)
                    finish_prefill(slot, gen, win.shape[1], nxt, best, lp,
                                   completion)
                except Exception as e:  # noqa: BLE001 — via completion
                    if blocks:
                        kvc.release(blocks)
                    self._report_fault("prefill", reason=str(e))
                    if completion[0] == "gen":
                        # server-side generation: hand to the recovery
                        # queue (re-admit + re-prefill) instead of
                        # failing the stream; client-driven sequences
                        # fail fast as before — only the client can
                        # replay its step protocol
                        self._recover_handoff(completion[2])
                    else:
                        deliver_error(completion, e)
                    # rebuild frees + bumps every slot in the bucket (incl.
                    # this gen slot) atomically; no separate release here
                    self._rebuild_bucket_cache(b)
                continue
            if kind == "prefill_cont":
                slot, gen, win, pos0, completion = payload
                if gen != self._slot_gen[slot]:
                    if completion[0] == "gen":
                        # a queued generation only goes stale via a bucket
                        # rebuild (gen slots carry no seq id, so idle
                        # eviction never touches them): recover it instead
                        # of failing a stream the fault didn't have to kill
                        self._recover_handoff(completion[2])
                    else:
                        deliver_error(completion,
                                      _stale_error(self._model.name))
                    continue
                if gen_was_cancelled(slot, completion):
                    continue
                C = self._prefill_chunk
                b, li = self._slot_bucket(slot)
                try:
                    self._maybe_inject_device_fault(b)
                    nxt, best, lp, self._k[b], self._v[b] = self._chunk_fn(
                        params, self._k[b], self._v[b],
                        jnp.asarray(win[:, pos0:pos0 + C]), li, pos0)
                    if pos0 + C < win.shape[1]:
                        self._jobs.put(("prefill_cont",
                                        (slot, gen, win, pos0 + C,
                                         completion), None))
                        continue
                    # final chunk: the slab now holds the whole window —
                    # commit its complete blocks to the prefix store
                    self._cache_commit(
                        win, 0, b, li,
                        getattr(completion[2], "tenant", "")
                        if completion[0] == "gen"
                        else self._slot_tenant.get(slot, ""))
                    finish_prefill(slot, gen, win.shape[1], nxt, best, lp,
                                   completion)
                except Exception as e:  # noqa: BLE001 — via completion
                    self._report_fault("prefill", reason=str(e))
                    if completion[0] == "gen":
                        # partial prefill died with the cache: recovery
                        # restarts the prompt from scratch (nothing was
                        # emitted yet, so the resume is trivially exact)
                        self._recover_handoff(completion[2])
                    else:
                        deliver_error(completion, e)
                    self._rebuild_bucket_cache(b)
                continue
            if kind == "recover":
                # Re-admit a generation whose bucket a device fault took
                # down: prefill ``prompt + emitted_so_far`` into a fresh
                # slot and let it self-feed the REMAINING budget.  Greedy
                # decode is deterministic in the token prefix, so the
                # resumed stream is bit-identical to the one the fault
                # interrupted — the consumer never notices beyond added
                # latency.  ``emitted`` is the gen-reader-thread snapshot
                # taken at handoff (see _recover_handoff for why it is
                # exact).
                from ..server.types import InferError

                sink, emitted = payload
                if getattr(sink, "cancelled", False):
                    self._close_decode_span(sink)
                    self._gen_reader.submit(sink.put, None)
                    continue
                if self._closed:
                    # the fault closed the model (unrebuildable cache →
                    # quarantine → shutdown): re-admitting into a dead
                    # worker would hang the stream forever
                    sink.failed = True
                    self._report_aborted()
                    self._gen_reader.submit(sink.put, InferError(
                        f"model '{self._model.name}' is unloading", 503))
                    continue
                remaining = sink.n_tokens_total - len(emitted)
                if remaining <= 0:
                    # fully emitted before the fault; only the stream-end
                    # sentinel was outstanding
                    self._close_decode_span(sink)
                    self._gen_reader.submit(sink.put, None)
                    continue
                win = sink.window
                if emitted:
                    # np.fromiter, not np.asarray: these are host-side
                    # Python ints (DEVICE-SYNC keeps blocking conversions
                    # out of the worker loop, and this one never was one)
                    win = np.concatenate(
                        [win, np.fromiter((t for t, _lp in emitted),
                                          dtype=win.dtype,
                                          count=len(emitted))
                              .reshape(1, -1)], axis=1)
                # prompt+emitted+remaining == the original admission size,
                # so the resume lands in the same bucket class
                need_s = int(win.shape[1]) + int(remaining)
                use_pen = sink.freq_pen != 0.0 or sink.pres_pen != 0.0
                with self._lock:
                    slot = self._alloc_slot_locked(need_s)
                    if slot is None:
                        self._evict_idle_locked(time.monotonic())
                        slot = self._alloc_slot_locked(need_s)
                    if slot is not None:
                        gen = self._slot_gen[slot]
                        self._slot_tenant[slot] = sink.tenant
                        if use_pen:
                            # reseed the penalty counts from the REAL
                            # prompt plus everything already emitted —
                            # the same state the interrupted slot's
                            # device-side count row had reached
                            pl = sink.prompt_len
                            real = (sink.window[
                                0, sink.window.shape[1] - pl:]
                                if pl else np.zeros(0, np.int32))
                            toks = np.fromiter(
                                (t for t, _lp in emitted), np.int32,
                                count=len(emitted))
                            row = np.bincount(
                                np.concatenate([real, toks]),
                                minlength=cfg.vocab_size).astype(np.int32)
                            self._slot_pen_seed[slot] = (
                                float(sink.freq_pen),
                                float(sink.pres_pen), row)
                if slot is None:
                    # the freed bucket was re-claimed by new admissions
                    # before recovery ran: budget the failure honestly
                    sink.failed = True
                    self._report_aborted()
                    self._gen_reader.submit(sink.put, InferError(
                        f"model '{self._model.name}': no free decode "
                        "slot for device-fault recovery; generation "
                        "aborted", 500))
                    continue
                self._kv_pin_slot(slot, need_s, sink.tenant)
                # recovery accounting closes at the re-prefill's
                # finish_prefill; t_prefill0 resets so the trace shows
                # the second SLOT_WAIT/PREFILL pair
                sink._recovering = True
                sink._recover_t0 = time.monotonic()
                sink.t_prefill0 = None
                self._jobs.put(("prefill",
                                (slot, gen, win,
                                 ("gen", remaining, sink)), None))
                continue
            # Merge steps into this tick. A short accumulation window is
            # load-bearing: the previous tick resolves every stream's
            # future at once, and their next requests all land a couple of
            # milliseconds later — grabbing only what is instantly queued
            # would start a near-empty (but full-price) tick and make the
            # cohort wait a whole extra one. Non-step jobs defer one tick.
            batch = []
            seen = set()
            deferred = []
            closing = False

            def admit(p, f):
                slot, gen, tok = p
                if gen != self._slot_gen[slot]:
                    fail_stale(f)
                    return
                batch.append(((slot, tok), f))
                seen.add(slot)

            if kind == "step":
                admit(payload, fut)
                deadline = time.monotonic() + self.TICK_ACCUMULATE_S
                while len(seen) < self._n_slots and not closing:
                    timeout = deadline - time.monotonic()
                    if timeout <= 0:
                        break
                    try:
                        nxt_job = self._jobs.get(timeout=timeout)
                    except _queue.Empty:
                        break
                    if nxt_job is None:
                        deferred.append(None)
                        closing = True
                        break
                    k2, p2, f2 = nxt_job
                    if k2 == "step" and p2[0] not in seen:
                        admit(p2, f2)
                    else:
                        deferred.append(nxt_job)
                for d in deferred:
                    self._jobs.put(d)
            if not batch and not self._auto_slots:
                continue
            t_asm0 = time.monotonic_ns()
            queue_depth = self._jobs.qsize()
            # group this tick's work by slab bucket — each bucket is its
            # own static-shape device dispatch (one when unbucketed)
            work = [None] * len(self._buckets)

            def bucket_work(b):
                if work[b] is None:
                    # tokens/mask stay None until a client step needs
                    # them: the steady-state pure-generation tick must
                    # not pay two host allocations per dispatch
                    work[b] = {"tokens": None, "mask": None,
                               "batch": [], "gens": []}
                return work[b]

            for (slot, tok), f in batch:
                b, li = self._slot_bucket(slot)
                w = bucket_work(b)
                if w["tokens"] is None:
                    cnt = self._buckets[b][0]
                    w["tokens"] = np.zeros(cnt, np.int32)
                    w["mask"] = np.zeros(cnt, bool)
                w["tokens"][li] = tok
                w["mask"][li] = True
                w["batch"].append((li, f))
            for slot in list(self._auto_slots):
                info = self._auto_slots[slot]
                if info["gen"] != self._slot_gen[slot]:
                    # slot invalidated (cache rebuild) while self-feeding:
                    # whoever bumped the gen already errored the sink
                    self._auto_slots.pop(slot)
                    continue
                b, li = self._slot_bucket(slot)
                bucket_work(b)["gens"].append((slot, li))
            T = self._decode_steps
            for b, w in enumerate(work):
                if w is None:
                    continue
                cnt, cap = self._buckets[b]
                off = self._bucket_off[b]
                # bound how far device dispatch runs ahead of readbacks: a
                # pure-auto loop would otherwise enqueue ticks unboundedly
                self._tick_budget.acquire()
                uploads = 0
                if w["batch"]:
                    # the ONLY per-tick H2D control uploads left: this
                    # dispatch's client-driven tokens and their slot mask
                    # — fresh arrays built above, never mutated after
                    # dispatch, so async capture is safe.  Pure-generation
                    # ticks (the steady-state hot path) take the else
                    # branch: cached device zeros, ZERO uploads.
                    step_tokens = jnp.asarray(w["tokens"])
                    step_mask = jnp.asarray(w["mask"])
                    uploads = 2
                else:
                    step_tokens = self._zero_tok[b]
                    step_mask = self._zero_mask[b]
                # host control-path cost split: assembly (job collection
                # + upload prep, ends HERE) vs the dispatch call below —
                # on CPU the jit call blocks on compute, so folding it
                # into assembly would make the host-overhead counter lie
                t_disp0 = time.monotonic_ns()
                try:
                    self._maybe_inject_device_fault(b)
                    if self._pen_n[b] > 0:
                        # >=1 penalized generation in this bucket: the
                        # penalized tick (per-slot counts + device-resident
                        # fp/pp, zero rows degenerate to the plain head for
                        # everyone else); unpenalized buckets never pay it
                        (self._k[b], self._v[b], self._dstate[b], out,
                         _steps_dev, self._pen_counts[b]) = \
                            self._fused_pen_fn(
                                params, self._k[b], self._v[b],
                                self._dstate[b], step_mask, step_tokens,
                                self._pen_counts[b], self._pen_fp_dev[b],
                                self._pen_pp_dev[b])
                    else:
                        (self._k[b], self._v[b], self._dstate[b], out,
                         _steps_dev) = self._fused_fn(
                            params, self._k[b], self._v[b],
                            self._dstate[b], step_mask, step_tokens)
                    # prefetch the [3, T, B] token block NOW: the resolver
                    # thread then finds the one fused D2H already in
                    # flight, so readbacks overlap later dispatches
                    # instead of costing one RTT each
                    start_readback(out)
                    for li, _f in w["batch"]:
                        self._pos[off + li] += 1
                except Exception as e:  # noqa: BLE001 — via futures
                    self._tick_budget.release()
                    self._report_fault("step", reason=str(e))
                    for _li, f in w["batch"]:
                        f.set_exception(e)
                    # the bucket's live generations (w["gens"] exactly)
                    # are handed to the recovery queue by the rebuild —
                    # not aborted here; only client-driven step futures
                    # fail fast (the client owns that replay protocol)
                    self._rebuild_bucket_cache(b)
                    # the next bucket's assembly window must not absorb
                    # this failed dispatch + cache rebuild
                    t_asm0 = time.monotonic_ns()
                    continue
                # host-side advance prediction — the "periodically
                # refreshed mirror" is in fact EXACT: greedy decode has no
                # data-dependent stop inside the kernel, so an auto slot
                # advances precisely min(T, remaining, cap - pos) steps
                # (the kernel deactivates it on device at the same step
                # the host predicts), and a client-driven slot advances 1.
                # No device readback feeds admission/eviction decisions.
                steps_run = 1 if w["batch"] else 0
                gen_batch = []
                for slot, li in w["gens"]:
                    info = self._auto_slots[slot]
                    adv = min(T, info["remaining"],
                              cap - int(self._pos[slot]))
                    self._pos[slot] += adv
                    info["remaining"] -= adv
                    steps_run = max(steps_run, adv)
                    done = (info["remaining"] <= 0
                            or int(self._pos[slot]) >= cap)
                    if done:
                        # the kernel already deactivated the slot on
                        # device; the readback snapshot keeps its values
                        # valid even if a later tick reuses the slot
                        self._auto_slots.pop(slot)
                        self._release_gen_slot(slot)
                    gen_batch.append((li, slot, info["sink"], adv, done,
                                      info["gen"]))
                t_done = time.monotonic_ns()
                self._tick_seq += 1
                tick_seq = self._tick_seq
                ds = self._device_stats
                ledger = self._cost_ledger
                want_cost = (ledger is not None and ledger.enabled)
                tick_cost = None
                if (ds is not None and ds.enabled) or want_cost:
                    tick_cost = self._fused_tick_cost(
                        b, params, step_mask, step_tokens)
                if ds is not None and ds.enabled:
                    # one tick row per fused dispatch: steps-per-dispatch
                    # and control-upload counters are the measurable form
                    # of the fast path (gen_tick_breakdown / triton-top
                    # buckets view / the no-upload regression test)
                    ds.record_tick(
                        self._model.name, bucket=cap,
                        batch=len(w["batch"]) + len(w["gens"]),
                        padded=cnt, queue_depth=queue_depth,
                        assembly_ns=t_disp0 - t_asm0,
                        compute_ns=t_done - t_disp0,
                        requests=len(w["batch"]), syncs=1,
                        steps=steps_run, uploads=uploads,
                        tick_seq=tick_seq,
                        flops=tick_cost.flops if tick_cost else 0.0,
                        bytes_accessed=(tick_cost.bytes_accessed
                                        if tick_cost else 0.0))
                traced = [g for g in gen_batch
                          if getattr(g[2], "trace", None) is not None]
                if traced:
                    # the dispatch this cohort rode, stamped onto every
                    # traced member's stream record (host dispatch window
                    # — the device may still be executing; the fused
                    # readback is what resolves it)
                    tick = {
                        "tick_seq": tick_seq, "bucket": cap,
                        "batch": len(w["batch"]) + len(w["gens"]),
                        "padded": cnt, "steps": steps_run,
                        "requests": len(w["batch"]),
                        "start_ns": t_disp0, "end_ns": t_done,
                    }
                    for _li, _slot, sink, _adv, _done, _gen in traced:
                        sink.trace.add_tick(tick)
                if want_cost:
                    # Per-tenant attribution: every live slot rode exactly
                    # one lane of this dispatch, so each is charged an
                    # equal share of the compute window — the shares sum
                    # to the tick's compute_ns by construction (the
                    # conservation contract the tests pin).  FLOPs split
                    # the same way from the bucket's analyzed dispatch
                    # cost; padded-but-idle lanes charge nobody.
                    live = len(w["batch"]) + len(gen_batch)
                    if live:
                        share_us = (t_done - t_disp0) / live / 1e3
                        flops_share = (tick_cost.flops / live
                                       if tick_cost is not None else 0.0)
                        if w["batch"]:
                            with self._lock:
                                step_tenants = [
                                    self._slot_tenant.get(off + li, "")
                                    for li, _f in w["batch"]]
                            for tenant in step_tenants:
                                ledger.charge(
                                    self._model.name, tenant,
                                    device_us=share_us,
                                    flops=flops_share, tokens=1)
                        for _li, _slot, sink, adv, done, _gen in gen_batch:
                            # tenant rides the sink: the done path already
                            # released the slot (and its tenant entry)
                            tenant = getattr(sink, "tenant", "")
                            ledger.charge(
                                self._model.name, tenant,
                                device_us=share_us, flops=flops_share,
                                tokens=int(adv))
                            sink.cost_device_us = getattr(
                                sink, "cost_device_us", 0.0) + share_us
                            sink.cost_tokens = getattr(
                                sink, "cost_tokens", 0) + int(adv)
                            if done:
                                # stamp the finished generation's cost on
                                # its trace/flight record BEFORE the
                                # resolver can emit the stream-end record
                                cost = {
                                    "tenant": tenant,
                                    "device_us": round(
                                        sink.cost_device_us, 1),
                                    "tokens": sink.cost_tokens,
                                }
                                st = getattr(sink, "trace", None)
                                if st is not None:
                                    st.cost = cost
                                    if st.flight is not None:
                                        st.flight.cost = cost
                # PIPELINE the readback: over a remote device the blocking
                # D2H costs a full round trip; resolving it on a reader
                # thread lets the next dispatch's compute start
                # immediately, so round trips overlap instead of gating
                # the tick rate (double-buffered, bounded by
                # _tick_budget).  Safe because a sequence never has two
                # steps in flight (closed loop + per-seq lock): dispatch
                # N+1 only carries other sequences' tokens.
                pool = self._gen_reader if gen_batch else self._readers
                pool.submit(self._resolve_tick, out, w["batch"], gen_batch,
                            self._tick_budget,
                            self._watch_readback("tick"))
                # next bucket's assembly window starts fresh: it must not
                # absorb this bucket's dispatch time
                t_asm0 = time.monotonic_ns()

    @staticmethod
    def _close_decode_span(sink) -> None:
        """Close a traced generation's DECODE stage exactly once (the
        last-token resolver and the worker's cancel path can race): the
        per-sink lock makes the t_decode0 take atomic; whoever wins
        records the span, the loser records nothing."""
        import time

        tr = getattr(sink, "trace", None)
        lock = getattr(sink, "span_lock", None)
        if tr is None or lock is None:
            return
        with lock:
            t0 = getattr(sink, "t_decode0", None)
            sink.t_decode0 = None
        if t0 is not None:
            tr.add_span("DECODE", t0, time.monotonic_ns())

    @staticmethod
    def _resolve_prefill(pair, fut):
        try:
            vals = finish_readback(pair)
            fut.set_result((int(vals[0]), float(vals[1])))
        except Exception as e:  # noqa: BLE001 — surfaced via future
            fut.set_exception(e)

    def _resolve_gen_token(self, pair_dev, sink, done, slot, gen,
                           watch_id=None):
        try:
            vals = finish_readback(pair_dev)
            tok = (int(vals[0]), float(vals[1]))
            # host mirror for device-fault recovery: appended on this
            # (ordered) gen-reader thread in lock-step with the
            # consumer-visible put, so a recovery snapshot taken on this
            # thread equals the streamed prefix exactly
            sink.emitted.append(tok)
            sink.put(tok)
            if done:
                # a generation whose whole budget resolved at prefill
                # (n_tokens == 1) ends here — its DECODE stage (opened at
                # finish_prefill) must still close, however short.  Span
                # BEFORE sentinel: the envelope emits the record the
                # moment it sees stream-end
                self._close_decode_span(sink)
                sink.put(None)
        except Exception as e:  # noqa: BLE001 — surfaced via sink
            sink.failed = True
            sink.put(e)
            with self._lock:
                self._dead_gens.add((slot, gen))
            self._report_fault("readback", reason=str(e))
        finally:
            self._unwatch_readback(watch_id)

    def _resolve_tick(self, out, batch, gen_batch=(), budget=None,
                      watch_id=None):
        """Resolve one fused dispatch's ``[3, T, B]`` token block.

        batch: [(li, fut)] — client-driven steps, resolved from their one
        step-0 row; gen_batch: [(li, slot, sink, n_emit, done, gen)] —
        each generation's ``n_emit`` step rows stream in order.  li is
        bucket-local (``out`` holds that bucket's block), slot stays
        global for dead-generation bookkeeping."""
        try:
            # ONE fused (and pre-started) D2H for the whole multi-step
            # dispatch — the only blocking sync the fast path pays
            vals = finish_readback(out)
        except Exception as e:  # noqa: BLE001 — surfaced via futures/sinks
            if budget is not None:
                budget.release()
            self._unwatch_readback(watch_id)
            for _li, f in batch:
                f.set_exception(e)
            for _li, slot, sink, _n_emit, _done, gen in gen_batch:
                sink.failed = True
                sink.put(e)
                with self._lock:
                    self._dead_gens.add((slot, gen))
            self._report_fault("readback", reason=str(e))
            return
        if budget is not None:
            budget.release()
        self._unwatch_readback(watch_id)
        for li, f in batch:
            f.set_result((int(vals[0, 0, li]), float(vals[1, 0, li])))
        for li, _slot, sink, n_emit, done, _gen in gen_batch:
            for t in range(n_emit):
                tok = (int(vals[0, t, li]), float(vals[2, t, li]))
                # lock-step host mirror — see _resolve_gen_token
                sink.emitted.append(tok)
                sink.put(tok)
            if done:
                # last token host-resolved: the DECODE stage closes
                # (resolver thread — host-side, no device sync added).
                # Span BEFORE sentinel: the envelope emits the record the
                # moment it sees stream-end
                self._close_decode_span(sink)
                sink.put(None)

    def _fused_tick_cost(self, b, params, mask, tokens):
        """One-time XLA cost analysis of this bucket's fused tick dispatch
        (server/costs.py), lowered against the live argument shapes and
        cached per bucket — feeds the tick profiler's roofline totals and
        the per-tenant FLOPs attribution.  Unavailable stays absent (the
        False sentinel is never retried): roofline and FLOPs simply don't
        materialize, nothing is fabricated."""
        c = self._bucket_cost.get(b)
        if c is None:
            from ..server.costs import analyze_jax_callable
            try:
                c = analyze_jax_callable(
                    self._fused_fn, params, self._k[b], self._v[b],
                    self._dstate[b], mask, tokens) or False
            except Exception:  # noqa: BLE001 — cost stays absent
                c = False
            self._bucket_cost[b] = c
        return c or None

    def _new_cache_arrays(self, cnt: int, cap: int, cfg):
        """Fresh zeroed k/v cache pair for one bucket, committed to the
        serve mesh.  Plain cfg.dtype arrays, or int8 {"q", "s"} pairs when
        TRITON_TPU_KV_QUANT=int8 (scales init to 1 so zero entries decode
        to zero)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        shape = (cfg.n_layers, cnt, cfg.n_heads, cap, cfg.head_dim)
        sh_q = NamedSharding(self._mesh, P(None, "dp", "tp", None, None))
        if self._kv_quant:
            sh_s = NamedSharding(self._mesh, P(None, "dp", "tp", None))

            def one():
                return {
                    "q": jax.device_put(jnp.zeros(shape, jnp.int8), sh_q),
                    "s": jax.device_put(
                        jnp.ones(shape[:-1], jnp.float32), sh_s),
                }
        else:
            def one():
                return jax.device_put(jnp.zeros(shape, cfg.dtype), sh_q)

        return one(), one()

    def _rebuild_bucket_cache(self, b: int) -> None:
        """Worker-side, after a failed donated step/prefill: the call may
        have consumed the bucket's cache buffers (donation invalidates the
        inputs even when the computation errors), so rebuild them zeroed
        and invalidate every slot in the bucket — queued sequence jobs
        then fail stale instead of touching garbage.  Live self-feeding
        generations hand off to the recovery queue (re-admit + re-prefill
        ``prompt + emitted_so_far``, budget-capped) instead of being
        aborted: the server owns their whole protocol, so the fault is
        containable without the caller ever seeing it."""
        cnt, cap = self._buckets[b]
        off = self._bucket_off[b]
        # prefix-cache revalidation (block-invalidation rule): committed
        # blocks are INDEPENDENT buffers extracted from the slab, so a
        # donated bucket's death normally leaves the store intact — but a
        # fault that did reach a block's buffers (allocator-level loss)
        # must drop those blocks now, or a recovery re-prefill could hit
        # a dead block and fail its insert.  Metadata sweep, no sync.
        if self._kv_cache is not None:
            self._kv_cache.revalidate()
        for slot in range(off, off + cnt):
            info = self._auto_slots.pop(slot, None)
            if info is not None:
                self._recover_handoff(info["sink"])
        with self._lock:
            # One atomic section: release the bucket's sequence mappings,
            # return every slot to the pool, and bump the generations.
            # The seq-id release is load-bearing — a live sequence whose
            # mapping survived would read the post-bump gen at submit
            # time, pass the worker's stale check, and silently decode
            # against the zeroed cache; with the mapping gone its next
            # step finds no slot and fails loudly.  Holding _lock for the
            # whole section keeps a concurrent submit from claiming a
            # freed slot mid-rebuild and reading an intermediate gen.
            for key in [k for k, s in self._state.items()
                        if isinstance(s, int) and off <= s < off + cnt]:
                self._release_entry_locked(key)
            for slot in range(off, off + cnt):
                self._free.add(slot)
                self._slot_gen[slot] += 1
                self._clear_pen_locked(slot)
                self._slot_tenant.pop(slot, None)
                self._kv_unpin_charge(self._slot_kv_pin.pop(slot, None))
        try:
            params, cfg = self._params
            # drop the count matrix with the bucket's other state — pen_n
            # is 0 after the clear loop, and the next penalized admission
            # reallocates via _ensure_pen_bucket
            self._pen_counts[b] = None
            self._k[b], self._v[b] = self._new_cache_arrays(cnt, cap, cfg)
            # the donated control state died with the failed dispatch too
            self._dstate[b] = _new_decode_state(cnt)
            self._pen_fp_dev[b] = jnp.zeros(cnt, jnp.float32)
            self._pen_pp_dev[b] = jnp.zeros(cnt, jnp.float32)
        except Exception as e:  # noqa: BLE001 — e.g. the same OOM that
            # failed the step: a sane cache cannot be restored, so fail
            # pending work cleanly (503 via the drain path) instead of
            # letting the worker die and leave futures hanging forever.
            # This is NOT a swallow anymore: a model that cannot rebuild
            # its cache is exactly what quarantine exists for — escalate
            # straight there (readiness flips, clients reroute, the
            # device_fault incident bundle captures the evidence)
            self._report_fault("rebuild", reason=str(e),
                               force_quarantine=True)
            with self._lock:
                self._closed = True
            # route the shutdown sentinel through the ORDERED gen-reader:
            # every recovery handoff already submitted rides ahead of it,
            # so its "recover" job reaches the worker before the drain —
            # a direct put here could orphan a handed-off stream forever
            self._gen_reader.submit(self._jobs.put, None)

    def _ensure_pen_bucket(self, b: int) -> None:
        """Worker-side: allocate the bucket's [cnt, V] count matrix on
        first penalized admission (unpenalized buckets never pay the HBM
        or the penalized-kernel compile)."""
        if self._pen_counts[b] is None:
            _, cfg = self._params
            cnt = self._buckets[b][0]
            self._pen_counts[b] = jnp.zeros((cnt, cfg.vocab_size),
                                            jnp.int32)

    def _clear_pen_locked(self, slot) -> None:
        """Under self._lock: forget a slot's penalty state on release.
        Count rows go stale harmlessly (fp/pp are zero, and admission
        reseeds the row before use)."""
        if self._fns is None:  # pen state lives in the lazy-init block
            return
        self._slot_pen_seed.pop(slot, None)
        b, li = self._slot_bucket(slot)
        if self._pen_fp[b][li] != 0.0 or self._pen_pp[b][li] != 0.0:
            self._pen_fp[b][li] = 0.0
            self._pen_pp[b][li] = 0.0
            self._pen_n[b] -= 1

    def _deactivate_slot(self, slot):
        """Worker-side: stop a slot's device-side self-feed (cancellation
        and reap paths — normal completion deactivates in-kernel)."""
        b, li = self._slot_bucket(slot)
        self._dstate[b] = _state_deactivate(self._dstate[b], li)

    def _release_gen_slot(self, slot):
        """Worker-side: return a generation slot to the pool (no seq id to
        clean up; the generation bump invalidates any stale job)."""
        b, li = self._slot_bucket(slot)
        with self._lock:
            had_pen = (self._pen_fp[b][li] != 0.0
                       or self._pen_pp[b][li] != 0.0)
            self._free.add(slot)
            self._slot_gen[slot] += 1
            self._clear_pen_locked(slot)
            self._slot_tenant.pop(slot, None)
            pin = self._slot_kv_pin.pop(slot, None)
        self._kv_unpin_charge(pin)
        if had_pen:
            # zero the device-resident scalars too: a later unpenalized
            # occupant of this slot must not inherit stale penalties
            # while the bucket still runs the penalized kernel
            self._pen_fp_dev[b] = self._pen_fp_dev[b].at[li].set(0.0)
            self._pen_pp_dev[b] = self._pen_pp_dev[b].at[li].set(0.0)

    def submit_generation(self, window, n_tokens: int,
                          freq_pen: float = 0.0, pres_pen: float = 0.0,
                          prompt_len: int = None, tenant: str = ""):
        """Queue a server-side greedy generation (batched mode): the prompt
        prefills into a free slot and the slot self-feeds — every active
        generation shares one batched device step per tick.  Returns a
        Queue yielding (token id, logprob) pairs, then None (or an
        Exception).

        ``freq_pen``/``pres_pen``: OpenAI penalties, honored INSIDE the
        shared tick (per-slot count vector seeded from the prompt, fed by
        the chosen token; applied at the greedy head) — penalized greedy
        generations keep continuous-batching capacity instead of falling
        back to per-request chains."""
        import queue as _queue
        import time

        import numpy as np

        from ..server.trace import current_trace
        from ..server.types import InferError

        # sequence-lifecycle tracing: the stream envelope's live context
        # (the core copied its contextvar into this producer thread).  The
        # submit stamp opens the SLOT_WAIT stage — everything from here
        # until the worker starts the prefill is waiting, including the
        # one-time lazy slab/compile init on a cold model.
        tr = current_trace()
        t_submit = time.monotonic_ns()

        # HBM-aware admission BEFORE the slab cache materializes: a slab
        # that doesn't fit the device headroom sheds typed (429,
        # shed_reason "memory") instead of OOMing the allocator on the
        # first request; once resident, slot admission is gated by slot
        # availability alone (no new device memory is pinned)
        self._gate_hbm_slab()
        self._ensure_fns()
        if self._closed:
            raise InferError(
                f"model '{self._model.name}' is unloading", 503)
        need_s = int(window.shape[1]) + int(n_tokens)
        use_pen = freq_pen != 0.0 or pres_pen != 0.0
        with self._lock:
            slot = self._alloc_slot_locked(need_s)
            if slot is None:
                self._evict_idle_locked(time.monotonic())
                slot = self._alloc_slot_locked(need_s)
            if slot is None:
                raise InferError(
                    f"model '{self._model.name}': no free decode slot "
                    f"holds {need_s} tokens ({self._n_slots} total); retry "
                    "when a generation or sequence completes", 429)
            gen = self._slot_gen[slot]
            self._slot_tenant[slot] = tenant
            if use_pen:
                # counts include the REAL prompt tokens (not the window's
                # zero padding) — same seeding as the per-request chain,
                # which needs the true prompt length (a nonzero filter
                # would drop legitimate token-id-0 prompt bytes)
                if prompt_len is None:
                    raise InferError(
                        "penalized generation requires prompt_len (the "
                        "count seed cannot be recovered from the padded "
                        "window)")
                _, cfg = self._params
                real = (window[0, window.shape[1] - prompt_len:]
                        if prompt_len else np.zeros(0, np.int32))
                row = np.bincount(
                    real, minlength=cfg.vocab_size).astype(np.int32)
                self._slot_pen_seed[slot] = (
                    float(freq_pen), float(pres_pen), row)
        # KV byte-seconds integrator: admitted tokens x per-token bytes,
        # integrated over the slot's admit..release lifetime (memory
        # governor); the release path charges the tenant the integral
        self._kv_pin_slot(slot, need_s, tenant)
        sink: "_queue.Queue" = _queue.Queue()
        # lifecycle-span plumbing rides the sink (worker + resolver
        # threads never touch the contextvar): only stream contexts
        # (add_tick) participate — a unary context has no token timeline
        sink.trace = tr if hasattr(tr, "add_tick") else None
        sink.t_submit = t_submit
        sink.t_prefill0 = None
        sink.t_decode0 = None
        # per-generation cost accumulators (worker-written, single
        # writer): tick compute shares and token counts; the tenant
        # rides the sink so attribution survives slot release
        sink.tenant = tenant
        sink.cost_device_us = 0.0
        sink.cost_tokens = 0
        # prefix-cache outcome (worker-stamped at prefill): rides the
        # sink into the usage backchannel and the stream trace record
        sink.cache_hit_tokens = 0
        sink.prefix_hash = None
        # guards the close-once take of t_decode0: the resolver's
        # last-token path and the worker's cancel path can race
        sink.span_lock = self._threading.Lock()
        # device-fault recovery metadata: the host mirror a recovery
        # re-prefill is rebuilt from.  ``emitted`` is appended ONLY on
        # the ordered gen-reader thread, in lock-step with each
        # consumer-visible put — a snapshot taken there equals the
        # streamed prefix exactly (the bit-identity anchor).  ``failed``
        # marks a stream that already surfaced an exception (never
        # resumed); ``recoveries`` counts re-admissions against
        # TRITON_TPU_RECOVERY_BUDGET.
        sink.window = window
        sink.prompt_len = prompt_len
        sink.n_tokens_total = int(n_tokens)
        sink.freq_pen = float(freq_pen)
        sink.pres_pen = float(pres_pen)
        sink.emitted = []
        sink.recoveries = 0
        sink.failed = False
        self._jobs.put(("prefill",
                        (slot, gen, window, ("gen", n_tokens, sink)),
                        None))
        return sink

    def _submit(self, kind, payload):
        import concurrent.futures

        from ..server.types import InferError

        if self._closed:
            raise InferError(
                f"model '{self._model.name}' is unloading", 503)
        fut = concurrent.futures.Future()
        if kind == "prefill":
            payload = payload + (("fut", fut),)
        self._jobs.put((kind, payload, fut))
        return fut

    # -- request path ------------------------------------------------------
    def _execute(self, inputs, parameters):
        if self._mode == "independent":
            return self._execute_independent(inputs, parameters)
        return self._execute_batched(inputs, parameters)

    def _execute_independent(self, inputs, parameters):
        """Per-sequence caches; step + readback on the calling executor
        thread so concurrent sequences' device round trips overlap."""
        import time

        import numpy as np

        from ..server.types import InferError

        seq_id = parameters.get("sequence_id", 0)
        start = bool(parameters.get("sequence_start", False))
        end = bool(parameters.get("sequence_end", False))
        if not seq_id:
            raise InferError(
                f"inference request to model '{self._model.name}' must "
                "specify a non-zero or non-empty correlation ID")
        prefill, step, params, cfg = self._ensure_fns_independent()
        toks = np.asarray(inputs["TOKENS"]).reshape(1, -1).astype(np.int32)
        toks = np.clip(toks, 0, cfg.vocab_size - 1)
        now = time.monotonic()
        with self._lock:
            self._evict_idle_locked(now)
            seq_lock = self._seq_locks.setdefault(
                seq_id, self._threading.Lock())
        with seq_lock:
            with self._lock:
                entry = self._state.get(seq_id)

            def drop():
                with self._lock:
                    self._release_locked(seq_id)

            if start or entry is None:
                if toks.shape[1] != self._prompt_len:
                    drop()
                    raise InferError(
                        f"model '{self._model.name}': sequence_start expects "
                        f"a [1,{self._prompt_len}] prompt, got "
                        f"{list(toks.shape)}")
                kvc = self._kv_cache
                hit, blocks, phash = 0, None, None
                if kvc is not None:
                    hit, blocks, phash = kvc.match(toks[0])
                try:
                    # independent mode allocates a FRESH s_max-deep cache
                    # per sequence — the projection the headroom gate must
                    # hold.  A prefix hit SHRINKS the projection: the
                    # cached positions' bytes already reside under the
                    # store's governor reservation, so admission prices
                    # only what this sequence newly computes and writes —
                    # reuse directly buys admission capacity.
                    self._gate_hbm(self._s_max - hit)
                    if hit:
                        # restore the cached prefix into a fresh cache,
                        # then prefill only the uncached tail (same
                        # bit-identity contract as the batched path)
                        shape = (cfg.n_layers, 1, cfg.n_heads,
                                 self._s_max, cfg.head_dim)
                        kz = jnp.zeros(shape, cfg.dtype)
                        vz = jnp.zeros(shape, cfg.dtype)
                        kz, vz = self._cache_insert_run_fn(
                            kz, vz, tuple(blk.k for blk in blocks),
                            tuple(blk.v for blk in blocks), 0, 0)
                        logits, cache = self._ind_tail_fn(
                            params, kz, vz, jnp.asarray(toks[:, hit:]),
                            hit)
                    else:
                        logits, cache = prefill(params, jnp.asarray(toks))
                finally:
                    if blocks:
                        kvc.release(blocks)
                if kvc is not None:
                    # commit the window's uncommitted complete blocks out
                    # of the fresh cache (independent leaves share the
                    # [L, B, H, S, K] layout the block ops slice)
                    digs = kvc.chain_digests(toks[0])
                    bt = kvc.block_tokens
                    tenant = parameters.get("_cost_tenant") or ""
                    for i in range(hit // bt, len(digs)):
                        d = digs[i]
                        if kvc.has(d):
                            continue
                        kb, vb = self._cache_extract_fn(
                            cache["k"], cache["v"], 0, i * bt)
                        kvc.put(d, digs[i - 1] if i else b"", kb, vb,
                                tenant)
                # host-side mirror of cache["pos"] — reading the device
                # scalar would cost a blocking D2H round trip per step
                host_pos = toks.shape[1]
            else:
                cache, host_pos = entry
                if host_pos >= self._s_max:
                    # free the cache even on the failure path: the client
                    # was told to send sequence_end and must not find the
                    # id poisoned (multi-MB device cache pinned until TTL)
                    if end:
                        drop()
                    raise InferError(
                        f"model '{self._model.name}': sequence exceeded the "
                        f"{self._s_max}-token cache; send sequence_end")
                if toks.shape[1] != 1:
                    raise InferError(
                        f"model '{self._model.name}': decode steps expect "
                        f"TOKENS [1,1], got {list(toks.shape)}")
                logits, cache = step(params, cache, jnp.asarray(toks))
                host_pos += 1
            # ONE fused D2H for both scalars — separate int()/float()
            # reads pay a blocking device round trip each.
            # start/finish_readback is the same resolve
            # pair the batched tick uses (one implementation for both
            # modes); this protocol is synchronous per step, so the
            # resolve still blocks here — the overlap win belongs to the
            # pipelined batched path.
            pair = start_readback(jnp.stack(
                [jnp.argmax(logits, axis=-1)[0].astype(jnp.float32),
                 jnp.max(logits, axis=-1)[0]]))
            vals = finish_readback(pair)
            nxt, best = int(vals[0]), float(vals[1])
            with self._lock:
                if end:
                    self._release_locked(seq_id)
                else:
                    self._state[seq_id] = (cache, host_pos)
                    self._touched[seq_id] = time.monotonic()
        return {
            "NEXT_TOKEN": np.array([nxt], np.int32).reshape(1),
            "NEXT_LOGIT": np.array([best], np.float32).reshape(1),
        }

    def _execute_batched(self, inputs, parameters):
        import time

        import numpy as np

        from ..server.types import InferError

        seq_id = parameters.get("sequence_id", 0)
        start = bool(parameters.get("sequence_start", False))
        end = bool(parameters.get("sequence_end", False))
        if not seq_id:
            raise InferError(
                f"inference request to model '{self._model.name}' must "
                "specify a non-zero or non-empty correlation ID")
        # same slab gate as submit_generation: protect the one-time cache
        # allocation the first request triggers, inert once resident
        self._gate_hbm_slab()
        _prefill, _params, cfg = self._ensure_fns()
        toks = np.asarray(inputs["TOKENS"]).reshape(1, -1).astype(np.int32)
        toks = np.clip(toks, 0, cfg.vocab_size - 1)
        now = time.monotonic()
        with self._lock:
            self._evict_idle_locked(now)
            # per-sequence lock: steps within one correlation id serialize
            # (Triton sequence semantics); different sequences overlap
            seq_lock = self._seq_locks.setdefault(
                seq_id, self._threading.Lock())
        with seq_lock:
            # slot AND its generation are read in ONE locked section: a
            # cache rebuild landing between separate reads would release
            # the mapping and bump the gen, and a gen read afterwards would
            # pass the worker's stale check — silently decoding against
            # the zeroed cache.  Read atomically, any later rebuild makes
            # the submitted gen stale and the step fails loudly.
            with self._lock:
                slot = self._state.get(seq_id)
                gen = self._slot_gen[slot] if slot is not None else None
            if start or slot is None:
                if toks.shape[1] != self._prompt_len:
                    with self._lock:
                        self._release_locked(seq_id)
                    raise InferError(
                        f"model '{self._model.name}': sequence_start "
                        f"expects a [1,{self._prompt_len}] prompt, got "
                        f"{list(toks.shape)}")
                with self._lock:
                    # re-read under this lock: a concurrent rebuild may
                    # have released the mapping since the peek above
                    slot = self._state.get(seq_id)
                    fresh = slot is None
                    if slot is None:
                        # open-ended length: prefer the largest slab so the
                        # sequence keeps maximum headroom before its cap
                        need = self._prompt_len + 1
                        slot = self._alloc_slot_locked(need,
                                                       prefer_large=True)
                        if slot is None:
                            self._evict_idle_locked(time.monotonic())
                            slot = self._alloc_slot_locked(
                                need, prefer_large=True)
                        if slot is None:
                            # drop the lock entry setdefault created, or
                            # retried starts leak one per correlation id
                            self._seq_locks.pop(seq_id, None)
                            raise InferError(
                                f"model '{self._model.name}': all "
                                f"{self._n_slots} decode slots are busy; "
                                "end or abandon a sequence first", 429)
                        self._state[seq_id] = slot
                        self._slot_tenant[slot] = \
                            parameters.get("_cost_tenant") or ""
                    gen = self._slot_gen[slot]
                if fresh:
                    # the slot pins its whole slab-lane capacity for the
                    # sequence's open-ended lifetime — that is the KV
                    # footprint its tenant holds against the pool
                    self._kv_pin_slot(slot, self._slot_cap(slot),
                                      parameters.get("_cost_tenant") or "")
                fut = self._submit("prefill", (slot, gen, toks))
            else:
                # self._pos is worker-owned, but this slot's previous step
                # completed before its future resolved (per-seq lock), so
                # the read is stable
                cap = self._slot_cap(slot)
                if int(self._pos[slot]) >= cap:
                    # free the slot even on the failure path: the client
                    # was told to send sequence_end and must not find the
                    # id poisoned
                    if end:
                        with self._lock:
                            self._release_locked(seq_id)
                    raise InferError(
                        f"model '{self._model.name}': sequence exceeded "
                        f"the {cap}-token cache; send sequence_end")
                if toks.shape[1] != 1:
                    raise InferError(
                        f"model '{self._model.name}': decode steps expect "
                        f"TOKENS [1,1], got {list(toks.shape)}")
                fut = self._submit("step", (slot, gen, int(toks[0, 0])))
            nxt, best = fut.result(timeout=3600)
            with self._lock:
                if end:
                    self._release_locked(seq_id)
                else:
                    self._touched[seq_id] = time.monotonic()
        return {
            "NEXT_TOKEN": np.array([nxt], np.int32).reshape(1),
            "NEXT_LOGIT": np.array([best], np.float32).reshape(1),
        }



class GenerateModel:
    """``llama_generate``: decoupled server-side text generation.

    The JSON-first face of the decode stack (Triton generate-extension
    surface): ``text_input`` BYTES [1] in, one ``text_output`` chunk per
    generated token out — served over ``POST .../generate_stream`` (SSE) or
    the decoupled gRPC stream.  ``max_tokens`` arrives as a request
    parameter.  Unlike ``llama_decode`` (client-side closed loop: one
    round trip per token), the generation loop runs server-side, so the
    client pays one request for the whole stream.

    Shares weights and compiled prefill/step functions with the passed
    ``DecodeModel`` — registering both costs one parameter set."""

    def __init__(self, decode: DecodeModel, name: str = "llama_generate",
                 default_tokens: int = 16):
        import numpy as np

        from ..server.model import Model, make_config

        self._decode = decode
        self._default_tokens = default_tokens
        self._np = np
        cfg = make_config(
            name,
            inputs=[("text_input", "BYTES", [1])],
            outputs=[("text_output", "BYTES", [1]),
                     ("token_id", "INT32", [1]),
                     ("logprob", "FP32", [1])],
            decoupled=True,
            instance_kind="KIND_TPU",
            parameters={"prompt_tokens": str(decode._prompt_len)},
        )
        outer = self

        class _Impl(Model):  # noqa: N801 — adapter onto the abstract Model
            def execute(inner, inputs, parameters):
                from ..server.types import InferError

                raise InferError(
                    f"model '{inner.name}' is decoupled: use "
                    "generate_stream or a gRPC stream")

            def execute_decoupled(inner, inputs, parameters):
                return outer._generate(inputs, parameters)

            device_loop = True

            def __getattr__(inner, name):
                return _worker_socket(outer._decode, inner.config.name, name)

        self.model = _Impl(cfg)

    @staticmethod
    @functools.lru_cache(maxsize=1)
    def _logprob_fn():
        """jitted (logits [1, V], token [1]) -> [1] log-probability of the
        token under the raw-logit softmax (OpenAI logprobs semantics:
        reported against the unmodified distribution, whatever the
        sampling knobs did)."""

        @jax.jit
        def lp(logits, tok):
            l32 = logits.astype(jnp.float32)
            chosen = jnp.take_along_axis(l32, tok[:, None], axis=-1)[:, 0]
            return chosen - jax.nn.logsumexp(l32, axis=-1)

        return lp

    @staticmethod
    @functools.lru_cache(maxsize=1)
    def _penalty_fns():
        """jitted pair for OpenAI frequency/presence penalties:
        ``pen(logits [1,V], counts [1,V], fp, pp)`` subtracts
        ``fp*count + pp*(count>0)`` per token (both scalars traced — no
        recompiles across values), and ``upd(counts, tok [1])`` bumps the
        chosen token's count for the next step.  Counts live on device for
        the whole chain — no host round trip per token."""

        @jax.jit
        def pen(logits, counts, fp, pp):
            c = counts.astype(jnp.float32)
            return (logits.astype(jnp.float32)
                    - fp * c - pp * (c > 0).astype(jnp.float32))

        @jax.jit
        def upd(counts, tok):
            return counts.at[0, tok[0]].add(1)

        return pen, upd

    @staticmethod
    @functools.lru_cache(maxsize=16)
    def _sampler(top_k: int, use_top_p: bool = False):
        """Jitted device-side token chooser — temperature scaling, optional
        static top-k truncation, optional nucleus (top-p) truncation,
        categorical sample.  One compile per distinct (top_k, top_p-on)
        pair (bounded by the lru cache); the top_p VALUE is a traced
        argument so sweeping it costs no recompiles."""

        def choose(logits, key, temperature, top_p):
            l32 = logits.astype(jnp.float32)
            top_vals = None
            if top_k > 0:
                top_vals, _ = lax.top_k(l32, top_k)
                thresh = top_vals[..., -1:]
                l32 = jnp.where(l32 >= thresh, l32, -jnp.inf)
            inv_t = 1.0 / jnp.maximum(temperature, 1e-6)
            if use_top_p:
                # nucleus: keep the smallest descending-probability prefix
                # whose mass reaches top_p (OpenAI semantics: temperature
                # applies before the nucleus cut; the first token always
                # survives).  top_k already produced the descending
                # survivors — masked entries contribute 0 mass, so the
                # length-k softmax equals the masked-vocab one and the
                # full-vocab re-sort is skipped.
                desc = (top_vals if top_vals is not None
                        else jnp.sort(l32, axis=-1)[..., ::-1])
                probs = jax.nn.softmax(desc * inv_t, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = jnp.concatenate(
                    [jnp.ones_like(cum[..., :1], bool),
                     cum[..., :-1] < top_p], axis=-1)
                kept_min = jnp.min(
                    jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
                l32 = jnp.where(l32 >= kept_min, l32, -jnp.inf)
            return jax.random.categorical(
                key, l32 * inv_t, axis=-1).astype(jnp.int32)

        return jax.jit(choose)

    def _generate_batched(self, window, n_tokens, freq_pen=0.0,
                          pres_pen=0.0, prompt_len=None, parameters=None):
        np = self._np
        from ..server.types import InferError

        tenant = ""
        if parameters is not None:
            tenant = parameters.get("_cost_tenant") or ""
        sink = self._decode.submit_generation(
            window, n_tokens, freq_pen=freq_pen, pres_pen=pres_pen,
            prompt_len=prompt_len, tenant=tenant)
        try:
            while True:
                item = sink.get(timeout=3600)
                if item is None:
                    # cost backchannel: the worker finished writing the
                    # accumulators before it let the end sentinel through,
                    # so the stream envelope can stamp device_time_us on
                    # the final response (OpenAI usage block)
                    if parameters is not None:
                        dev_us = getattr(sink, "cost_device_us", 0.0)
                        if dev_us:
                            parameters["_cost_device_us"] = round(dev_us, 1)
                        hit = getattr(sink, "cache_hit_tokens", 0)
                        if hit:
                            # prefix-cache backchannel (mirrors the cost
                            # one): the stream envelope stamps it on the
                            # final response for the OpenAI usage block
                            parameters["_cache_hit_tokens"] = int(hit)
                    return
                if isinstance(item, Exception):
                    if isinstance(item, InferError):
                        raise item
                    raise InferError(f"generation failed: {item}", 500)
                tok, lp = item
                yield {
                    "text_output": np.asarray(
                        [chr(int(tok) % 256).encode("utf-8")], dtype=object),
                    "token_id": np.asarray([tok], np.int32),
                    "logprob": np.asarray([lp], np.float32),
                }
        except GeneratorExit:
            # consumer closed mid-stream (disconnect / stop sequence): flag
            # the sink so the decode worker frees the slot instead of
            # ticking an unread generation to completion
            sink.cancelled = True
            raise

    def _generate(self, inputs, parameters):
        np = self._np
        dec = self._decode
        params, cfg = dec._ensure_params()
        raw = np.asarray(inputs["text_input"]).reshape(-1)
        prompt = raw[0] if len(raw) else b""
        if isinstance(prompt, str):
            prompt = prompt.encode()
        from ..server.types import InferError

        try:
            n_tokens = int(parameters.get("max_tokens", self._default_tokens))
            temperature = float(parameters.get("temperature", 0.0))
            top_k = int(parameters.get("top_k", 0))
            top_p = float(parameters.get("top_p", 1.0))
            freq_pen = float(parameters.get("frequency_penalty", 0.0))
            pres_pen = float(parameters.get("presence_penalty", 0.0))
            seed = parameters.get("seed")
            seed = None if seed is None else int(seed)
        except (TypeError, ValueError) as e:
            raise InferError(f"invalid sampling parameter: {e}")
        n_tokens = max(1, min(n_tokens, dec._s_max - dec._prompt_len))
        if not (temperature >= 0 and math.isfinite(temperature)):
            raise InferError(
                f"temperature must be finite and >= 0, got {temperature}")
        if top_k < 0 or top_k > cfg.vocab_size:
            raise InferError(
                f"top_k must be in [0, {cfg.vocab_size}], got {top_k}")
        if not (0.0 < top_p <= 1.0):
            raise InferError(f"top_p must be in (0, 1], got {top_p}")
        for name, v in (("frequency_penalty", freq_pen),
                        ("presence_penalty", pres_pen)):
            if not (-2.0 <= v <= 2.0):
                raise InferError(
                    f"{name} must be in [-2, 2], got {v}")
        use_pen = freq_pen != 0.0 or pres_pen != 0.0
        if seed is None:
            # unseeded sampling must vary across requests
            import os as _os

            seed = int.from_bytes(_os.urandom(4), "little")

        window = np.zeros((1, dec._prompt_len), np.int32)
        b = np.frombuffer(bytes(prompt[-dec._prompt_len:]), np.uint8)
        if b.size:
            window[0, dec._prompt_len - b.size:] = b
        window = np.clip(window, 0, cfg.vocab_size - 1)

        if dec._mode == "batched" and temperature == 0:
            # continuous batching for server-side generation: the request
            # joins the decode worker's shared tick — N concurrent greedy
            # generations cost ONE batched device step per token position,
            # with the feedback token never leaving the device.  Penalties
            # ride the tick too (per-slot count vectors; see
            # make_fused_slot_step_pen), so penalized greedy keeps batched
            # capacity.  Sampled requests keep the per-request chain
            # below: RNG state is per-request.
            yield from self._generate_batched(
                window, n_tokens, freq_pen=freq_pen, pres_pen=pres_pen,
                prompt_len=int(b.size), parameters=parameters)
            return

        prefill, step, params, cfg = dec._ensure_fns_independent()
        # Enqueue the WHOLE decode chain with the chosen token (greedy or
        # sampled) fed back as a
        # device array — no host readback inside the loop (jax async
        # dispatch).  A per-token blocking argmax readback would put a
        # host round trip between every two steps; device-resident
        # feedback makes inter-token latency the on-device step time, with
        # readbacks prefetched so they overlap the remaining steps.
        if temperature > 0:
            sampler = self._sampler(top_k, top_p < 1.0)
            base_key = jax.random.PRNGKey(seed)

            def choose(logits, i):
                return sampler(logits, jax.random.fold_in(base_key, i),
                               jnp.float32(temperature),
                               jnp.float32(top_p))
        else:
            def choose(logits, i):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        lp_of = self._logprob_fn()
        if use_pen:
            # OpenAI penalties count "text so far" including the prompt:
            # seed the device-resident count vector from the REAL prompt
            # bytes (not the window's zero padding)
            pen, upd = self._penalty_fns()
            counts = jnp.asarray(np.bincount(
                window[0, dec._prompt_len - b.size:] if b.size
                else np.zeros(0, np.int32),
                minlength=cfg.vocab_size).astype(np.int32).reshape(1, -1))
            fp_t, pp_t = jnp.float32(freq_pen), jnp.float32(pres_pen)
        logits, cache = prefill(params, jnp.asarray(window))
        pair_devs = []
        for i in range(n_tokens):
            cur = pen(logits, counts, fp_t, pp_t) if use_pen else logits
            tok_dev = choose(cur, i)  # [1], stays on device
            if use_pen:
                counts = upd(counts, tok_dev)
            # chosen token's log-probability under the raw-logit softmax
            # (OpenAI semantics: logprobs report the unmodified
            # distribution, whatever sampling/penalties did), stacked with
            # the token so the prefetched readback stays ONE fused D2H
            pair_devs.append(start_readback(
                jnp.stack([tok_dev.astype(jnp.float32),
                           lp_of(logits, tok_dev)])))
            if i < n_tokens - 1:
                logits, cache = step(
                    params, cache, tok_dev.reshape(1, 1))
        for pair_dev in pair_devs:
            vals = finish_readback(pair_dev)
            tok = int(vals[0, 0])
            # text_output: chr(token mod 256) as UTF-8 (JSON-safe; the byte
            # "detokenizer" aliases ids >= 256 at large vocab sizes, same as
            # llama_postprocess) — token_id carries the exact id losslessly
            yield {
                "text_output": np.asarray(
                    [chr(tok % 256).encode("utf-8")], dtype=object),
                "token_id": np.asarray([tok], np.int32),
                "logprob": np.asarray([vals[1, 0]], np.float32),
            }


def make_llama_generate(decode: DecodeModel):
    # llama_generate SHARES the DecodeModel's weights and mesh (one weight
    # set by design), so its placement follows the decode model's override
    # — a generate-name mesh var would be a silent no-op; warn instead
    import os
    import warnings

    key = tr.serve_mesh_env_key("llama_generate")
    if os.environ.get(key) is not None:
        warnings.warn(
            f"{key} is ignored: llama_generate shares llama_decode's "
            f"weights and mesh — set "
            f"{tr.serve_mesh_env_key(decode.model.name)} instead",
            stacklevel=2)
    return GenerateModel(decode).model


def reference_forward(params, tokens, cfg: tr.TransformerConfig):
    """Plain full forward over [B, S] with absolute positions — the
    equivalence oracle for prefill+decode (same math, no cache)."""
    x = jnp.take(params["embed"].astype(cfg.dtype), tokens, axis=0)
    blocks = _layer_blocks(params, cfg)

    def layer(x, blk):
        x, _, _ = _prefill_layer(blk, x, cfg)
        return x, None

    x, _ = lax.scan(layer, x, blocks)
    return _head(params, x, cfg)
