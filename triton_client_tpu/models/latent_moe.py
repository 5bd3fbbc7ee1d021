"""Latent attention over a sparse expert layer: the DeepSeek-V3 block.

The second block of the zoo, beside ``transformer.py``'s.  What it has that
the shared stack has not:

* **Latent attention (MLA).**  Queries and keys/values go through low-rank
  paths with an inner norm each; a head's query and key are a 128-wide part
  without position beside a 64-wide rotary part (YaRN frequencies), and the
  key's rotary part is one vector a token, shared by every head; values are
  128 wide, so q·k contracts over 192 and P·v writes 128
  (``ops/flash_attention.py``'s looped form takes the two widths).
* **SwiGLU** (three matrices) in every FFN.
* **A stack that is not one scan**: the leading layers are dense, the rest
  are expert layers (one ``lax.scan`` over those).
* **An expert layer that is told which experts it holds.**  The router scores
  all ``routed_experts_total`` experts (sigmoid, a selection bias, top-k,
  renormalised weights times a factor); this chip holds experts
  ``first_expert .. first_expert + n_routed_experts - 1`` and computes the
  part of the result its own experts give, sparsely: only the (token, expert)
  pairs that land on a held expert, by a grouped matmul over chunks of
  sorted pairs (one chunk holds what even routing sends, and a quarter).  No pair is dropped: a step in which every
  token picks held experts walks more chunks.  What the absent experts would
  add is left out; no code stands in for the other chips or their exchange.
* **Weights made and held in bfloat16**, leaf by leaf on the device, each
  layer from ``fold_in(PRNGKey(weights_seed), layer)``: the bf16 values are
  the checkpoint.

The forward also returns, per batch row and expert layer, the rows routed to
each held expert; the served model hands them to ``ModelStats``.
``prefill`` returns the latent cache (``c_kv ‖ k_rope``, 576 values a token a
layer) beside the logits and ``decode_step`` takes one token through it in
the absorbed form (``W_kb`` folded into q, ``W_vb`` into the output).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import parts


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """The source's keys under the source's names; ``n_routed_experts`` and
    ``vocab_size`` are what this chip holds, ``routed_experts_total`` is the
    router's published width."""

    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    n_routed_experts: int          # held here
    routed_experts_total: int      # the router's outputs
    first_expert: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    vocab_size: int                # rows of the vocabulary held here
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_original_max_position: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    seq_len: int
    weights_seed: int
    # how the router scores (``route``): DeepSeek-V3's sigmoid with a
    # selection bias; another block's config says otherwise
    scoring_func: str = "sigmoid"
    router_bias: bool = True
    router_eps: float = 1e-20      # added to the chosen scores' sum

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # what ``tr.serve_mesh`` asks of a configuration
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_experts(self) -> int:
        return self.n_routed_experts

    @property
    def moe(self) -> bool:
        return True


#: Kimi-K2-Instruct's ``config.json`` cut to one chip of a 32-chip expert-
#: parallel prefill pool: 12 of 384 routed experts, an eighth of the
#: vocabulary, the dense layer and four expert layers; every width as
#: published (``chipbench/configs/kimi_k2.json`` states the cut).
KIMI_K2_EP32_SHARE = LatentMoEConfig(
    hidden_size=7168, num_hidden_layers=5, first_k_dense_replace=1,
    num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    intermediate_size=18432, moe_intermediate_size=2048, n_shared_experts=1,
    n_routed_experts=12, routed_experts_total=384, first_expert=0,
    num_experts_per_tok=8, routed_scaling_factor=2.827, vocab_size=20480,
    rms_norm_eps=1e-6, rope_theta=50000.0, rope_factor=32.0,
    rope_original_max_position=4096, rope_beta_fast=1.0, rope_beta_slow=1.0,
    rope_mscale=1.0, rope_mscale_all_dim=1.0, seq_len=8192, weights_seed=28)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def leaf_shapes(cfg: LatentMoEConfig, dense: bool) -> Dict[str, Tuple]:
    """``{leaf: (shape, scale of the normal draw)}`` of one layer; a routed
    expert's leaves are per expert (the leading axis is added by the draw)."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    shapes = {
        "w_qa": ((D, rq), fan(D)),
        "w_qb_nope": ((rq, H, dn), fan(rq)),
        "w_qb_rope": ((rq, H, dr), fan(rq)),
        "w_kva": ((D, rkv + dr), fan(D)),
        "w_kb": ((rkv, H, dn), fan(rkv)),
        "w_vb": ((rkv, H, dv), fan(rkv)),
        "w_o": ((H, dv, D), fan(H * dv)),
    }
    if dense:
        F = cfg.intermediate_size
        shapes.update({"w_gate": ((D, F), fan(D)), "w_up": ((D, F), fan(D)),
                       "w_down": ((F, D), fan(F))})
    else:
        Fe = cfg.moe_intermediate_size
        Fs = Fe * cfg.n_shared_experts
        shapes.update({
            "router": ((D, cfg.routed_experts_total), 0.02),
            # normal x 0.01, so that the bias moves some choices
            "router_bias": ((cfg.routed_experts_total,), 0.01),
            "we_gate": ((D, Fe), fan(D)), "we_up": ((D, Fe), fan(D)),
            "we_down": ((Fe, D), fan(Fe)),
            "ws_gate": ((D, Fs), fan(D)), "ws_up": ((D, Fs), fan(D)),
            "ws_down": ((Fs, D), fan(Fs)),
        })
    return shapes


def norms(cfg) -> Dict[str, int]:
    """A latent-attention layer's norms and their widths."""
    return {"ln_attn": cfg.hidden_size, "ln_q": cfg.q_lora_rank,
            "ln_kv": cfg.kv_lora_rank, "ln_ffn": cfg.hidden_size}


def _layer_params(cfg: LatentMoEConfig, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves in bfloat16 (the selection bias upcast to f32)."""
    return parts.draw_layer(
        cfg.weights_seed, layer,
        leaf_shapes(cfg, layer < cfg.first_k_dense_replace), norms(cfg),
        cfg.first_expert + jnp.arange(cfg.n_routed_experts),
        ("router_bias",))


def init_params(cfg: LatentMoEConfig, quantized: bool = False) -> Dict[str, Any]:
    """``{"embed", "final_ln", "head", "dense": [layer...], "experts":
    stacked layer}``: the expert layers' leaves are stacked on a leading
    axis for the scan, a layer at a time."""
    prep = jax.jit(parts.quantize_weights) if quantized else (lambda x: x)
    n_dense = cfg.first_k_dense_replace
    dense = [prep(_layer_params(cfg, i)) for i in range(n_dense)]
    experts = {}
    for i in range(cfg.n_expert_layers):
        parts.stack(experts, prep(_layer_params(cfg, n_dense + i)), i,
                    cfg.n_expert_layers)
    return dict(parts.outer_params(cfg), dense=dense, experts=experts)


# ---------------------------------------------------------------------------
# Rotary positions (YaRN)
# ---------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(cfg: LatentMoEConfig) -> float:
    """``qk_head_dim ** -0.5`` times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2``."""
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.qk_head_dim ** -0.5 * m * m


def yarn_inv_freq(cfg: LatentMoEConfig):
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding``: interpolated
    frequencies below the correction range, the model's own above it, a
    linear ramp between.  Returns ``(inv_freq [dim/2], cos/sin multiplier)``."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    orig = cfg.rope_original_max_position

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** exponent
    inter = extra / cfg.rope_factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    multiplier = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                  / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return inter * (1.0 - keep) + extra * keep, multiplier


def _rotary(cfg: LatentMoEConfig, positions):
    """``(cos, sin)`` as ``[len(positions), dim/2]`` f32."""
    inv_freq, multiplier = yarn_inv_freq(cfg)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang) * multiplier, jnp.sin(ang) * multiplier


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def latents(blk, h, cfg: LatentMoEConfig, cos, sin):
    """The two low-rank paths of a row of tokens ``h [..., D]``: the query's
    two parts per head, the normed kv latent and the rotated shared key."""
    with jax.named_scope("q_proj"):
        c_q = parts.rmsnorm(jnp.dot(h, parts.w(blk, "w_qa")), blk["ln_q"],
                            cfg.rms_norm_eps)
        q_nope = jnp.einsum("...sr,rhk->...hsk", c_q,
                            parts.w(blk, "w_qb_nope"))
        q_rope = jnp.einsum("...sr,rhk->...hsk", c_q,
                            parts.w(blk, "w_qb_rope"))
    with jax.named_scope("kv_proj"):
        kva = jnp.dot(h, parts.w(blk, "w_kva"))
        c_kv = parts.rmsnorm(kva[..., :cfg.kv_lora_rank], blk["ln_kv"],
                             cfg.rms_norm_eps)
        k_rope = kva[..., cfg.kv_lora_rank:]
    with jax.named_scope("rope"):
        q_rope = parts.rotate(q_rope, cos, sin)
        k_rope = parts.rotate(k_rope, cos, sin)
    return q_nope, q_rope, c_kv, k_rope


@jax.named_scope("mla")
def _mla(blk, x, cfg: LatentMoEConfig, cos, sin):
    """Prefill: ``x [B,S,D]`` -> ``(x + attention, (c_kv, k_rope))``."""
    h = parts.rmsnorm(x, blk["ln_attn"], cfg.rms_norm_eps)
    q_nope, q_rope, c_kv, k_rope = latents(blk, h, cfg, cos, sin)
    with jax.named_scope("kv_proj"):
        k_nope = jnp.einsum("bsc,chk->bhsk", c_kv, parts.w(blk, "w_kb"))
        v = jnp.einsum("bsc,chk->bhsk", c_kv, parts.w(blk, "w_vb"))
    with jax.named_scope("rope"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None], k_nope.shape[:3]
                                      + k_rope.shape[-1:])], axis=-1)
    with jax.named_scope("scores_softmax"):
        from ..ops import flash_attention

        o = flash_attention(q, k, v, causal=True,
                            sm_scale=softmax_scale(cfg))
    with jax.named_scope("out_proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"))
    return x + out, (c_kv, k_rope)


@jax.named_scope("dense_ffn")
def _dense_ffn(blk, x, cfg: LatentMoEConfig):
    h = parts.rmsnorm(x, blk["ln_ffn"], cfg.rms_norm_eps)
    y = parts.swiglu(h, parts.w(blk, "w_gate"), parts.w(blk, "w_up"),
                     parts.w(blk, "w_down"))
    return x + y.astype(x.dtype)


def route(blk, h, cfg):
    """``h [T,D]`` -> the chosen experts ``idx [T,k]`` (of all
    ``routed_experts_total``) and their weights ``[T,k]`` f32.  The config
    says how: ``scoring_func`` ("sigmoid", or "softmax" over all experts, in
    f32), ``router_bias`` (the top k is taken of score + the layer's
    selection bias, or of the scores alone); the weights come from the
    scores alone, renormalised over the k (their sum + ``router_eps``) and
    times ``routed_scaling_factor``."""
    logits = jnp.dot(h, blk["router"], preferred_element_type=jnp.float32)
    if cfg.scoring_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring_func {cfg.scoring_func!r}: 'sigmoid' or "
                         "'softmax'")
    chosen_by = scores + blk["router_bias"] if cfg.router_bias else scores
    _, idx = lax.top_k(chosen_by, cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True)
                         + cfg.router_eps) * cfg.routed_scaling_factor)
    return idx, weights


_GMM_TILING = (256, 1024, 1024)
#: an expert's matrix of at most this many elements is one tile
_GMM_WHOLE = 2048 * 768


def _gmm_tiling(k: int, n: int):
    """The megablox tile ``(rows, k, n)`` for experts of ``[k,n]``, from
    the operands' shape alone.  A matrix that fits VMEM whole is one tile,
    with 128 rows (``[.,2048] x [128,2048,768]`` at 131,072 rows 3.13 ms
    against 4.84 at ``_GMM_TILING``, at 512 rows 0.65 against 0.92; ``[.,768]
    x [128,768,2048]`` 3.32 against 4.31 and 0.66 against 0.87; PERF.md §6,
    PR 32); larger ones (``kimi_k2``'s 7168 x 2048) keep ``_GMM_TILING``,
    the best PR 28 read for them."""
    return (128, k, n) if k * n <= _GMM_WHOLE else _GMM_TILING


def _grouped_matmul(lhs, rhs, sizes):
    """``lhs [m,k]`` in groups of ``sizes`` rows against ``rhs [G,k,n]`` ->
    ``[m,n]`` f32; rows past ``sum(sizes)`` hold nothing the caller may use.
    The megablox kernel on a TPU (PERF.md §6, PR 28 and PR 32 have the
    candidates' numbers), ``lax.ragged_dot`` elsewhere.  Rows that do not
    fill the kernel's tiles are padded to them, and fewer rows than a tile
    (a decode step's pairs) are one tile of their own, in whole sublanes:
    ``ragged_dot`` on the chip would read every group's matrix, the other
    layers' too where the experts are one stack."""
    tiling = _gmm_tiling(*rhs.shape[1:])
    if jax.default_backend() != "tpu":
        return lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = lhs.shape[0]
    rows = min(tiling[0], -(-m // 16) * 16)
    pad = -m % rows
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
              tiling=(rows,) + tiling[1:])
    return out[:m] if pad else out


#: (token, expert) pairs a pass of an all-held layer takes: what bounds its
#: temporaries (gathered rows, two f32 activations, the output), whatever T
_PAIRS_A_PASS = 32768


def _rows_by_run(group, batch: int, n_experts: int):
    """Pairs on each expert by run of tokens ``[batch, E]``; ``group [T*k]``
    holds each pair's expert, ``n_experts`` for a pair that is not held."""
    return jnp.sum(group.reshape(batch, -1, 1) == jnp.arange(n_experts),
                   axis=1, dtype=jnp.int32)


def _grouped_swiglu(blk, rows, sizes, limit: Optional[float] = None):
    """``rows`` in groups of ``sizes`` through the layer's experts (SwiGLU
    clamped at ``limit``, where one is given).  Where
    ``blk`` holds the experts of every layer as one stack (``first_group``
    says where this layer's begin), the stack is read in place: the other
    layers' groups get no rows."""
    first = blk.get("first_group")
    if first is not None:
        sizes = lax.dynamic_update_slice(
            jnp.zeros(blk["we_gate"].shape[:1], sizes.dtype), sizes, (first,))
    with jax.named_scope("experts"):
        g = _grouped_matmul(rows, parts.w(blk, "we_gate"), sizes)
        u = _grouped_matmul(rows, parts.w(blk, "we_up"), sizes)
        a = parts.gated(g, u, limit).astype(rows.dtype)
        return _grouped_matmul(a, parts.w(blk, "we_down"), sizes)


def _all_held(blk, h, idx, weights, cfg, batch: int,
              limit: Optional[float]):
    """``held_experts`` where every routed expert is held: all ``T*k`` pairs
    are computed, so there is no slack to leave and nothing to mask.  Sorted
    by expert, they are taken ``_PAIRS_A_PASS`` at a time (no temporary
    grows with T), and a pass's output rows go back to their pairs' places
    by the inverse permutation: a token's k rows then lie side by side and
    are weighted and summed in f32, in time linear in T (PERF.md §6, PR 32
    has the segment sum's and the 0/1 matmul's numbers beside it)."""
    T, D = h.shape
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    N = T * k
    C = min(N, _PAIRS_A_PASS)
    n_passes = -(-N // C)
    with jax.named_scope("dispatch"):
        group = idx.reshape(-1)
        rows = _rows_by_run(group, batch, E)
        counts = jnp.sum(rows, axis=0)
        starts = jnp.cumsum(counts) - counts
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        # where each pair's row lies among the sorted ones
        place = jnp.zeros((N,), jnp.int32).at[order].set(
            jnp.arange(N, dtype=jnp.int32))
        order = jnp.pad(order, (0, n_passes * C - N))

    def one_pass(i):
        lo = i * C
        with jax.named_scope("dispatch"):
            pairs = lax.dynamic_slice(order, (lo,), (C,))
            taken = jnp.take(h, pairs // k, axis=0)
            sizes = jnp.clip(jnp.minimum(starts + counts, lo + C)
                             - jnp.maximum(starts, lo), 0, C)
        return _grouped_swiglu(blk, taken, sizes, limit).astype(h.dtype)

    if n_passes == 1:
        out = one_pass(0)
    else:
        out = lax.map(one_pass, jnp.arange(n_passes)).reshape(-1, D)
    with jax.named_scope("combine"):
        mine = jnp.take(out, place, axis=0).reshape(T, k, D)
        y = jnp.einsum("tkd,tk->td", mine.astype(jnp.float32), weights)
    return y, rows


def held_experts(blk, h, idx, weights, cfg, batch: int = 1,
                 limit: Optional[float] = None):
    """The held experts' part of the layer for ``h [T,D]`` (``batch`` equal
    runs of tokens; SwiGLU clamped at ``limit``, where one is given): ``(y
    [T,D] f32, rows routed to each held expert by run [batch,E])``.  Pairs on held experts are
    sorted by expert and taken a chunk at a time: gather the
    tokens' rows, one grouped SwiGLU over the experts the chunk spans,
    weight, add into ``y`` (in the activations' dtype, summed in f32).  The
    loop runs as many chunks as the routing
    filled, so nothing is dropped and nothing of the worst case's size
    stands allocated.  The form follows the config's static shape: a layer
    that holds every routed expert takes :func:`_all_held`; one that holds a
    few of many keeps the chunk with slack and the 0/1-matmul combine, the
    faster there (PERF.md §6, PR 28)."""
    if cfg.n_routed_experts == cfg.routed_experts_total:
        return _all_held(blk, h, idx, weights, cfg, batch, limit)
    T, D = h.shape
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    # pairs a pass takes: a quarter over what even routing sends the held
    # experts (every row of a pass costs, filled or not), in whole tiles
    even = -(-5 * T * k * E // (4 * cfg.routed_experts_total))
    tile = _GMM_TILING[0] if even >= _GMM_TILING[0] else 8
    C = -(-even // tile) * tile
    with jax.named_scope("dispatch"):
        local = idx - cfg.first_expert
        held = (local >= 0) & (local < E)
        group = jnp.where(held, local, E).reshape(-1)           # [T*k]
        rows = _rows_by_run(group, batch, E)
        counts = jnp.sum(rows, axis=0)
        starts = jnp.cumsum(counts) - counts
        n_held = jnp.sum(counts)
        n_chunks = -(-T * k // C)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, n_chunks * C - T * k))
        pair_weight = weights.reshape(-1)

    def chunk(i, y):
        lo = i * C
        with jax.named_scope("dispatch"):
            pairs = lax.dynamic_slice(order, (lo,), (C,))
            valid = lo + jnp.arange(C) < n_held
            token = pairs // k
            rows = jnp.take(h, token, axis=0)
            sizes = jnp.clip(jnp.minimum(starts + counts, lo + C)
                             - jnp.maximum(starts, lo), 0, C)
        out = _grouped_swiglu(blk, rows, sizes, limit)
        with jax.named_scope("combine"):
            # y[t] += the chunk's rows of token t, as one matmul against
            # the 0/1 matrix (token, row): a scatter-add of the same rows
            # takes twice as long on the chip (PERF.md §6, PR 28)
            out = jnp.where(valid[:, None],
                            out * jnp.take(pair_weight, pairs)[:, None], 0.0)
            rows_of = (token[None, :] == jnp.arange(T)[:, None])
            return y + jnp.dot(rows_of.astype(h.dtype), out.astype(h.dtype),
                               preferred_element_type=jnp.float32)

    y = lax.fori_loop(0, -(-n_held // C), chunk,
                      jnp.zeros((T, D), jnp.float32))
    return y, rows


@jax.named_scope("moe")
def _moe_ffn(blk, x, cfg: LatentMoEConfig):
    """``x [B,S,D]`` -> ``(x + held experts' part + shared expert,
    rows routed to each held expert by batch row [B,E])``."""
    B, S, D = x.shape
    h = parts.rmsnorm(x, blk["ln_ffn"], cfg.rms_norm_eps).reshape(B * S, D)
    with jax.named_scope("router"):
        idx, weights = route(blk, h, cfg)
    y, rows = held_experts(blk, h, idx, weights, cfg, batch=B)
    with jax.named_scope("shared_expert"):
        y = y + parts.swiglu(h, parts.w(blk, "ws_gate"),
                             parts.w(blk, "ws_up"), parts.w(blk, "ws_down"))
    with jax.named_scope("combine"):
        return x + y.astype(x.dtype).reshape(B, S, D), rows


def _run(params, tokens, cfg: LatentMoEConfig, want_cache: bool):
    S = tokens.shape[1]
    cos, sin = _rotary(cfg, jnp.arange(S))
    x = parts.embed(params, tokens, cfg)
    latents = []
    for blk in params["dense"]:
        x, latent = _mla(blk, x, cfg, cos, sin)
        latents.append(latent)
        x = _dense_ffn(blk, x, cfg)

    def expert_layer(x, blk):
        x, latent = _mla(blk, x, cfg, cos, sin)
        x, rows = _moe_ffn(blk, x, cfg)
        return x, (rows, latent if want_cache else None)

    x, (rows, scanned) = lax.scan(expert_layer, x, params["experts"])
    cache = None
    if want_cache:
        cache = tuple(
            jnp.concatenate([jnp.stack([lat[i] for lat in latents]),
                             scanned[i]]) if latents else scanned[i]
            for i in range(2))
    return parts.head(params, x[:, -1], cfg), rows.transpose(1, 0, 2), cache


def prefill(params, tokens, cfg: LatentMoEConfig):
    """``tokens [B,S]`` -> ``(logits [B,V] f32 of the last position, rows
    routed to held experts [B, expert layers, E] int32, latent cache)``; the
    cache is ``(c_kv [L,B,S,kv_lora_rank], k_rope [L,B,S,qk_rope_head_dim])``."""
    return _run(params, tokens, cfg, True)


def forward(params, tokens, cfg: LatentMoEConfig):
    """The served step: ``prefill`` without the cache."""
    return _run(params, tokens, cfg, False)[:2]


# ---------------------------------------------------------------------------
# One token through the latent cache, in the absorbed form
# ---------------------------------------------------------------------------

def _mla_decode(blk, x, c_kv_cache, k_rope_cache, pos, cfg, cos, sin):
    """``x [B,D]`` at position ``pos`` against ``c_kv_cache [B,S,rkv]`` and
    ``k_rope_cache [B,S,dr]`` (this token's latents written at ``pos``):
    ``W_kb`` is folded into the query and ``W_vb`` into the output, so
    attention runs over the latents and no per-head key or value exists."""
    h = parts.rmsnorm(x, blk["ln_attn"], cfg.rms_norm_eps)[:, None]   # [B,1,D]
    q_nope, q_rope, c_kv, k_rope = latents(blk, h, cfg, cos, sin)
    c_kv_cache = lax.dynamic_update_slice_in_dim(c_kv_cache, c_kv, pos, 1)
    k_rope_cache = lax.dynamic_update_slice_in_dim(k_rope_cache, k_rope,
                                                   pos, 1)
    q_lat = jnp.einsum("bhsk,chk->bhsc", q_nope, parts.w(blk, "w_kb"))
    scores = (jnp.einsum("bhsc,btc->bhst", q_lat, c_kv_cache,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhsk,btk->bhst", q_rope, k_rope_cache,
                           preferred_element_type=jnp.float32))
    scores = scores * softmax_scale(cfg)
    seen = jnp.arange(c_kv_cache.shape[1]) <= pos
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    o_lat = jnp.einsum("bhst,btc->bhsc", p.astype(x.dtype), c_kv_cache)
    o = jnp.einsum("bhsc,chk->bhsk", o_lat, parts.w(blk, "w_vb"))
    out = jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"))
    return x + out[:, 0], c_kv_cache, k_rope_cache


def decode_step(params, token, cache, pos, cfg: LatentMoEConfig):
    """``token [B]`` at position ``pos`` -> ``(logits [B,V], cache)``; the
    cache's sequence axis is as long as the caller allocated."""
    c_kv_all, k_rope_all = cache
    cos, sin = _rotary(cfg, jnp.reshape(pos, (1,)))
    x = parts.embed(params, token, cfg)
    n_dense = len(params["dense"])
    new_ckv, new_kr = [], []
    for i, blk in enumerate(params["dense"]):
        x, ckv, kr = _mla_decode(blk, x, c_kv_all[i], k_rope_all[i], pos,
                                 cfg, cos, sin)
        new_ckv.append(ckv)
        new_kr.append(kr)
        x = _dense_ffn(blk, x[:, None], cfg)[:, 0]

    def expert_layer(x, scanned):
        blk, ckv, kr = scanned
        x, ckv, kr = _mla_decode(blk, x, ckv, kr, pos, cfg, cos, sin)
        x, _ = _moe_ffn(blk, x[:, None], cfg)
        return x[:, 0], (ckv, kr)

    x, (ckv, kr) = lax.scan(
        expert_layer, x,
        (params["experts"], c_kv_all[n_dense:], k_rope_all[n_dense:]))
    cache = (jnp.concatenate([jnp.stack(new_ckv), ckv]),
             jnp.concatenate([jnp.stack(new_kr), kr]))
    return parts.head(params, x, cfg), cache


# ---------------------------------------------------------------------------
# What a forward needs
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg: LatentMoEConfig) -> Dict[str, float]:
    """Matrix elements a token passes through, by part of a layer."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    expert = 3 * D * cfg.moe_intermediate_size
    return {
        "mla": (D * cfg.q_lora_rank + cfg.q_lora_rank * H * cfg.qk_head_dim
                + D * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank * H
                * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                + H * cfg.v_head_dim * D),
        "dense_ffn": 3 * D * cfg.intermediate_size,
        "router": D * cfg.routed_experts_total,
        "shared_expert": expert * cfg.n_shared_experts,
        # a token's expected pairs on held experts under even routing
        "held_experts": expert * cfg.num_experts_per_tok
        * cfg.n_routed_experts / cfg.routed_experts_total,
    }


def flops_per_inference(cfg: LatentMoEConfig) -> float:
    """FLOPs one prompt of ``seq_len`` tokens needs: every matrix a token
    passes through, the causal half of the scores and of P·v, the pairs on
    held experts at even routing's expectation, the last position's head.
    No padding, norms or rotary."""
    S, H = cfg.seq_len, cfg.num_attention_heads
    part = layer_matmul_params(cfg)
    n_dense, n_moe = cfg.first_k_dense_replace, cfg.n_expert_layers
    per_token = 2.0 * (
        cfg.num_hidden_layers * part["mla"] + n_dense * part["dense_ffn"]
        + n_moe * (part["router"] + part["shared_expert"]
                   + part["held_experts"]))
    # a query at position t sees t + 1 keys: S (S + 1) / 2 pairs a head
    attention = (2.0 * cfg.num_hidden_layers * H
                 * (cfg.qk_head_dim + cfg.v_head_dim) * S * (S + 1) / 2)
    head = 2.0 * cfg.hidden_size * cfg.vocab_size
    return S * per_token + attention + head
