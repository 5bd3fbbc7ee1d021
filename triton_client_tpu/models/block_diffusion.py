"""Generation by diffusion over blocks, on a Qwen3-MoE layer: SDAR's block.

The third block of the zoo.  What it has that the other two have not:

* **Grouped-query attention with a norm over each head.**  32 query heads
  read 4 key/value heads (``ops/flash_attention.py`` maps query head ``h`` to
  key head ``h // 8`` and never repeats them in HBM); q and k go through an
  RMSNorm over the head's 128 values with a learned scale each, before the
  rotation (RoPE over the whole head, half-split pairs).
* **An expert layer that holds every expert it routes over**: a softmax over
  all 128, the top 8, renormalised, no bias, no shared expert.  The layer is
  ``latent_moe.held_experts`` itself, whose all-held form takes ``T*k`` pairs
  exactly; the experts' matrices of every layer stay one stack that the
  grouped matmul reads in place (a group's rows are zero outside the layer),
  so a pass never copies a layer's 1.2 GB.
* **A step that does not yield one token a sequence.**  The prompt is
  prefilled under a mask that is causal over blocks of ``block_length``
  (row ``i`` sees key ``j`` where ``j // B <= i // B``) and its keys (normed
  and rotated) and values go to a cache.  Then, block by block, ``B`` MASK
  ids are run ``denoising_steps`` times against the cache, attending to
  themselves in both directions; each pass commits the most confident of the
  positions still masked (and every one over ``confidence_threshold``, when
  that is set).  **The cache is written once a block, after its last token
  is committed**, since every position's keys depend on its peers' final
  tokens; no pass reads a stale key.  **No pass is run for that alone: the
  block's final tokens ride the next block's first pass**, ``2B`` rows a
  sequence under the mask that is causal over blocks.  The riding rows see
  the cache and themselves, so their keys and values are the ones a pass
  of their own would give; the open block's rows see them as they will lie
  in the cache, where they are written before the second pass reads it.  A
  pass is bound by the experts' weights it reads, not by its rows, so the
  four more rows cost the experts they newly touch and not a pass.  **The
  last block's keys are never computed**: ``generate`` returns no cache,
  and no output depends on them.  The output at position ``i`` predicts
  the token at ``i`` (no shift).

The whole generation of a batch is one program (``generate``).  It also
returns what the device counted on the way, a row of the batch each, for
``ModelStats``' queue of device counters.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import latent_moe as lm
from . import parts


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    """The source's keys under the source's names, then what the source
    leaves to the caller of its ``generate`` (the configuration file lists
    those under ``assumed``) and the served shape."""

    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    block_length: int
    denoising_steps: int
    mask_token_id: int
    confidence_threshold: Optional[float]
    seq_len: int        # the prompt, a multiple of ``block_length``
    new_tokens: int     # generated, a multiple of ``block_length``
    weights_seed: int

    def __post_init__(self):
        B = self.block_length
        if B & (B - 1) or self.seq_len % B or self.new_tokens % B \
                or B % self.denoising_steps:
            raise ValueError(
                f"block_length {B}: a power of two that divides the prompt "
                f"({self.seq_len}) and the answer ({self.new_tokens}), and "
                f"a multiple of denoising_steps ({self.denoising_steps})")

    @classmethod
    def from_file(cls, cfg: dict) -> "BlockDiffusionConfig":
        """A configuration file of ``chipbench/configs`` (the source's keys
        at the top, ``assumed.generation`` and ``served`` below)."""
        names = {f.name for f in dataclasses.fields(cls)}
        generation = cfg["assumed"]["generation"]
        return cls(**{k: v for k, v in cfg.items() if k in names},
                   **{k: generation[k] for k in (
                       "block_length", "denoising_steps", "mask_token_id",
                       "confidence_threshold")},
                   seq_len=cfg["served"]["seq_len"],
                   new_tokens=cfg["served"]["new_tokens"],
                   weights_seed=cfg["served"]["weights_seed"])

    # what ``latent_moe.route`` and ``held_experts`` ask of a configuration
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def routed_experts_total(self) -> int:
        return self.num_experts

    first_expert = 0
    scoring_func = "softmax"
    router_bias = False
    routed_scaling_factor = 1.0   # ``norm_topk_prob`` and nothing more
    router_eps = 1e-20

    # what ``tr.serve_mesh`` asks of a configuration
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_experts(self) -> int:
        return self.num_experts

    moe = True

    @property
    def n_blocks(self) -> int:
        return self.new_tokens // self.block_length


#: SDAR-30B-A3B-Chat's ``config.json`` cut to one stage of an 8-stage
#: pipeline: six of its 48 layers, each whole (all 128 experts), with the
#: embedding and the head; every width as published
#: (``chipbench/configs/sdar_30b_a3b.json`` states the cut and what is
#: assumed of the generation).
SDAR_30B_A3B_STAGE = BlockDiffusionConfig(
    hidden_size=2048, num_hidden_layers=6, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, moe_intermediate_size=768,
    num_experts=128, num_experts_per_tok=8, vocab_size=151936,
    rms_norm_eps=1e-6, rope_theta=1000000.0, block_length=4,
    denoising_steps=4, mask_token_id=151669, confidence_threshold=None,
    seq_len=1024, new_tokens=32, weights_seed=32)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: BlockDiffusionConfig):
    """``{leaf: (shape, scale of the normal draw)}`` of one layer; an
    expert's leaves are per expert."""
    D, H, Hkv = (cfg.hidden_size, cfg.num_attention_heads,
                 cfg.num_key_value_heads)
    dh, F = cfg.head_dim, cfg.moe_intermediate_size
    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    return {"w_q": ((D, H, dh), fan(D)), "w_k": ((D, Hkv, dh), fan(D)),
            "w_v": ((D, Hkv, dh), fan(D)), "w_o": ((H, dh, D), fan(H * dh)),
            "router": ((D, cfg.num_experts), 0.02),
            "we_gate": ((D, F), fan(D)), "we_up": ((D, F), fan(D)),
            "we_down": ((F, D), fan(F))}


def _layer_params(cfg: BlockDiffusionConfig, layer: int):
    """One layer's leaves in bfloat16, an expert under its id; the four
    norms are ones."""
    D, dh = cfg.hidden_size, cfg.head_dim
    return parts.draw_layer(
        cfg.weights_seed, layer, _leaf_shapes(cfg),
        {"ln_attn": D, "ln_ffn": D, "ln_qh": dh, "ln_kh": dh},
        jnp.arange(cfg.num_experts))


def init_params(cfg: BlockDiffusionConfig, quantized: bool = False
                ) -> Dict[str, Any]:
    """``{"embed", "final_ln", "head", "layers": leaves stacked for the scan,
    "experts": every layer's experts as one stack [layers * experts, ...]}``.
    Quantised (the int8 control), the experts' leaves stay with their
    layers, where the scan hands them to ``parts.w`` a layer at a time, and
    ``experts`` is empty.  A layer is drawn, written into the stacks in
    place and let go before the next one exists: the 8.7 GB are never held
    twice."""
    prep = jax.jit(parts.quantize_weights) if quantized else (lambda x: x)
    L = cfg.num_hidden_layers
    stacked, experts = {}, {}
    for i in range(L):
        layer = prep(_layer_params(cfg, i))
        if not quantized:
            parts.stack(experts, {name: layer.pop(name)
                                  for name in parts.EXPERT_LEAVES}, i, L,
                        flat=True)
        parts.stack(stacked, layer, i, L)
    return dict(parts.outer_params(cfg), layers=stacked, experts=experts)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _qkv(blk, x, cfg: BlockDiffusionConfig, cos, sin):
    """``x [b,S,D]`` -> q ``[b,H,S,dh]``, k and v ``[b,Hkv,S,dh]``: q and k
    normed over the head and rotated, as the cache holds them."""
    h = parts.rmsnorm(x, blk["ln_attn"], cfg.rms_norm_eps)
    q = jnp.einsum("bsd,dhk->bhsk", h, parts.w(blk, "w_q"))
    k = jnp.einsum("bsd,dhk->bhsk", h, parts.w(blk, "w_k"))
    v = jnp.einsum("bsd,dhk->bhsk", h, parts.w(blk, "w_v"))
    with jax.named_scope("qk_norm"):
        q = parts.rmsnorm(q, blk["ln_qh"], cfg.rms_norm_eps)
        k = parts.rmsnorm(k, blk["ln_kh"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        q, k = parts.rotate(q, cos, sin), parts.rotate(k, cos, sin)
    return q, k, v


def _out_proj(blk, x, o):
    return x + jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"))


@jax.named_scope("moe")
def _moe(blk, x, cfg: BlockDiffusionConfig):
    """``x [b,S,D]`` -> ``(x + the experts' part, rows routed to each expert
    by batch row [b,E], the experts each token chose [b,S,k])``."""
    b, S, D = x.shape
    h = parts.rmsnorm(x, blk["ln_ffn"], cfg.rms_norm_eps).reshape(b * S, D)
    with jax.named_scope("router"):
        idx, weights = lm.route(blk, h, cfg)
    y, rows = lm.held_experts(blk, h, idx, weights, cfg, batch=b)
    with jax.named_scope("combine"):
        return (x + y.astype(x.dtype).reshape(b, S, D), rows,
                idx.reshape(b, S, -1))


def _scan_layers(params, cfg: BlockDiffusionConfig, x, layer_fn, *scanned):
    """``layer_fn(blk, x, *scanned of the layer) -> (x, out)`` over the
    layers under one ``lax.scan``; ``blk`` holds the layer's own leaves
    and, where the experts are one stack, the stack whole with the layer's
    first group."""
    E = cfg.num_experts

    def body(x, xs):
        layer, leaves, rest = xs
        blk = dict(leaves, **params["experts"])
        if params["experts"]:
            blk["first_group"] = layer * E
        return layer_fn(blk, x, *rest)

    return lax.scan(body, x, (jnp.arange(cfg.num_hidden_layers),
                              params["layers"], scanned))


# ---------------------------------------------------------------------------
# Prefill, a pass, the loop
# ---------------------------------------------------------------------------

@jax.named_scope("prefill")
def prefill(params, tokens, cfg: BlockDiffusionConfig,
            want_logits: bool = False):
    """``tokens [b,P]`` under the block mask -> ``(cache, rows routed to
    each expert [b,L,E], logits [b,P,V] or None)``; the cache is ``(k, v)``,
    each ``[L,b,Hkv,P + new_tokens,dh]`` with the prompt's part written."""
    from ..ops import flash_attention

    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta,
                            jnp.arange(tokens.shape[1]))

    def layer(blk, x):
        with jax.named_scope("attention"):
            q, k, v = _qkv(blk, x, cfg, cos, sin)
            o = flash_attention(q, k, v, causal=True,
                                mask_block=cfg.block_length)
            x = _out_proj(blk, x, o)
        x, rows, _ = _moe(blk, x, cfg)
        return x, (k, v, rows)

    x, (k, v, rows) = _scan_layers(
        params, cfg, parts.embed(params, tokens, cfg), layer)
    room = [(0, 0)] * 3 + [(0, cfg.new_tokens), (0, 0)]
    logits = parts.head(params, x, cfg) if want_logits else None
    return (jnp.pad(k, room), jnp.pad(v, room)), rows.transpose(1, 0, 2), \
        logits


@jax.named_scope("pass")
def block_pass(params, cache, tokens, start, cfg: BlockDiffusionConfig):
    """The rows ``tokens [b,R]`` of one block or of two adjacent ones (``R``
    = ``B`` or ``2B``) at positions ``start ..`` through every layer,
    attending to the cache's keys before ``start`` and among themselves
    under the mask that is causal over blocks: a block sees all of itself,
    the second block sees the first as well, the first never the second
    -> ``(x [b,R,D] before the head, the rows' (k, v) [L,b,Hkv,R,dh], rows
    routed [b,L,E], the experts each row chose [b,R,L,k])``.  The cache is
    read, never written.  Of two blocks the first's keys and values are
    what a pass over it alone gives, and the second's ``x`` what a pass
    over it gives against a cache that holds them: ``generate`` runs a
    block's final tokens so beside the next block's first pass."""
    R, B = tokens.shape[1], cfg.block_length
    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta,
                            start + jnp.arange(R))
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)
    block = jnp.arange(R) // B
    among = block[:, None] >= block[None, :]

    def layer(blk, x, k_cache, v_cache):
        with jax.named_scope("attention"):
            q, k, v = _qkv(blk, x, cfg, cos, sin)
            with jax.named_scope("cache_attend"):
                b = q.shape[0]
                qg = q.reshape(b, -1, group, R, cfg.head_dim)
                past = jnp.einsum("bgrqk,bgtk->bgrqt", qg, k_cache,
                                  preferred_element_type=jnp.float32)
                seen = jnp.arange(k_cache.shape[2]) < start
                own = jnp.einsum("bgrqk,bgtk->bgrqt", qg, k,
                                 preferred_element_type=jnp.float32)
                s = jnp.concatenate(
                    [jnp.where(seen, past, -1e30),
                     jnp.where(among, own, -1e30)], axis=-1) * scale
                p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
                o = (jnp.einsum("bgrqt,bgtk->bgrqk", p[..., :-R], v_cache)
                     + jnp.einsum("bgrqt,bgtk->bgrqk", p[..., -R:], v))
                o = o.reshape(q.shape)
            x = _out_proj(blk, x, o)
        x, rows, routes = _moe(blk, x, cfg)
        return x, (k, v, rows, routes)

    x, (k, v, rows, routes) = _scan_layers(
        params, cfg, parts.embed(params, tokens, cfg), layer, *cache)
    return x, (k, v), rows.transpose(1, 0, 2), routes.transpose(1, 2, 0, 3)


def generate(params, tokens, cfg: BlockDiffusionConfig):
    """``tokens [b,P]`` -> the answer and what the device counted:

    * ``tokens [b,G]`` int32 and ``commit_pass [b,G]`` int32 (the pass, 0 ..
      ``denoising_steps - 1``, at which each position was committed);
    * ``logits [b,2,V]`` f32: the logits of the first position (lowest
      index) committed at block 0's pass 0, and of the first committed at
      the last block's last pass; ``routes [b,2,L,k]`` int32: the experts
      that position chose in each layer of that pass (a reference that
      recomputes the row has to be told: where two experts lie closer than
      bfloat16's rounding the choice is not the reference's);
    * ``counters``: ``expert_rows [b,L,E]`` (pairs on each expert, the
      prefill's and every row's of every pass, the riding rows' too),
      ``denoise_passes [b]`` (passes run: ``denoising_steps`` a block under
      the static rule, none for the cache alone), ``denoise_tokens [b]``,
      ``experts_touched [b]`` (over passes and layers; entry ``r`` counts
      the rows ``0 .. r``).

    Block 0 runs its passes alone (the prefill wrote the keys before it);
    every later block's first pass carries the block before it
    (``block_pass``) and writes that block's keys and values; after the
    last block nothing runs."""
    b, P = tokens.shape
    B, T, G, V = (cfg.block_length, cfg.denoising_steps, cfg.new_tokens,
                  cfg.vocab_size)
    cache, expert_rows, _ = prefill(params, tokens, cfg)
    row = jnp.arange(b)
    zeros = jnp.zeros((b, G), jnp.int32)
    none = jnp.zeros((b,), jnp.int32)
    no_routes = jnp.zeros(
        (b, cfg.num_hidden_layers, cfg.num_experts_per_tok), jnp.int32)
    all_masked = {
        "t": jnp.int32(0),
        "tokens": jnp.full((b, B), cfg.mask_token_id, jnp.int32),
        "masked": jnp.ones((b, B), bool),
        "when": jnp.zeros((b, B), jnp.int32),
        "committed": none,
        "first_logits": jnp.zeros((b, V), jnp.float32),
        "last_logits": jnp.zeros((b, V), jnp.float32),
        "first_routes": no_routes, "last_routes": no_routes,
        "rows": jnp.zeros_like(expert_rows),
        "touched": none}

    def masked_left(s):
        return (s["t"] < T) & jnp.any(s["masked"])

    def end_pass(s, x, rows, routes):
        """The end of a pass: the head over the open block's ``x [b,B,D]``,
        the commit rule, and the pass's ``rows`` among the counters."""
        lg = parts.head(params, x, cfg)                         # [b,B,V]
        with jax.named_scope("confidence"):
            conf = jnp.max(jax.nn.softmax(lg, axis=-1), axis=-1)
            best = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        with jax.named_scope("commit"):
            open_conf = jnp.where(s["masked"], conf, -jnp.inf)
            _, top = lax.top_k(open_conf, B // T)
            commit = jnp.any(top[..., None] == jnp.arange(B), axis=1)
            if cfg.confidence_threshold is not None:
                commit |= open_conf > cfg.confidence_threshold
            commit &= s["masked"]
            first = jnp.argmax(commit, axis=-1)     # lowest index
            mine, my_routes = lg[row, first], routes[row, first]
            # a sequence that has no mask left rides along with the
            # batch: its last pass is the last at which it committed
            moved = jnp.any(commit, axis=-1, keepdims=True)
            return {
                "t": s["t"] + 1,
                "tokens": jnp.where(commit, best, s["tokens"]),
                "masked": s["masked"] & ~commit,
                "when": jnp.where(commit, s["t"], s["when"]),
                "committed": s["committed"]
                + jnp.sum(commit, axis=-1, dtype=jnp.int32),
                "first_logits": jnp.where(s["t"] == 0, mine,
                                          s["first_logits"]),
                "last_logits": jnp.where(moved, mine, s["last_logits"]),
                "first_routes": jnp.where(s["t"] == 0, my_routes,
                                          s["first_routes"]),
                "last_routes": jnp.where(moved[..., None], my_routes,
                                         s["last_routes"]),
                "rows": s["rows"] + rows,
                "touched": s["touched"] + parts.touched(rows),
            }

    def one_block(n, state, rides=True):
        cache, out, logits, routes, counters = state
        start = P + n * B

        def one_pass(s):
            x, _, rows, routes = block_pass(params, cache, s["tokens"],
                                            start, cfg)
            return end_pass(s, x, rows, routes)

        with jax.named_scope("denoise"):
            s = all_masked
            if rides:
                # pass 0: the block before, all tokens final, rides it; its
                # keys and values are the ones this and the later blocks read
                before = lax.dynamic_slice_in_dim(out["tokens"], (n - 1) * B,
                                                  B, 1)
                x, kv, rows, chose = block_pass(
                    params, cache,
                    jnp.concatenate([before, s["tokens"]], axis=1),
                    start - B, cfg)
                s = end_pass(s, x[:, B:], rows, chose[:, B:])
                with jax.named_scope("cache_commit"):
                    cache = tuple(lax.dynamic_update_slice_in_dim(
                        c, new[..., :B, :], start - B, 3)
                        for c, new in zip(cache, kv))
            s = lax.while_loop(masked_left, one_pass, s)
        out = {"tokens": lax.dynamic_update_slice_in_dim(
                   out["tokens"], s["tokens"], n * B, 1),
               "commit_pass": lax.dynamic_update_slice_in_dim(
                   out["commit_pass"], s["when"], n * B, 1)}
        logits = (jnp.where(n == 0, s["first_logits"], logits[0]),
                  s["last_logits"])
        routes = (jnp.where(n == 0, s["first_routes"], routes[0]),
                  s["last_routes"])
        counters = {
            "expert_rows": counters["expert_rows"] + s["rows"],
            "denoise_passes": counters["denoise_passes"] + s["t"],
            "denoise_tokens": counters["denoise_tokens"] + s["committed"],
            "experts_touched": counters["experts_touched"] + s["touched"]}
        return cache, out, logits, routes, counters

    # block 0 stands before the loop: nothing rides its first pass
    state = one_block(0, (
        cache, {"tokens": zeros, "commit_pass": zeros},
        (jnp.zeros((b, V), jnp.float32),) * 2, (no_routes,) * 2,
        {"expert_rows": expert_rows, "denoise_passes": none,
         "denoise_tokens": none, "experts_touched": none}), rides=False)
    _, out, logits, routes, counters = lax.fori_loop(
        1, cfg.n_blocks, one_block, state)
    return {**out, "logits": jnp.stack(logits, axis=1),
            "routes": jnp.stack(routes, axis=1), "counters": counters}


# ---------------------------------------------------------------------------
# What a request needs
# ---------------------------------------------------------------------------

def flops_per_inference(cfg: BlockDiffusionConfig) -> float:
    """FLOPs one request needs: the prompt through every matrix a token
    passes (its 8 experts among them) with the block-causal half of the
    scores, no head; then a block's rows ``denoising_steps + 1`` times
    through the layers against the keys so far (the last time final,
    beside the next block's first pass), ``denoising_steps`` of them with
    the head.  The last block's rows are counted so too, though nothing
    runs them the last time: 0.3% of the total, kept so that the count
    stays the benchmark's yardstick to the digit
    (``chipbench/flop_counts/sdar_30b_a3b.py``).  No padding, norms or
    rotary."""
    D, H, Hkv, dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    B, T, P = cfg.block_length, cfg.denoising_steps, cfg.seq_len
    attention = D * (H + 2 * Hkv) * dh + H * dh * D
    per_token = 2.0 * cfg.num_hidden_layers * (
        attention + D * cfg.num_experts
        + cfg.num_experts_per_tok * 3 * D * cfg.moe_intermediate_size)

    def scores(rows, keys):  # q.k and p.v, every head
        return 2.0 * cfg.num_hidden_layers * H * 2 * dh * rows * keys

    # a prompt row sees the keys up to the end of its block
    total = P * per_token + sum(
        scores(B, end) for end in range(B, P + 1, B))
    head = 2.0 * D * cfg.vocab_size
    for n in range(cfg.n_blocks):
        keys = P + (n + 1) * B
        total += (T + 1) * (B * per_token + scores(B, keys)) + T * B * head
    return total
