"""A stack run several times over one set of weights: Ouro's looped block.

The fourth block of the zoo.  What it has that the other three have not:

* **Depth by iteration.**  The whole stack of ``num_hidden_layers`` layers is
  run ``total_ut_steps`` times a token, the output of one loop step (after
  the final norm, which closes *every* step) being the input of the next.
  The weights are the same at every step: one ``lax.scan`` over the stacked
  layers inside one ``lax.scan`` over the steps, the stacks closed over
  once, so a token's arithmetic is ``total_ut_steps`` times what the
  parameters alone would say and a decode step reads them as many times.
* **A cache for every loop step.**  Layer ``l`` at step ``t`` attends to the
  keys and values that *this* ``l`` at *this* ``t`` gave the earlier
  positions: ``[steps, layers, P + G, heads, b, head_dim]`` twice.  It is a
  carry of both scans, written in place (the prefill a whole prompt a
  layer-step, a decode step one position) and read where it was written.
* **A sandwich of norms.**  A norm before and after each branch, each with
  its own scale: ``h = x + norm(attn(norm(x)))``, ``y = h + norm(ffn(norm(
  h)))``.  The residual stream and every norm are float32; the matrices
  see bfloat16.
* **An exit gate.**  After each step's final norm a ``hidden -> 1`` linear
  gives ``lambda_t = sigmoid(g_t)``; ``p_t = lambda_t prod_{s<t}(1 -
  lambda_s)`` (the last step takes what is left) is the probability of
  leaving at ``t``, and a token leaves at the first step whose cumulated
  probability reaches ``early_exit_threshold``: its logits are the head's
  over that step's state.  At the published threshold of 1 that is the last
  step for every token.  **Every step is run for every token whatever the
  threshold** (the later tokens read this one's keys at every step), so
  under a threshold below 1 the rule chooses the state that is read out and
  what ``loop_steps`` counts, not the work done: depth that depends on the
  data in a batch whose rows leave at different steps is not built yet.

Attention is full multi-head (as many key/value heads as query heads), RoPE
over the whole head in half-split pairs; the FFN is SwiGLU; the head is
untied.  Generation is greedy and the output at position ``i`` predicts the
token at ``i + 1``; the whole generation of a batch is one program
(``generate``), which also returns what the device counted on the way.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from . import parts


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    """The source's keys under the source's names, then the served shape."""

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    total_ut_steps: int
    early_exit_threshold: float
    seq_len: int        # the prompt
    new_tokens: int     # generated: one by the prefill, the rest decoded
    weights_seed: int

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key/value heads: the looped "
                "block's cache holds a key/value head a query head")
        if self.total_ut_steps < 1 or self.new_tokens < 1:
            raise ValueError("total_ut_steps and new_tokens are at least 1")

    @classmethod
    def from_file(cls, cfg: dict) -> "LoopedConfig":
        """A configuration file of ``chipbench/configs`` (the source's keys
        at the top, ``served`` below)."""
        names = {f.name for f in dataclasses.fields(cls)}
        served = cfg["served"]
        return cls(**{k: v for k, v in cfg.items() if k in names},
                   seq_len=served["seq_len"],
                   new_tokens=served["new_tokens"],
                   weights_seed=served["weights_seed"])

    # what ``tr.serve_mesh`` asks of a configuration
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    n_experts = 0
    moe = False


#: Ouro-2.6B's ``config.json``, the whole model: nothing reduced
#: (``chipbench/configs/ouro_2_6b.json`` states what is assumed of the
#: wiring and of the generation).
OURO_2_6B = LoopedConfig(
    hidden_size=2048, intermediate_size=5632, num_hidden_layers=48,
    num_attention_heads=16, num_key_value_heads=16, head_dim=128,
    vocab_size=49152, rms_norm_eps=1e-6, rope_theta=1000000.0,
    total_ut_steps=4, early_exit_threshold=1.0, seq_len=128, new_tokens=16,
    weights_seed=34)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

#: a layer's four norms: before and after the attention, before and after
#: the FFN
_NORMS = ("ln_attn", "ln_attn_out", "ln_ffn", "ln_ffn_out")


def _leaf_shapes(cfg: LoopedConfig):
    """``{leaf: (shape, scale of the normal draw)}`` of one layer."""
    D, H, dh, F = (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
                   cfg.intermediate_size)
    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    return {"w_q": ((D, H, dh), fan(D)), "w_k": ((D, H, dh), fan(D)),
            "w_v": ((D, H, dh), fan(D)), "w_o": ((H, dh, D), fan(H * dh)),
            "w_gate": ((D, F), fan(D)), "w_up": ((D, F), fan(D)),
            "w_down": ((F, D), fan(F))}


def _layer_params(cfg: LoopedConfig, layer: int):
    """One layer's leaves in bfloat16; the four norms are ones."""
    return parts.draw_layer(cfg.weights_seed, layer, _leaf_shapes(cfg),
                            dict.fromkeys(_NORMS, cfg.hidden_size))


def init_params(cfg: LoopedConfig, quantized: bool = False) -> Dict[str, Any]:
    """``{"embed", "final_ln", "exit_gate", "exit_gate_bias", "head",
    "layers": leaves stacked for the scan}``.  A layer is drawn, written
    into the stacks in place and let go before the next one exists.
    Quantised (the int8 control), a layer's seven matrices are stored as
    ``parts.quantize_weights`` stores them."""
    prep = jax.jit(parts.quantize_weights) if quantized else (lambda x: x)
    L = cfg.num_hidden_layers
    stacked = {}
    for i in range(L):
        parts.stack(stacked, prep(_layer_params(cfg, i)), i, L)
    return dict(parts.outer_params(cfg, exit_gate=(cfg.hidden_size,),
                                   exit_gate_bias=(1,)), layers=stacked)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _qkv(blk, x, cfg: LoopedConfig, cos, sin):
    """``x [b,S,D]`` f32 -> q, k, v ``[b,H,S,dh]`` in the matrices' dtype,
    q and k rotated as the cache holds them."""
    w_q = parts.w(blk, "w_q")
    u = parts.rmsnorm(x, blk["ln_attn"], cfg.rms_norm_eps).astype(w_q.dtype)
    q = jnp.einsum("bsd,dhk->bhsk", u, w_q)
    k = jnp.einsum("bsd,dhk->bhsk", u, parts.w(blk, "w_k"))
    v = jnp.einsum("bsd,dhk->bhsk", u, parts.w(blk, "w_v"))
    with jax.named_scope("rope"):
        return parts.rotate(q, cos, sin), parts.rotate(k, cos, sin), v


def _attention_out(blk, x, o, cfg: LoopedConfig):
    """The heads' output ``o [b,H,S,dh]`` through the out projection,
    normed, joins the stream ``x``."""
    a = jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"),
                   preferred_element_type=jnp.float32)
    with jax.named_scope("sandwich_norm"):
        return x + parts.rmsnorm(a, blk["ln_attn_out"], cfg.rms_norm_eps)


@jax.named_scope("ffn")
def _ffn(blk, x, cfg: LoopedConfig):
    w_gate = parts.w(blk, "w_gate")
    n = parts.rmsnorm(x, blk["ln_ffn"], cfg.rms_norm_eps).astype(w_gate.dtype)
    y = parts.swiglu(n, w_gate, parts.w(blk, "w_up"), parts.w(blk, "w_down"))
    with jax.named_scope("sandwich_norm"):
        return x + parts.rmsnorm(y, blk["ln_ffn_out"], cfg.rms_norm_eps)


def _close_step(params, x, cfg: LoopedConfig):
    """What closes a loop step: the final norm, whose output is the next
    step's input, and the exit gate over it -> ``(x, g [b,S] f32)``."""
    with jax.named_scope("final_norm"):
        x = parts.rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
    with jax.named_scope("exit_gate"):
        g = jnp.einsum("bsd,d->bs", x,
                       params["exit_gate"].astype(jnp.float32)) \
            + params["exit_gate_bias"].astype(jnp.float32)
    return x, g


def _stack(params, cfg: LoopedConfig, tokens, cache, layer_fn):
    """``tokens [b,S]`` through ``total_ut_steps`` x ``num_hidden_layers``
    layer-steps; ``layer_fn(blk, x, cache, t, l) -> (x, cache)`` is the
    layer at step ``t``.  Returns ``(cache, the last position after each
    step's final norm [T,b,D] f32, the gate at every position [T,b,S])``."""
    x = parts.embed(params, tokens, cfg).astype(jnp.float32)
    layers = (jnp.arange(cfg.num_hidden_layers), params["layers"])

    def loop_step(carry, t):
        def layer(carry, scanned):
            l, blk = scanned
            return layer_fn(blk, *carry, t, l), None

        with jax.named_scope("loop_step"):
            (x, cache), _ = lax.scan(layer, carry, layers)
            x, gate = _close_step(params, x, cfg)
        return (x, cache), (x[:, -1], gate)

    (_, cache), (last, gates) = lax.scan(
        loop_step, (x, cache), jnp.arange(cfg.total_ut_steps))
    return cache, last, gates


def _compute_dtype(params):
    dtype = params["layers"]["w_k"].dtype
    return jnp.bfloat16 if dtype == jnp.int8 else dtype


#: the cache lies in memory as its shape reads.  Without this the compiler
#: gives the prefill's carry the attention kernel's layout (the sequence on
#: the lanes) and the decode loop's another, and re-lays the whole cache
#: between the two: a second cache's worth of temporaries (3.6 GB at 16
#: sequences, counted by the chip's compiler for a v5e).
_AS_WRITTEN = Layout(major_to_minor=(0, 1, 2, 3, 4, 5))


def _write(cache, k, v, t, l, pos):
    """The keys and values ``[b,H,S,dh]`` of layer ``l`` at step ``t``,
    positions ``pos ..``, into the cache ``[T,L,positions,H,b,dh]``, in
    place: a position's keys lie together, so a decode step's write is one
    run of memory."""
    at = (t, l, pos, 0, 0, 0)
    return tuple(lax.dynamic_update_slice(c, with_layout_constraint(
        new.transpose(2, 1, 0, 3)[None, None], _AS_WRITTEN), at)
        for c, new in zip(cache, (k, v)))


# ---------------------------------------------------------------------------
# Prefill, a decode step, the exit, the loop
# ---------------------------------------------------------------------------

@jax.named_scope("prefill")
def prefill(params, tokens, cfg: LoopedConfig):
    """``tokens [b,P]`` -> ``(cache, x [T,b,D] of the last position after
    each loop step, gates [T,b,P])``; the cache is ``(k, v)``, each
    ``[T,L,P + new_tokens,H,b,dh]`` with the prompt's part written (the
    last token generated is never fed back: its slot stays empty)."""
    from ..ops import flash_attention

    b, P = tokens.shape
    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta, jnp.arange(P))
    room = (cfg.total_ut_steps, cfg.num_hidden_layers, P + cfg.new_tokens,
            cfg.num_attention_heads, b, cfg.head_dim)
    cache = (jnp.zeros(room, _compute_dtype(params)),) * 2

    def layer(blk, x, cache, t, l):
        with jax.named_scope("attention"):
            q, k, v = _qkv(blk, x, cfg, cos, sin)
            cache = _write(cache, k, v, t, l, 0)
            o = flash_attention(q, k, v, causal=True)
            x = _attention_out(blk, x, o, cfg)
        return _ffn(blk, x, cfg), cache

    return _stack(params, cfg, tokens, cache, layer)


@jax.named_scope("token")
def decode_step(params, cache, token, pos, cfg: LoopedConfig):
    """``token [b]`` at position ``pos`` through every loop step, each
    layer-step writing its key and value at ``pos`` and attending to its
    own cache up to there -> ``(cache, x [T,b,D], gates [T,b])``."""
    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta,
                            jnp.reshape(pos, (1,)))
    scale = 1.0 / math.sqrt(cfg.head_dim)
    seen = jnp.arange(cache[0].shape[2]) <= pos

    def layer(blk, x, cache, t, l):
        with jax.named_scope("attention"):
            q, k, v = _qkv(blk, x, cfg, cos, sin)
            cache = _write(cache, k, v, t, l, pos)
            with jax.named_scope("cache_attend"):
                keys, values = (lax.dynamic_index_in_dim(
                    lax.dynamic_index_in_dim(c, t, 0, False), l, 0, False)
                    for c in cache)
                s = jnp.einsum("bhqk,thbk->bhqt", q, keys,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
                o = jnp.einsum("bhqt,thbk->bhqk", p.astype(q.dtype), values)
            x = _attention_out(blk, x, o, cfg)
        return _ffn(blk, x, cfg), cache

    cache, last, gates = _stack(params, cfg, token[:, None], cache, layer)
    return cache, last, gates[..., 0]


def exit_pdf(gates):
    """``gates [T,...]`` -> the probability of leaving at each step
    ``[T,...]``: ``lambda_t prod_{s<t}(1 - lambda_s)``, the last step
    taking what is left; sums to 1 over the steps."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return (lam * before).at[-1].set(before[-1])


def exit_step(pdf, threshold: float):
    """The first step whose cumulated probability reaches ``threshold``
    (the last where rounding keeps the sum under it) ``[...]`` int32."""
    reached = (jnp.cumsum(pdf, axis=0) >= threshold).at[-1].set(True)
    return jnp.argmax(reached, axis=0).astype(jnp.int32)


def readout(params, last, gates, cfg: LoopedConfig):
    """``last [T,b,D]`` and ``gates [T,b]`` of one position -> ``(logits
    [b,V] f32 over the state of the step each row left at, exit pdf [b,T],
    the steps each row took [b])``."""
    pdf = exit_pdf(gates)
    at = exit_step(pdf, cfg.early_exit_threshold)
    x = jnp.take_along_axis(last, at[None, :, None], axis=0)[0]
    with jax.named_scope("head"):
        logits = jnp.dot(x.astype(_compute_dtype(params)), params["head"],
                         preferred_element_type=jnp.float32)
    return logits, pdf.T, at + 1


def generate(params, tokens, cfg: LoopedConfig):
    """``tokens [b,P]`` -> the greedy answer and what the device counted:

    * ``tokens [b,G]`` int32: the first from the prefill's last position,
      the others a decode step each;
    * ``logits [b,2,V]`` f32: the logits that chose the first new token and
      those that chose the last (which have read every cached position of
      every loop step); ``exit_pdf [b,2,T]``: the exit probabilities at
      those two positions;
    * ``counters``: ``loop_steps [b]`` (the loop steps the row's tokens took
      before they left, summed: ``T`` a token at threshold 1) and
      ``loop_tokens [b]`` (tokens through the stack: ``P + G - 1``)."""
    b, P = tokens.shape
    G, threshold = cfg.new_tokens, cfg.early_exit_threshold
    cache, last, gates = prefill(params, tokens, cfg)
    logits, pdf, _ = readout(params, last, gates[..., -1], cfg)
    steps = jnp.sum(exit_step(exit_pdf(gates), threshold) + 1, axis=-1)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(i, state):
        cache, out, token, _, _, steps = state
        cache, last, gates = decode_step(params, cache, token, P + i - 1,
                                         cfg)
        logits, pdf, took = readout(params, last, gates, cfg)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = lax.dynamic_update_slice_in_dim(out, token[:, None], i, 1)
        return cache, out, token, logits, pdf, steps + took

    out = jnp.zeros((b, G), jnp.int32).at[:, 0].set(token)
    with jax.named_scope("decode"):
        _, out, _, last_logits, last_pdf, steps = lax.fori_loop(
            1, G, one, (cache, out, token, logits, pdf, steps))
    return {"tokens": out,
            "logits": jnp.stack([logits, last_logits], axis=1),
            "exit_pdf": jnp.stack([pdf, last_pdf], axis=1),
            "counters": {"loop_steps": steps,
                         "loop_tokens": jnp.full((b,), P + G - 1,
                                                 jnp.int32)}}


# ---------------------------------------------------------------------------
# What a request needs
# ---------------------------------------------------------------------------

def flops_per_inference(cfg: LoopedConfig) -> float:
    """FLOPs one request needs: every token of prompt and answer but the
    last through the layers' matrices ``total_ut_steps`` times, the causal
    half of the prefill's scores and a decode step's scores against the
    keys so far a loop step, the head a generated token.  No padding,
    norms, rotary or gate."""
    D, H, dh, L, T = (cfg.hidden_size, cfg.num_attention_heads,
                      cfg.head_dim, cfg.num_hidden_layers,
                      cfg.total_ut_steps)
    P, G = cfg.seq_len, cfg.new_tokens
    matrices = L * (4 * D * H * dh + 3 * D * cfg.intermediate_size)
    pairs = P * (P + 1) // 2 + sum(P + i for i in range(1, G))
    return (T * 2.0 * matrices * (P + G - 1)
            + T * L * H * 2 * 2.0 * dh * pairs
            + G * 2.0 * D * cfg.vocab_size)
