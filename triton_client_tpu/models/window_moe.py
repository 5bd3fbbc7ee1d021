"""Sliding-window and full attention in one stack over a shared expert and
128 sigmoid-routed ones: the AFMoE block (Arcee's Trinity).

The seventh block of the zoo.  What it has that the other six have not:

* **Layers whose attention differs by kind.**  ``layer_types`` names each
  layer's: ``sliding_attention`` (query ``t`` sees keys ``t - window < s <=
  t``, rotated) or ``full_attention`` (every earlier key, **no positional
  encoding**).  Both kinds have the same leaves, so every layer's attention
  is one stack and one ``lax.scan`` runs the whole stack, dense layers and
  expert layers alike: the layer's kinds ride the scan as flags and
  ``lax.cond`` takes the branch.  A sliding layer runs
  ``ops/flash_attention``'s windowed form, which reads and walks the
  window's keys alone; a full layer the looped form.  Each kernel is then
  one op of the program whatever the number of layers that call it, and one
  layer's temporaries are live at a time (3.7 GB at 32,768 positions; 6.5
  GB with the four expert layers unrolled as a period).
* **A gated output.**  ``o`` is multiplied elementwise by ``sigmoid(h
  W_g)``, one gate a head and value channel, before ``W_o``.
* **Sandwich norms**: a norm before and after each branch (``looped``'s
  four), a norm over each head's q and k, and the embedding scaled by
  ``sqrt(hidden_size)`` (``mup_enabled``).
* **An expert layer that holds every expert and a shared one beside
  them**: ``latent_moe.route`` (sigmoid scores in f32, the top k of score +
  bias, the chosen scores over their sum + ``router_eps``, times
  ``route_scale``) over ``latent_moe.held_experts``' all-held form, the
  experts of every layer one stack the grouped matmul reads in place.

The residual stream, every norm, the gate, the router's scores, the softmax
and the logits are float32; the matrices see bfloat16.  One request is one
prompt; the step answers it with the greedy next token, that token's row of
logits and every token's experts, and returns what the device counted.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import latent_moe as lm
from . import parts

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    """The source's keys under the source's names, then what the
    configuration file lists under ``assumed`` (``router_eps``) and the
    served shape."""

    hidden_size: int
    intermediate_size: int       # the leading layers' dense SwiGLU
    moe_intermediate_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    score_func: str
    route_norm: bool
    route_scale: float
    mup_enabled: bool
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    router_eps: float            # added to the chosen scores' sum
    seq_len: int                 # the prompt
    weights_seed: int

    def __post_init__(self):
        kinds = set(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers \
                or not kinds <= {SLIDING, FULL}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(kinds)}: {self.num_hidden_layers} of "
                f"{SLIDING!r} or {FULL!r}")
        if not 0 < self.num_dense_layers < self.num_hidden_layers:
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers}: at least one "
                f"leads, and one expert layer of {self.num_hidden_layers} "
                "follows them")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key/value heads")
        if self.score_func != "sigmoid" or not self.route_norm:
            raise ValueError(
                "the block scores by sigmoid and renormalises the chosen "
                "experts' weights (score_func 'sigmoid', route_norm true)")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window}")

    @classmethod
    def from_file(cls, cfg: dict) -> "WindowMoEConfig":
        """A configuration file of ``chipbench/configs`` (the source's keys
        at the top, with ``layer_types`` whole: the first
        ``num_hidden_layers`` entries are the layers held; ``assumed`` and
        ``served`` below)."""
        names = {f.name for f in dataclasses.fields(cls)}
        top = {k: v for k, v in cfg.items() if k in names}
        top["layer_types"] = tuple(
            cfg["layer_types"][:cfg["num_hidden_layers"]])
        return cls(**top, router_eps=cfg["assumed"]["router_eps"],
                   seq_len=cfg["served"]["seq_len"],
                   weights_seed=cfg["served"]["weights_seed"])

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    # what ``latent_moe.route`` and ``held_experts`` ask of a configuration
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def routed_experts_total(self) -> int:
        return self.num_experts

    @property
    def scoring_func(self) -> str:
        return self.score_func

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    router_bias = True
    first_expert = 0

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


_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)

#: Trinity-Mini's ``config.json`` cut to stage 0 of a pipeline over its 32
#: layers: layers 0-5 (the two dense layers and the four expert layers after
#: them, one whole period of the pattern, every expert of each), the
#: embedding, the closing norm and the untied head; every width as published
#: (``chipbench/configs/trinity_mini.json`` states the cut and what is
#: assumed of the wiring).
TRINITY_MINI_STAGE = WindowMoEConfig(
    hidden_size=2048, intermediate_size=6144, moe_intermediate_size=1024,
    num_hidden_layers=6, num_dense_layers=2,
    layer_types=(_PERIOD + _PERIOD)[:6], sliding_window=2048,
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    num_experts=128, num_experts_per_tok=8, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.826,
    mup_enabled=True, vocab_size=200192, rms_norm_eps=1e-5,
    rope_theta=10000, router_eps=1e-20, seq_len=32768, weights_seed=47)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

#: a layer's norms: before and after each branch, and over each head's q, k
_NORMS = ("ln_attn", "ln_attn_out", "ln_ffn", "ln_ffn_out")


def _leaf_shapes(cfg: WindowMoEConfig, layer: int):
    """``{leaf: (shape, scale of the normal draw)}`` of one layer: the
    attention's (both kinds alike), then its FFN's; an expert's leaves are
    per expert."""
    D, H, Hkv, dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    shapes = {"w_q": ((D, H, dh), fan(D)), "w_k": ((D, Hkv, dh), fan(D)),
              "w_v": ((D, Hkv, dh), fan(D)), "w_g": ((D, H, dh), fan(D)),
              "w_o": ((H, dh, D), fan(H * dh))}
    if layer < cfg.num_dense_layers:
        F = cfg.intermediate_size
        shapes.update({"w_gate": ((D, F), fan(D)), "w_up": ((D, F), fan(D)),
                       "w_down": ((F, D), fan(F))})
    else:
        F, Fs = (cfg.moe_intermediate_size,
                 cfg.moe_intermediate_size * cfg.num_shared_experts)
        shapes.update({"router": ((D, cfg.num_experts), 0.02),
                       # normal x 0.01, so that the bias moves some choices
                       "router_bias": ((cfg.num_experts,), 0.01),
                       "we_gate": ((D, F), fan(D)), "we_up": ((D, F), fan(D)),
                       "we_down": ((F, D), fan(F)),
                       "ws_gate": ((D, Fs), fan(D)),
                       "ws_up": ((D, Fs), fan(D)),
                       "ws_down": ((Fs, D), fan(Fs))})
    return shapes


def _layer_params(cfg: WindowMoEConfig, layer: int):
    """One layer's leaves in bfloat16 (the selection bias upcast to f32), an
    expert under its id; every norm is ones."""
    norms = dict({name: cfg.hidden_size for name in _NORMS},
                 ln_qh=cfg.head_dim, ln_kh=cfg.head_dim)
    return parts.draw_layer(cfg.weights_seed, layer, _leaf_shapes(cfg, layer),
                            norms, jnp.arange(cfg.num_experts),
                            ("router_bias",))


#: the leaves of a dense layer's FFN and of an expert layer's router and
#: shared expert (with their int8 scales, quantised); every other leaf of a
#: layer (its attention and its norms) is alike in both kinds
_DENSE = ("w_gate", "w_up", "w_down")
_MOE = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")


def _stack_of(name: str, quantized: bool) -> str:
    base = name[:-len("_scale")] if name.endswith("_scale") else name
    if base in parts.EXPERT_LEAVES:
        return "moe" if quantized else "experts"
    return "dense" if base in _DENSE else "moe" if base in _MOE else "layers"


def init_params(cfg: WindowMoEConfig, quantized: bool = False
                ) -> Dict[str, Any]:
    """``{"embed", "final_ln", "head", "layers": every layer's attention and
    norms stacked [layers, ...], "dense": the dense FFNs stacked [dense
    layers, ...], "moe": the routers and shared experts stacked [expert
    layers, ...], "experts": every expert layer's experts as one stack
    [expert layers * experts, ...]}``.  Quantised (the int8 control), the
    experts' leaves are stacked with ``moe`` [expert layers, experts, ...]
    and ``experts`` is empty.  A layer is drawn, written into the stacks in
    place and let go before the next one exists."""
    prep = jax.jit(parts.quantize_weights) if quantized else (lambda x: x)
    first, n = cfg.num_dense_layers, cfg.n_expert_layers
    out = {"layers": {}, "dense": {}, "moe": {}, "experts": {}}
    for i in range(cfg.num_hidden_layers):
        leaves = {part: {} for part in out}
        for name, leaf in prep(_layer_params(cfg, i)).items():
            leaves[_stack_of(name, quantized)][name] = leaf
        parts.stack(out["layers"], leaves["layers"], i, cfg.num_hidden_layers)
        if i < first:
            parts.stack(out["dense"], leaves["dense"], i, first)
            continue
        parts.stack(out["moe"], leaves["moe"], i - first, n)
        parts.stack(out["experts"], leaves["experts"], i - first, n,
                    flat=True)
    return dict(parts.outer_params(cfg), **out)


# ---------------------------------------------------------------------------
# The two kinds of attention and the two FFNs
# ---------------------------------------------------------------------------

def _attention(blk, x, cfg: WindowMoEConfig, kind: str, cos, sin):
    """``x [b,S,D]`` f32 -> ``x`` + the norm of the gated attention: a
    sliding layer rotates q and k and attends over its window, a full layer
    attends over every earlier key without positions."""
    from ..ops import flash_attention

    sliding = kind == SLIDING
    with jax.named_scope("attn.window" if sliding else "attn.full"):
        w_q = parts.w(blk, "w_q")
        n = parts.rmsnorm(x, blk["ln_attn"], cfg.rms_norm_eps).astype(
            w_q.dtype)
        with jax.named_scope("qkv_proj"):
            q = jnp.einsum("bsd,dhk->bhsk", n, w_q)
            k = jnp.einsum("bsd,dhk->bhsk", n, parts.w(blk, "w_k"))
            v = jnp.einsum("bsd,dhk->bhsk", n, parts.w(blk, "w_v"))
        with jax.named_scope("qk_norm"):
            q = parts.rmsnorm(q, blk["ln_qh"], cfg.rms_norm_eps)
            k = parts.rmsnorm(k, blk["ln_kh"], cfg.rms_norm_eps)
        if sliding:
            with jax.named_scope("rope"):
                q, k = parts.rotate(q, cos, sin), parts.rotate(k, cos, sin)
        o = flash_attention(q, k, v, causal=True,
                            window=cfg.sliding_window if sliding else None)
        with jax.named_scope("gate"):
            g = jnp.einsum("bsd,dhk->bhsk", n, parts.w(blk, "w_g"),
                           preferred_element_type=jnp.float32)
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(o.dtype)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"),
                             preferred_element_type=jnp.float32)
        with jax.named_scope("sandwich_norm"):
            return x + parts.rmsnorm(out, blk["ln_attn_out"],
                                     cfg.rms_norm_eps)


@jax.named_scope("dense_ffn")
def _dense_ffn(blk, x, cfg: WindowMoEConfig):
    w_gate = parts.w(blk, "w_gate")
    n = parts.rmsnorm(x, blk["ln_ffn"], cfg.rms_norm_eps).astype(w_gate.dtype)
    y = parts.swiglu(n, w_gate, parts.w(blk, "w_up"), parts.w(blk, "w_down"))
    with jax.named_scope("sandwich_norm"):
        return x + parts.rmsnorm(y, blk["ln_ffn_out"], cfg.rms_norm_eps)


@jax.named_scope("moe")
def _moe(blk, x, cfg: WindowMoEConfig):
    """``x [b,S,D]`` f32 -> ``(x + the norm of the routed and shared
    experts' sum, rows routed to each expert by batch row [b,E], the experts
    each position chose [b,S,k])``."""
    b, S, D = x.shape
    h = parts.rmsnorm(x, blk["ln_ffn"], cfg.rms_norm_eps).astype(
        blk["router"].dtype).reshape(b * S, D)
    with jax.named_scope("router"):
        idx, weights = lm.route(blk, h, cfg)
    y, rows = lm.held_experts(blk, h, idx, weights, cfg, batch=b)
    with jax.named_scope("shared_expert"):
        y = y + parts.swiglu(h, parts.w(blk, "ws_gate"), parts.w(blk, "ws_up"),
                             parts.w(blk, "ws_down"))
    with jax.named_scope("sandwich_norm"):
        y = parts.rmsnorm(y.reshape(b, S, D), blk["ln_ffn_out"],
                          cfg.rms_norm_eps)
    return x + y, rows, idx.reshape(b, S, -1)


def _head(params, x, cfg: WindowMoEConfig):
    """The closing norm and the untied head: ``x [b,D]`` f32 -> logits
    ``[b,V]`` f32, the head read as it is held (an f32 operand would have
    the compiler convert the whole head first)."""
    with jax.named_scope("head"):
        head = params["head"]
        h = parts.rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
        return jnp.dot(h.astype(head.dtype), head,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The served step
# ---------------------------------------------------------------------------

@jax.named_scope("prefill")
def forward(params, tokens, cfg: WindowMoEConfig):
    """``tokens [b,S]`` -> the answer and what the device counted:

    * ``tokens [b,1]`` int32: the greedy next token;
    * ``logits [b,1,V]`` f32: its row (the last position's);
    * ``routes [b, expert layers, S, k]`` int32: the experts every token
      chose in each expert layer (a reference that recomputes the rows has
      to be told: where the k-th and the next expert lie closer than
      bfloat16's rounding the choice is not the reference's, and what a
      token carries reaches the last row through every later key);
    * ``counters``: ``expert_rows [b, expert layers, E]`` (pairs on each
      expert), ``window_queries [b]`` (query rows x windowed layers) and
      ``window_pairs [b]`` ((query, key) pairs those layers attended).

    One ``lax.scan`` runs every layer: the layer's kind of attention and of
    FFN ride it as flags, and ``lax.cond`` takes the branch (one runs on the
    chip), so that each kernel is one op however many layers call it and
    one layer's temporaries are live at a time."""
    b, S = tokens.shape
    first, E, k = cfg.num_dense_layers, cfg.num_experts, \
        cfg.num_experts_per_tok
    kinds = cfg.layer_types
    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta, jnp.arange(S))
    x = parts.embed(params, tokens, cfg).astype(jnp.float32)
    if cfg.mup_enabled:
        x = x * math.sqrt(cfg.hidden_size)

    def at(stack, n):  # layer n of a stack
        return {name: lax.dynamic_index_in_dim(leaf, n, keepdims=False)
                for name, leaf in stack.items()}

    def dense(i, x, norms):
        return (_dense_ffn(dict(norms, **at(params["dense"], i)), x, cfg),
                jnp.zeros((b, E), jnp.int32), jnp.zeros((b, S, k), jnp.int32))

    def moe(i, x, norms):
        n = jnp.maximum(i - first, 0)
        blk = dict(norms, **at(params["moe"], n))
        if params["experts"]:
            blk.update(params["experts"], first_group=n * E)
        return _moe(blk, x, cfg)

    def layer(x, scanned):
        i, full, blk = scanned
        x = lax.cond(full, lambda x: _attention(blk, x, cfg, FULL, cos, sin),
                     lambda x: _attention(blk, x, cfg, SLIDING, cos, sin), x)
        norms = {name: blk[name] for name in ("ln_ffn", "ln_ffn_out")}
        x, rows, chose = lax.cond(i < first, dense, moe, i, x, norms)
        return x, (rows, chose)

    x, (rows, chose) = lax.scan(
        layer, x, (jnp.arange(cfg.num_hidden_layers),
                   jnp.asarray([kind == FULL for kind in kinds]),
                   params["layers"]))
    rows = rows[first:].transpose(1, 0, 2)
    chose = chose[first:].transpose(1, 0, 2, 3)
    logits = _head(params, x[:, -1], cfg)
    windowed = kinds.count(SLIDING)
    # the keys each row sees under the window's mask, i - W < j <= i
    pairs = jnp.sum(jnp.minimum(jnp.arange(S) + 1, cfg.sliding_window))
    return {"tokens": jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None],
            "logits": logits[:, None], "routes": chose,
            "counters": {
                "expert_rows": rows,
                "window_queries": jnp.full((b,), windowed * S, jnp.int32),
                "window_pairs": jnp.full((b,), windowed, jnp.int32) * pairs}}


# ---------------------------------------------------------------------------
# What a request needs
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg: WindowMoEConfig) -> Dict[str, int]:
    """Matrix elements a token passes through, by part of a layer."""
    D, H, Hkv, dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    expert = 3 * D * cfg.moe_intermediate_size
    return {
        "attention": D * (2 * H + 2 * Hkv) * dh + H * dh * D,
        "dense_ffn": 3 * D * cfg.intermediate_size,
        "moe": D * cfg.num_experts
        + (cfg.num_experts_per_tok + cfg.num_shared_experts) * expert,
    }


def attended_pairs(cfg: WindowMoEConfig, kind: str) -> int:
    """(query, key) pairs a layer of ``kind`` attends for one prompt."""
    S, W = cfg.seq_len, cfg.sliding_window
    if kind == FULL:
        return S * (S + 1) // 2
    m = min(S, W)
    return m * (m + 1) // 2 + (S - m) * W


def flops_per_inference(cfg: WindowMoEConfig) -> float:
    """FLOPs one prompt of ``seq_len`` tokens needs: every matrix a token
    passes through (its 8 experts and the shared one among them), QK^T and
    P·v over the pairs each layer attends (a window's in a sliding layer,
    the causal half in a full one), the last position's head.  No padding,
    norms, gate's sigmoid or rotary."""
    part = layer_matmul_params(cfg)
    per_token = (cfg.num_hidden_layers * part["attention"]
                 + cfg.num_dense_layers * part["dense_ffn"]
                 + cfg.n_expert_layers * part["moe"])
    attention = sum(2.0 * cfg.num_attention_heads * 2 * cfg.head_dim
                    * attended_pairs(cfg, kind) for kind in cfg.layer_types)
    return (2.0 * per_token * cfg.seq_len + attention
            + 2.0 * cfg.hidden_size * cfg.vocab_size)
