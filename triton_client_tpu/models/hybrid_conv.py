"""Gated short convolutions beside grouped-query attention: LFM2's block.

The fifth block of the zoo.  What it has that the other four have not:

* **Layers of two kinds in one stack.**  ``layer_types`` names each layer's
  operator: ``conv`` (a gated short convolution) or ``full_attention``
  (grouped-query heads with a norm over each head's q and k, as
  ``block_diffusion``'s).  The leading ``num_dense_layers`` layers carry a
  dense SwiGLU and are run one by one; the expert layers after them are
  scanned **a period of the pattern at a time** (``attn conv conv conv`` in
  the published model's first 14 layers), each position of the period with
  leaves of its own kind stacked over the periods, so that one ``lax.scan``
  runs layers whose leaves differ in shape.  A pattern that does not repeat
  is one period: the scan then has one step.
* **Two kinds of state in one generation program.**  An attention layer
  appends a key and a value a position (``[periods, b, Hkv, P + G, dh]``
  twice, written at the position and read up to it).  A conv layer keeps the
  last ``conv_L_cache - 1`` rows of its gated input ``z = B * u``
  (``[periods, b, conv_L_cache - 1, D]``), **overwritten in place every
  token**, whatever the length.  Both are carries of the scan over periods
  and of the decode loop, written where they lie.  The prefill hands both to
  the decode steps: the keys and values of the whole prompt, and the last
  rows of ``z`` of the prompt for each conv layer (zeros before position 0).
* **A router that selects by score + bias and weights by the score alone**:
  ``latent_moe.route`` itself (sigmoid scores in f32, the top k of ``s + b``,
  ``s_chosen / (sum + router_eps)``), over experts that are all held:
  ``latent_moe.held_experts``' all-held form, the experts of every layer one
  stack that the grouped matmul reads in place, as ``block_diffusion`` holds
  them.  No shared expert.
* **A head tied to the embedding.**

The residual stream, every norm, the router's scores, the softmax, the
convolution's taps and the logits are float32; the matrices see bfloat16.
Generation is greedy and the output at position ``i`` predicts the token at
``i + 1``; the whole generation of a batch is one program (``generate``),
which also returns what the device counted on the way.
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

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class HybridConvConfig:
    """The source's keys under the source's names, then what the
    configuration file lists under ``assumed`` (``head_dim``,
    ``router_eps``) and the served shape."""

    hidden_size: int
    intermediate_size: int       # the leading layers' dense SwiGLU
    moe_intermediate_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: Tuple[str, ...]
    conv_L_cache: int
    conv_bias: bool
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    use_expert_bias: bool
    norm_topk_prob: bool
    routed_scaling_factor: float
    router_eps: float            # added to the chosen scores' sum
    vocab_size: int
    norm_eps: float
    rope_theta: float
    seq_len: int        # the prompt
    new_tokens: int     # generated: one by the prefill, the rest decoded
    weights_seed: int

    def __post_init__(self):
        kinds = set(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers \
                or not kinds <= {CONV, ATTENTION}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(kinds)}: {self.num_hidden_layers} of 'conv' or "
                "'full_attention'")
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers}: at least one "
                f"expert layer of {self.num_hidden_layers} follows them")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key/value heads")
        if self.conv_bias or not self.norm_topk_prob:
            raise ValueError(
                "the block has no convolution bias and always renormalises "
                "the chosen experts' weights (conv_bias false, "
                "norm_topk_prob true)")
        if self.conv_L_cache < 2 or self.new_tokens < 1:
            raise ValueError("conv_L_cache is at least 2, new_tokens 1")

    @classmethod
    def from_file(cls, cfg: dict) -> "HybridConvConfig":
        """A configuration file of ``chipbench/configs`` (the source's keys
        at the top, with ``layer_types`` whole: the first
        ``num_hidden_layers`` entries are the layers held; ``assumed`` and
        ``served`` below)."""
        names = {f.name for f in dataclasses.fields(cls)}
        top = {k: v for k, v in cfg.items() if k in names}
        top["layer_types"] = tuple(
            cfg["layer_types"][:cfg["num_hidden_layers"]])
        return cls(**top, head_dim=cfg["assumed"]["head_dim"],
                   router_eps=cfg["assumed"]["router_eps"],
                   seq_len=cfg["served"]["seq_len"],
                   new_tokens=cfg["served"]["new_tokens"],
                   weights_seed=cfg["served"]["weights_seed"])

    @property
    def period(self) -> int:
        """Layers in the shortest pattern the expert layers repeat."""
        kinds = self.layer_types[self.num_dense_layers:]
        return next(p for p in range(1, len(kinds) + 1)
                    if len(kinds) % p == 0
                    and all(kind == kinds[i % p]
                            for i, kind in enumerate(kinds)))

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
    def router_bias(self) -> bool:
        return self.use_expert_bias

    first_expert = 0
    scoring_func = "sigmoid"

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


_PERIOD = (ATTENTION, CONV, CONV, CONV)

#: LFM2-8B-A1B's ``config.json`` cut to stage 0 of a 2-stage pipeline:
#: layers 0-13 of its 24 (the two dense layers and three whole periods of
#: expert layers, every expert of each), the embedding with its tied head
#: and the closing norm; every width as published
#: (``chipbench/configs/lfm2_8b_a1b.json`` states the cut and what is
#: assumed of the wiring).
LFM2_8B_A1B_STAGE = HybridConvConfig(
    hidden_size=2048, intermediate_size=7168, moe_intermediate_size=1792,
    num_hidden_layers=14, num_dense_layers=2,
    layer_types=(CONV, CONV) + _PERIOD * 3, conv_L_cache=3, conv_bias=False,
    num_attention_heads=32, num_key_value_heads=8, head_dim=64,
    num_experts=32, num_experts_per_tok=4, use_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1, router_eps=1e-6,
    vocab_size=65536, norm_eps=1e-5, rope_theta=1000000, seq_len=512,
    new_tokens=32, weights_seed=40)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: HybridConvConfig, layer: int):
    """``{leaf: (shape, scale of the normal draw)}`` of one layer: its
    operator's by kind, then its FFN's; an expert's leaves are per expert.
    The taps lie ``[conv_L_cache, D]``: tap ``j`` of every channel a row."""
    D, H, Hkv, dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    if cfg.layer_types[layer] == CONV:
        shapes = {"w_in": ((D, 3 * D), fan(D)),
                  "conv_w": ((cfg.conv_L_cache, D), fan(cfg.conv_L_cache)),
                  "w_out": ((D, D), fan(D))}
    else:
        shapes = {"w_q": ((D, H, dh), fan(D)), "w_k": ((D, Hkv, dh), fan(D)),
                  "w_v": ((D, Hkv, dh), fan(D)),
                  "w_o": ((H, dh, D), fan(H * dh))}
    if layer < cfg.num_dense_layers:
        F = cfg.intermediate_size
        shapes.update({"w_gate": ((D, F), fan(D)), "w_up": ((D, F), fan(D)),
                       "w_down": ((F, D), fan(F))})
    else:
        F = cfg.moe_intermediate_size
        shapes.update({"router": ((D, cfg.num_experts), 0.02),
                       "we_gate": ((D, F), fan(D)), "we_up": ((D, F), fan(D)),
                       "we_down": ((F, D), fan(F))})
        if cfg.use_expert_bias:
            # normal x 0.01, so that the bias moves some choices
            shapes["router_bias"] = ((cfg.num_experts,), 0.01)
    return shapes


def _layer_params(cfg: HybridConvConfig, layer: int):
    """One layer's leaves in bfloat16 (the selection bias upcast to f32), an
    expert under its id; every norm is ones."""
    D, dh = cfg.hidden_size, cfg.head_dim
    norms = {"ln_op": D, "ln_ffn": D}
    if cfg.layer_types[layer] == ATTENTION:
        norms.update(ln_qh=dh, ln_kh=dh)
    return parts.draw_layer(cfg.weights_seed, layer, _leaf_shapes(cfg, layer),
                            norms, jnp.arange(cfg.num_experts),
                            ("router_bias",))


def init_params(cfg: HybridConvConfig, quantized: bool = False
                ) -> Dict[str, Any]:
    """``{"embed" (the head too), "final_ln", "dense": [layer...],
    "periods": one dict a position of the period, its leaves stacked over
    the periods, "experts": every expert layer's experts as one stack
    [expert layers * experts, ...]}``.  Quantised (the int8 control), the
    experts' leaves stay with their layers, where the scan hands them to
    ``parts.w`` a layer at a time, and ``experts`` is empty.  A layer is
    drawn, written into the stacks in place and let go before the next one
    exists."""
    prep = jax.jit(parts.quantize_weights) if quantized else (lambda x: x)
    first, p, n = cfg.num_dense_layers, cfg.period, cfg.n_expert_layers
    dense = [prep(_layer_params(cfg, i)) for i in range(first)]
    periods = [{} for _ in range(p)]
    experts = {}
    for i in range(n):
        layer = prep(_layer_params(cfg, first + i))
        if not quantized:
            parts.stack(experts, {name: layer.pop(name)
                                  for name in parts.EXPERT_LEAVES}, i, n,
                        flat=True)
        parts.stack(periods[i % p], layer, i // p, n // p)
    return dict(parts.outer_params(cfg, tied=True), dense=dense,
                periods=tuple(periods), experts=experts)


# ---------------------------------------------------------------------------
# The two operators and the two FFNs
# ---------------------------------------------------------------------------

@jax.named_scope("short_conv")
def _short_conv(blk, x, before, cfg: HybridConvConfig):
    """``x [b,S,D]`` f32 through the gated short convolution; ``before
    [b, conv_L_cache - 1, D]`` holds the rows of ``z`` that precede ``x``'s
    first position (zeros at position 0) -> ``(x + op, the last
    conv_L_cache - 1 rows of z: what the next position's step reads)``."""
    S = x.shape[1]
    w_in = parts.w(blk, "w_in")
    n = parts.rmsnorm(x, blk["ln_op"], cfg.norm_eps).astype(w_in.dtype)
    with jax.named_scope("in_proj"):
        gate_in, gate_out, u = jnp.split(jnp.dot(n, w_in), 3, axis=-1)
        z = gate_in * u
    with jax.named_scope("conv"):
        window = jnp.concatenate([before.astype(z.dtype), z], axis=1)
        taps = blk["conv_w"].astype(jnp.float32)
        c = sum(taps[j] * window[:, j:j + S].astype(jnp.float32)
                for j in range(cfg.conv_L_cache))
        gated = (gate_out.astype(jnp.float32) * c).astype(z.dtype)
    with jax.named_scope("out_proj"):
        out = jnp.dot(gated, parts.w(blk, "w_out"),
                      preferred_element_type=jnp.float32)
    with jax.named_scope("conv_state"):
        return x + out, window[:, S:]


def _qkv(blk, x, cfg: HybridConvConfig, cos, sin):
    """``x [b,S,D]`` f32 -> q ``[b,H,S,dh]``, k and v ``[b,Hkv,S,dh]`` in
    the matrices' dtype: q and k normed over the head and rotated, as the
    cache holds them."""
    w_q = parts.w(blk, "w_q")
    n = parts.rmsnorm(x, blk["ln_op"], cfg.norm_eps).astype(w_q.dtype)
    q = jnp.einsum("bsd,dhk->bhsk", n, w_q)
    k = jnp.einsum("bsd,dhk->bhsk", n, parts.w(blk, "w_k"))
    v = jnp.einsum("bsd,dhk->bhsk", n, parts.w(blk, "w_v"))
    with jax.named_scope("qk_norm"):
        q = parts.rmsnorm(q, blk["ln_qh"], cfg.norm_eps)
        k = parts.rmsnorm(k, blk["ln_kh"], cfg.norm_eps)
    with jax.named_scope("rope"):
        return parts.rotate(q, cos, sin), parts.rotate(k, cos, sin), v


def _attention_out(blk, x, o):
    return x + jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"),
                          preferred_element_type=jnp.float32)


@jax.named_scope("dense_ffn")
def _dense_ffn(blk, x, cfg: HybridConvConfig):
    w_gate = parts.w(blk, "w_gate")
    n = parts.rmsnorm(x, blk["ln_ffn"], cfg.norm_eps).astype(w_gate.dtype)
    return x + parts.swiglu(n, w_gate, parts.w(blk, "w_up"),
                            parts.w(blk, "w_down"))


@jax.named_scope("moe")
def _moe(blk, x, cfg: HybridConvConfig):
    """``x [b,S,D]`` f32 -> ``(x + the experts' part, rows routed to each
    expert by batch row [b,E], the experts each position chose
    [b,S,k])``."""
    b, S, D = x.shape
    h = parts.rmsnorm(x, blk["ln_ffn"], cfg.norm_eps).astype(
        blk["router"].dtype).reshape(b * S, D)
    with jax.named_scope("router"):
        idx, weights = lm.route(blk, h, cfg)
    y, rows = lm.held_experts(blk, h, idx, weights, cfg, batch=b)
    with jax.named_scope("combine"):
        return x + y.reshape(b, S, D), rows, idx.reshape(b, S, -1)


def _layers(params, cfg: HybridConvConfig, x, state, op):
    """``x [b,S,D]`` through every layer.  ``op(kind, blk, x, held, n) ->
    (x, held)`` is a layer's operator: ``held`` is the state of the layers
    at this position of the period, stacked over the periods (a dense
    layer's has one entry), and ``n`` the entry that is this layer's.
    Returns ``(x, the state, rows routed to each expert [b, expert layers,
    E], the experts each position chose [b, S, expert layers, k])``; the
    state is ``{"dense": [a layer's...], "periods": (a position's...)}``."""
    first, p, E = cfg.num_dense_layers, cfg.period, cfg.num_experts
    kinds = cfg.layer_types
    dense = []
    for i, blk in enumerate(params["dense"]):
        x, held = op(kinds[i], blk, x, state["dense"][i], 0)
        dense.append(held)
        x = _dense_ffn(blk, x, cfg)

    def period(carry, scanned):
        x, state = carry
        n, leaves = scanned
        state, rows, chose = list(state), [], []
        for j in range(p):
            blk = dict(leaves[j], **params["experts"])
            if params["experts"]:
                blk["first_group"] = (n * p + j) * E
            x, state[j] = op(kinds[first + j], blk, x, state[j], n)
            x, routed, chosen = _moe(blk, x, cfg)
            rows.append(routed)
            chose.append(chosen)
        # [b,p,E] and [b,S,p,k]
        return (x, tuple(state)), (jnp.stack(rows, axis=1),
                                   jnp.stack(chose, axis=2))

    n_periods = cfg.n_expert_layers // p
    (x, periods), (rows, chose) = lax.scan(
        period, (x, state["periods"]),
        (jnp.arange(n_periods), params["periods"]))
    b, S = x.shape[:2]
    rows = rows.transpose(1, 0, 2, 3).reshape(b, cfg.n_expert_layers, E)
    chose = chose.transpose(1, 2, 0, 3, 4).reshape(
        b, S, cfg.n_expert_layers, -1)
    return x, {"dense": dense, "periods": periods}, rows, chose


def _head(params, x, cfg: HybridConvConfig):
    """The closing norm and the head, which is the embedding: ``x [b,D]``
    -> logits ``[b,V]`` f32."""
    with jax.named_scope("head"):
        embed = params["embed"]
        h = parts.rmsnorm(x, params["final_ln"], cfg.norm_eps).astype(
            embed.dtype)
        return jnp.einsum("bd,vd->bv", h, embed,
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Prefill, a decode step, the loop
# ---------------------------------------------------------------------------

def _empty_state(params, cfg: HybridConvConfig, b: int, positions: int):
    """Room for both kinds of state, zeros: an attention layer's ``(k, v)``
    ``[n, b, Hkv, positions, dh]`` each, a conv layer's ``[n, b,
    conv_L_cache - 1, D]``, ``n`` the periods (1 for a dense layer)."""
    dtype = params["embed"].dtype

    def room(kind, n):
        if kind == CONV:
            return jnp.zeros((n, b, cfg.conv_L_cache - 1, cfg.hidden_size),
                             dtype)
        return (jnp.zeros((n, b, cfg.num_key_value_heads, positions,
                           cfg.head_dim), dtype),) * 2

    first, p = cfg.num_dense_layers, cfg.period
    return {"dense": [room(cfg.layer_types[i], 1) for i in range(first)],
            "periods": tuple(room(cfg.layer_types[first + j],
                                  cfg.n_expert_layers // p)
                             for j in range(p))}


@jax.named_scope("prefill")
def prefill(params, tokens, cfg: HybridConvConfig):
    """``tokens [b,P]`` -> ``(logits [b,V] f32 of the last position, the
    state, rows routed to each expert [b, expert layers, E], the experts
    each position chose [b, P, expert layers, k])``.  The state
    has room for ``P + new_tokens`` positions of keys and values with the
    prompt's written, and each conv layer's last ``conv_L_cache - 1`` rows
    of ``z`` (zeros where the prompt is shorter)."""
    from ..ops import flash_attention

    b, P = tokens.shape
    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta, jnp.arange(P))
    nothing = jnp.zeros((b, cfg.conv_L_cache - 1, cfg.hidden_size),
                        params["embed"].dtype)

    def op(kind, blk, x, held, n):
        if kind == CONV:
            x, rows = _short_conv(blk, x, nothing, cfg)
            return x, lax.dynamic_update_index_in_dim(held, rows, n, 0)
        with jax.named_scope("attention"):
            q, k, v = _qkv(blk, x, cfg, cos, sin)
            held = tuple(
                lax.dynamic_update_slice(c, new[None], (n, 0, 0, 0, 0))
                for c, new in zip(held, (k, v)))
            o = flash_attention(q, k, v, causal=True)
            return _attention_out(blk, x, o), held

    x = parts.embed(params, tokens, cfg).astype(jnp.float32)
    x, state, rows, chose = _layers(
        params, cfg, x, _empty_state(params, cfg, b, P + cfg.new_tokens), op)
    return _head(params, x[:, -1], cfg), state, rows, chose


@jax.named_scope("token")
def decode_step(params, state, token, pos, cfg: HybridConvConfig):
    """``token [b]`` at position ``pos`` through every layer, each writing
    its state where it lies: an attention layer its key and value at
    ``pos`` (then attending up to there), a conv layer the rows of ``z``
    that the next position reads, over the oldest -> ``(logits [b,V] f32,
    the state, rows routed [b, expert layers, E], the experts chosen [b,
    expert layers, k])``."""
    cos, sin = parts.rotary(cfg.head_dim, cfg.rope_theta,
                            jnp.reshape(pos, (1,)))
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def op(kind, blk, x, held, n):
        if kind == CONV:
            before = lax.dynamic_index_in_dim(held, n, 0, keepdims=False)
            x, rows = _short_conv(blk, x, before, cfg)
            return x, lax.dynamic_update_index_in_dim(held, rows, n, 0)
        with jax.named_scope("attention"):
            q, k, v = _qkv(blk, x, cfg, cos, sin)
            held = tuple(
                lax.dynamic_update_slice(c, new[None], (n, 0, 0, pos, 0))
                for c, new in zip(held, (k, v)))
            with jax.named_scope("cache_attend"):
                keys, values = (lax.dynamic_index_in_dim(c, n, 0, False)
                                for c in held)
                seen = jnp.arange(keys.shape[2]) <= pos
                qg = q.reshape(q.shape[0], -1, group, 1, cfg.head_dim)
                s = jnp.einsum("bgrqk,bgtk->bgrqt", qg, keys,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
                o = jnp.einsum("bgrqt,bgtk->bgrqk", p.astype(q.dtype),
                               values).reshape(q.shape)
            return _attention_out(blk, x, o), held

    x = parts.embed(params, token[:, None], cfg).astype(jnp.float32)
    x, state, rows, chose = _layers(params, cfg, x, state, op)
    return _head(params, x[:, 0], cfg), state, rows, chose[:, 0]


def generate(params, tokens, cfg: HybridConvConfig):
    """``tokens [b,P]`` -> the greedy answer and what the device counted:

    * ``tokens [b,G]`` int32: the first from the prefill's last position,
      the others a decode step each;
    * ``logits [b,3,V]`` f32: the logits that chose the first new token
      (the prefill's last position), the second (the first decode step,
      which reads what the prefill handed over: the prompt's keys and each
      conv layer's last rows, and nothing else) and the last (which has
      read every cached key); with fewer than three new tokens the later
      rows repeat the last; ``routes [b,P + G - 1,expert layers,k]`` int32:
      the experts every position of prompt and answer but the last chose
      in each layer (a reference that recomputes the rows has to be told:
      where two experts lie closer than bfloat16's rounding the choice is
      not the reference's, and what a position chose reaches the next two
      through every conv layer's state);
    * ``counters``: ``expert_rows [b, expert layers, E]`` (pairs on each
      expert, the prefill's and every decode step's), ``experts_touched
      [b]`` (over decode steps and layers, the experts with a row; entry
      ``r`` counts the rows ``0 .. r``), ``decode_steps [b]`` (decode steps
      run for the row) and ``decode_tokens [b]`` (tokens they yielded)."""
    b, P = tokens.shape
    G = cfg.new_tokens
    logits, state, rows, chose = prefill(params, tokens, cfg)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    none = jnp.zeros((b,), jnp.int32)
    routes = jnp.pad(chose, [(0, 0), (0, G - 1), (0, 0), (0, 0)])

    def one(i, carry):
        state, out, routes, token, second, _, rows, touched, steps = carry
        logits, state, routed, chose = decode_step(params, state, token,
                                                   P + i - 1, cfg)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = lax.dynamic_update_slice_in_dim(out, token[:, None], i, 1)
        routes = lax.dynamic_update_slice_in_dim(routes, chose[:, None],
                                                 P + i - 1, 1)
        return (state, out, routes, token,
                jnp.where(i == 1, logits, second), logits, rows + routed,
                touched + parts.touched(routed), steps + 1)

    out = jnp.zeros((b, G), jnp.int32).at[:, 0].set(token)
    with jax.named_scope("decode"):
        _, out, routes, _, second, last, rows, touched, steps = lax.fori_loop(
            1, G, one,
            (state, out, routes, token, logits, logits, rows, none, none))
    return {"tokens": out, "routes": routes,
            "logits": jnp.stack([logits, second, last], axis=1),
            "counters": {"expert_rows": rows, "experts_touched": touched,
                         "decode_steps": steps,
                         "decode_tokens": jnp.full((b,), G - 1, jnp.int32)}}


# ---------------------------------------------------------------------------
# What a request needs
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg: HybridConvConfig) -> Dict[str, int]:
    """Matrix elements a token passes through, by part of a layer (a conv
    operator's taps among them)."""
    D, H, Hkv, dh = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    return {
        CONV: 4 * D * D + cfg.conv_L_cache * D,
        ATTENTION: D * (H + 2 * Hkv) * dh + H * dh * D,
        "dense_ffn": 3 * D * cfg.intermediate_size,
        "moe": D * cfg.num_experts
        + cfg.num_experts_per_tok * 3 * D * cfg.moe_intermediate_size,
    }


def flops_per_inference(cfg: HybridConvConfig) -> float:
    """FLOPs one request needs: every token of prompt and answer but the
    last through every matrix it passes (its 4 experts among them), the
    causal half of the prefill's scores and a decode step's against the
    keys so far in the attention layers, the head a generated token.  No
    padding, norms or rotary."""
    part = layer_matmul_params(cfg)
    P, G = cfg.seq_len, cfg.new_tokens
    per_token = sum(part[kind] for kind in cfg.layer_types) \
        + cfg.num_dense_layers * part["dense_ffn"] \
        + cfg.n_expert_layers * part["moe"]
    pairs = P * (P + 1) // 2 + sum(P + i for i in range(1, G))
    return (2.0 * per_token * (P + G - 1)
            + cfg.layer_types.count(ATTENTION) * cfg.num_attention_heads
            * 2 * 2.0 * cfg.head_dim * pairs
            + G * 2.0 * cfg.hidden_size * cfg.vocab_size)
