"""Plain float32 reference of what the ``kimi_k2`` configuration serves.

The DeepSeek-V3 block at Kimi-K2-Instruct's numbers, as the configuration
file cuts it to one chip: straightforward ``jax.numpy`` at ``highest`` matmul
precision, no kernels, no batching, nothing imported from the program.

* Latent attention: ``h = norm(x)``; ``c_q = norm(h W_qa)``; ``q = c_q W_qb``
  (per head a part without position beside a rotary part); ``h W_kva`` gives
  the kv latent (normed) and one rotary key a token, shared by all heads;
  ``c_kv W_kb`` and ``c_kv W_vb`` give the heads' keys and values; YaRN
  frequencies on the rotary parts (half-split pairs, ``assumed``); scores
  times ``qk_head_dim ** -0.5 * mscale ** 2``, causal softmax, out projection.
* The leading layers' FFN is a SwiGLU of ``intermediate_size``.
* An expert layer: sigmoid scores over **all** published experts, the top k
  of score + bias, weights from the scores alone, renormalised and scaled.
  Every held expert (``deployment.first_expert`` and the next
  ``n_routed_experts - 1``) is computed for every token and masked to the
  tokens that chose it: the dense form.  What the absent experts would add
  is left out.  The shared expert is added for every token.
* The answer is the last position's logits over the held rows of the head.

The weights are the bfloat16 values the configuration describes (each layer
from ``fold_in(PRNGKey(weights_seed), layer)``, a key a leaf, an expert's
draw under its id; f32 normal times the scale, rounded to bfloat16 once;
norms at one, so their scales are left out), used here upcast, in float32.
They are made one layer at a time, an expert at a time, and all the sampled
prompts go through a layer before the next layer's weights exist.
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

_OUTER = 1 << 16
_LEAF_KEYS = {
    "w_qa": 0, "w_qb_nope": 1, "w_qb_rope": 2, "w_kva": 3, "w_kb": 4,
    "w_vb": 5, "w_o": 6, "w_gate": 7, "w_up": 8, "w_down": 9, "router": 10,
    "router_bias": 11, "we_gate": 12, "we_up": 13, "we_down": 14,
    "ws_gate": 15, "ws_up": 16, "ws_down": 17, "embed": 18, "head": 19,
}
QUERY_BLOCK = 256


def _draw(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _layer_key(cfg: dict, layer: int):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg["served"]["weights_seed"]), layer)


def layer_weights(cfg: dict, layer: int) -> dict:
    """Every leaf of one layer but the routed experts, in float32."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    root = _layer_key(cfg, layer)

    def leaf(name, shape, scale):
        return _draw(jax.random.fold_in(root, _LEAF_KEYS[name]), shape, scale)

    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    w = {"w_qa": leaf("w_qa", (D, rq), fan(D)),
         "w_qb_nope": leaf("w_qb_nope", (rq, H, dn), fan(rq)),
         "w_qb_rope": leaf("w_qb_rope", (rq, H, dr), fan(rq)),
         "w_kva": leaf("w_kva", (D, rkv + dr), fan(D)),
         "w_kb": leaf("w_kb", (rkv, H, dn), fan(rkv)),
         "w_vb": leaf("w_vb", (rkv, H, dv), fan(rkv)),
         "w_o": leaf("w_o", (H, dv, D), fan(H * dv))}
    if layer < cfg["first_k_dense_replace"]:
        F = cfg["intermediate_size"]
        w.update({"w_gate": leaf("w_gate", (D, F), fan(D)),
                  "w_up": leaf("w_up", (D, F), fan(D)),
                  "w_down": leaf("w_down", (F, D), fan(F))})
    else:
        total = cfg["deployment"]["published"]["n_routed_experts"]
        Fs = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
        w.update({"router": leaf("router", (D, total), 0.02),
                  "router_bias": leaf("router_bias", (total,), 0.01),
                  "ws_gate": leaf("ws_gate", (D, Fs), fan(D)),
                  "ws_up": leaf("ws_up", (D, Fs), fan(D)),
                  "ws_down": leaf("ws_down", (Fs, D), fan(Fs))})
    return w


def expert_weights(cfg: dict, layer: int, expert: int) -> dict:
    """Routed expert ``expert`` (its id among all published) of ``layer``."""
    D, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    root = _layer_key(cfg, layer)

    def leaf(name, shape, scale):
        key = jax.random.fold_in(
            jax.random.fold_in(root, _LEAF_KEYS[name]), expert)
        return _draw(key, shape, scale)

    return {"gate": leaf("we_gate", (D, Fe), 1.0 / math.sqrt(D)),
            "up": leaf("we_up", (D, Fe), 1.0 / math.sqrt(D)),
            "down": leaf("we_down", (Fe, D), 1.0 / math.sqrt(Fe))}


def outer_weights(cfg: dict) -> dict:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    root = _layer_key(cfg, _OUTER)
    return {"embed": _draw(jax.random.fold_in(root, _LEAF_KEYS["embed"]),
                           (V, D), 0.02),
            "head": _draw(jax.random.fold_in(root, _LEAF_KEYS["head"]),
                          (D, V), 0.02)}


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict):
    """DeepSeek-V3's ``yarn_find_correction_range`` / ``linear_ramp_mask``;
    returns the frequencies and the multiplier on cos and sin."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / rs["factor"]
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    multiplier = (_mscale(rs["factor"], rs["mscale"])
                  / _mscale(rs["factor"], rs["mscale_all_dim"]))
    return inter * (1.0 - mask) + extra * mask, multiplier


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, cfg: dict):
    """``x [S,D]`` of one prompt -> ``x`` plus the layer's attention."""
    eps, rkv = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    S = x.shape[0]
    h = _rmsnorm(x, eps)
    c_q = _rmsnorm(h @ w["w_qa"], eps)
    q_nope = jnp.einsum("sr,rhk->hsk", c_q, w["w_qb_nope"])
    q_rope = jnp.einsum("sr,rhk->hsk", c_q, w["w_qb_rope"])
    kva = h @ w["w_kva"]
    c_kv, k_rope = _rmsnorm(kva[:, :rkv], eps), kva[:, rkv:]
    k_nope = jnp.einsum("sc,chk->hsk", c_kv, w["w_kb"])
    v = jnp.einsum("sc,chk->hsk", c_kv, w["w_vb"])

    inv_freq, multiplier = yarn_inv_freq(cfg)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * multiplier, jnp.sin(ang) * multiplier
    q = jnp.concatenate([q_nope, _rotate(q_rope, cos, sin)], -1)
    k_rope = jnp.broadcast_to(_rotate(k_rope, cos, sin)[None],
                              k_nope.shape[:2] + k_rope.shape[-1:])
    k = jnp.concatenate([k_nope, k_rope], -1)

    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    scale = q.shape[-1] ** -0.5 * m * m
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    keys = jnp.arange(S)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", qb, k) * scale
        seen = (start + jnp.arange(block))[:, None] >= keys[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, S, block))   # [blocks,H,block,dv]
    o = o.transpose(1, 0, 2, 3).reshape(q.shape[0], S, -1)
    return x + jnp.einsum("hsk,hkd->sd", o, w["w_o"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_ffn(x, w, cfg: dict):
    h = _rmsnorm(x, cfg["rms_norm_eps"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def route(h, w, cfg: dict):
    """``(idx [S,k] among all published experts, weights [S,k])``."""
    scores = jax.nn.sigmoid(h @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
               * cfg["routed_scaling_factor"])
    return idx, weights


def expert_part(h, idx, weights, expert: int, we: dict):
    """Expert ``expert`` computed for every token, masked to its own."""
    gate = jnp.sum(jnp.where(idx == expert, weights, 0.0), axis=-1)
    return gate[:, None] * _swiglu(h, we["gate"], we["up"], we["down"])


def held_ids(cfg: dict):
    first = cfg["deployment"]["first_expert"]
    return range(first, first + cfg["n_routed_experts"])


class Reference:
    """``outputs`` runs the sampled prompts layer by layer."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self._attention = jax.jit(lambda x, w: attention(x, w, cfg))
        self._dense_ffn = jax.jit(lambda x, w: dense_ffn(x, w, cfg))
        self._route = jax.jit(lambda x, w: route(
            _rmsnorm(x, cfg["rms_norm_eps"]), w, cfg))
        self._shared = jax.jit(lambda x, w: _swiglu(
            _rmsnorm(x, cfg["rms_norm_eps"]), w["ws_gate"], w["ws_up"],
            w["ws_down"]))
        self._expert = jax.jit(lambda x, idx, weights, expert, we: expert_part(
            _rmsnorm(x, cfg["rms_norm_eps"]), idx, weights, expert, we))
        self._rows = jax.jit(lambda idx: jnp.stack(
            [jnp.sum(idx == e) for e in held_ids(cfg)]))

    def outputs(self, inputs: dict) -> dict:
        """``{"INPUT_IDS": [N,S] int32}`` -> ``{"LOGITS": [N,V] float32,
        "EXPERT_ROWS": [N, expert layers, held experts]}`` (the second for
        tests: the rows each held expert was routed)."""
        cfg, served = self.cfg, self.cfg["served"]
        tokens = np.asarray(inputs[served["inputs"][0]["name"]])
        gc.collect()  # whatever held the device before is let go first
        with jax.default_matmul_precision("highest"):
            outer = outer_weights(cfg)
            ids = np.clip(tokens, 0, cfg["vocab_size"] - 1)
            xs = [jnp.take(outer["embed"], jnp.asarray(row), axis=0)
                  for row in ids]
            rows = [[] for _ in xs]
            for layer in range(cfg["num_hidden_layers"]):
                w = layer_weights(cfg, layer)
                xs = [self._attention(x, w) for x in xs]
                if layer < cfg["first_k_dense_replace"]:
                    xs = [self._dense_ffn(x, w) for x in xs]
                    continue
                routed = [self._route(x, w) for x in xs]
                ys = [self._shared(x, w) for x in xs]
                for expert in held_ids(cfg):
                    we = expert_weights(cfg, layer, expert)
                    ys = [y + self._expert(x, idx, weights, expert, we)
                          for x, y, (idx, weights) in zip(xs, ys, routed)]
                xs = [x + y for x, y in zip(xs, ys)]
                for n, (idx, _) in enumerate(routed):
                    rows[n].append(np.asarray(self._rows(idx)))
            eps = cfg["rms_norm_eps"]
            logits = [np.asarray(_rmsnorm(x[-1], eps) @ outer["head"])
                      for x in xs]
        return {served["outputs"][0]["name"]: np.stack(logits),
                "EXPERT_ROWS": np.asarray(rows, np.int64).reshape(
                    len(xs), -1, cfg["n_routed_experts"])}
