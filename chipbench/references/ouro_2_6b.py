"""Plain float32 reference of what the ``ouro_2_6b`` configuration serves.

Ouro-2.6B's looped stack, as the configuration file states it (the sizes are
the published ``config.json``'s, the wiring is what the file lists under
``assumed``): straightforward ``jax.numpy`` at ``highest`` matmul precision,
no kernel, no cache, no batching, no scan, nothing imported from the
program.

* The layer (the same weights at every loop step).  ``u = norm(x)``; ``q, k,
  v = u W_q, u W_k, u W_v`` (16 heads of 128, no bias); q and k rotated
  (theta 1e6, the whole head, half-split pairs); scores ``q.k / sqrt(128)``
  under the causal mask, softmax; ``h = x + norm((P v) W_o)``; ``n =
  norm(h)``; ``y = h + norm(W_down(silu(W_gate n) * W_up n))``.  Four norms
  a layer, their scales at one.
* The model.  ``x = E[ids]``; for each of ``total_ut_steps`` loop steps:
  every layer in turn, then the final norm, whose output is both what the
  step hands on and what the exit gate (``g = w_g . x + b_g``) and the head
  read.
* The exit.  ``lambda_t = sigmoid(g_t)``; ``p_t = lambda_t prod_{s<t}(1 -
  lambda_s)``, the last step taking what is left; a token leaves at the
  first step whose cumulated ``p`` reaches ``early_exit_threshold`` (the
  last, where rounding keeps the sum under it) and its logits are ``W_head
  x`` of that step.
* Generation is greedy and the output at position ``i`` predicts the token
  at ``i + 1``.  **Every position here is a row of one full causal
  forward**: what the program's prefill, its per-step cache and its decode
  steps must reproduce.

``forward(seqs, positions)`` gives the logits and the exit probabilities at
the named positions.  ``replay(ids, tokens)`` is **teacher-forced on the
program's own tokens** (on random weights the largest of 49,152 logits
changes on bfloat16's rounding): one forward over ``ids + tokens[:-1]``,
read at the prompt's last position and at the last but one of the whole.

The weights are the bfloat16 values the configuration describes (a layer
from ``fold_in(PRNGKey(weights_seed), layer)``, a key a leaf), upcast; a
layer's exist one at a time and are drawn again at every loop step.
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

_OUTER = 1 << 16
_LEAF_KEYS = {"w_o": 6, "w_gate": 7, "w_up": 8, "w_down": 9, "embed": 18,
              "head": 19, "w_q": 20, "w_k": 21, "w_v": 22, "exit_gate": 23,
              "exit_gate_bias": 24}


def _draw(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _layer_key(cfg: dict, layer):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg["served"]["weights_seed"]), layer)


def _fan(n: int) -> float:
    return 1.0 / math.sqrt(n)


def layer_weights(cfg: dict, layer) -> dict:
    """The seven matrices of one layer (``layer`` may be traced), float32."""
    D, H, dh, F = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["head_dim"], cfg["intermediate_size"])
    root = _layer_key(cfg, layer)

    def leaf(name, shape, scale):
        return _draw(jax.random.fold_in(root, _LEAF_KEYS[name]), shape, scale)

    return {"w_q": leaf("w_q", (D, H, dh), _fan(D)),
            "w_k": leaf("w_k", (D, H, dh), _fan(D)),
            "w_v": leaf("w_v", (D, H, dh), _fan(D)),
            "w_o": leaf("w_o", (H, dh, D), _fan(H * dh)),
            "w_gate": leaf("w_gate", (D, F), _fan(D)),
            "w_up": leaf("w_up", (D, F), _fan(D)),
            "w_down": leaf("w_down", (F, D), _fan(F))}


def outer_weights(cfg: dict, name: str):
    """``"embed"`` ``[V,D]``, ``"head"`` ``[D,V]``, ``"exit_gate"`` ``[D]``
    or ``"exit_gate_bias"`` ``[1]``."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    shape = {"embed": (V, D), "head": (D, V), "exit_gate": (D,),
             "exit_gate_bias": (1,)}[name]
    key = jax.random.fold_in(_layer_key(cfg, _OUTER), _LEAF_KEYS[name])
    return _draw(key, shape, 0.02)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, w, cfg: dict):
    """``x [S,D]`` of one sequence through one layer."""
    eps, dh = cfg["rms_norm_eps"], cfg["head_dim"]
    S = x.shape[0]
    u = _rmsnorm(x, eps)
    q = jnp.einsum("sd,dhk->hsk", u, w["w_q"])
    k = jnp.einsum("sd,dhk->hsk", u, w["w_k"])
    v = jnp.einsum("sd,dhk->hsk", u, w["w_v"])
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(dh // 2, dtype=np.float32) / (dh // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    q, k = (_rotate(t, jnp.cos(ang), jnp.sin(ang)) for t in (q, k))
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(dh)
    pos = jnp.arange(S)
    p = jax.nn.softmax(
        jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hsk,hkd->sd", jnp.einsum("hqk,hkd->hqd", p, v), w["w_o"])
    h = x + _rmsnorm(a, eps)
    n = _rmsnorm(h, eps)
    ffn = (jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])) @ w["w_down"]
    return h + _rmsnorm(ffn, eps)


def exit_pdf(gates: np.ndarray) -> np.ndarray:
    """``gates [..., T]`` -> the probability of leaving at each step."""
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    left = np.ones(lam.shape[:-1])
    pdf = np.empty_like(lam)
    for t in range(lam.shape[-1]):
        pdf[..., t] = lam[..., t] * left
        left = left * (1.0 - lam[..., t])
    pdf[..., -1] += left   # the last step takes what is left
    return pdf


def exit_step(pdf: np.ndarray, threshold: float) -> np.ndarray:
    """The first step whose cumulated probability reaches ``threshold``,
    the last where none does."""
    reached = np.cumsum(pdf, axis=-1) >= threshold
    reached[..., -1] = True
    return reached.argmax(-1)


class Reference:
    """Every sequence goes through a layer in one call of the one-sequence
    function above.  Weights are drawn by a program of their own (the
    chip's compiler takes half a minute where a draw is fused into a
    ``highest`` matmul), the layer index traced, so that five programs
    serve a whole comparison."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        eps = cfg["rms_norm_eps"]
        draw_outer = outer_weights
        self._outer = jax.jit(lambda name: draw_outer(cfg, name),
                              static_argnums=0)
        self._weights = jax.jit(lambda index: layer_weights(cfg, index))
        self._layer = jax.jit(
            lambda xs, w: jax.vmap(lambda x: layer(x, w, cfg))(xs))
        self._embed = jax.jit(lambda embed, ids: jnp.take(embed, ids, axis=0))

        def close(xs, at, w_g, b_g):
            xs = _rmsnorm(xs, eps)
            kept = jnp.take(xs, at, axis=1)               # [N,len(at),D]
            return xs, kept, kept @ w_g + b_g

        self._close = jax.jit(close)
        self._head = jax.jit(lambda x, head: x @ head)

    def forward(self, seqs, positions) -> dict:
        """``seqs [N,S]`` ids, ``positions`` (the same for every sequence)
        -> ``{"logits" [N,len(positions),V], "exit_pdf" [N,len(positions),
        T], "exit_step" [N,len(positions)]}``."""
        cfg = self.cfg
        T = cfg["total_ut_steps"]
        gc.collect()  # whatever held the device before is let go first
        ids = np.clip(np.asarray(seqs), 0, cfg["vocab_size"] - 1).astype(
            np.int32)
        at = np.asarray(positions, np.int32)
        with jax.default_matmul_precision("highest"):
            xs = self._embed(self._outer("embed"), ids)
            w_g, b_g = self._outer("exit_gate"), self._outer("exit_gate_bias")
            states, gates = [], []
            for _ in range(T):
                for index in range(cfg["num_hidden_layers"]):
                    xs = self._layer(xs, self._weights(index))
                xs, kept, gate = self._close(xs, at, w_g, b_g)
                states.append(np.asarray(kept))
                gates.append(np.asarray(gate))
            pdf = exit_pdf(np.stack(gates, axis=-1))      # [N,len(at),T]
            left = exit_step(pdf, float(cfg["early_exit_threshold"]))
            states = np.stack(states, axis=2)             # [N,len(at),T,D]
            x = np.take_along_axis(states, left[..., None, None],
                                   axis=2)[:, :, 0]
            logits = np.asarray(self._head(x, self._outer("head")))
        return {"logits": logits, "exit_pdf": pdf.astype(np.float32),
                "exit_step": left}

    def replay(self, ids, tokens) -> dict:
        """``ids [N,P]`` and the program's ``tokens [N,G]`` -> ``{"logits"
        [N,2,V], "exit_pdf" [N,2,T]}`` at the prompt's last position (which
        chose ``tokens[:, 0]``) and at the last but one position of prompt
        and answer together (which chose ``tokens[:, -1]``)."""
        ids, tokens = np.asarray(ids), np.asarray(tokens)
        P, G = ids.shape[1], tokens.shape[1]
        seqs = np.concatenate([ids, tokens[:, :G - 1]], axis=1)
        return self.forward(seqs, [P - 1, P + G - 2])
