"""Plain float32 reference of what the ``lfm2_8b_a1b`` configuration serves.

LFM2-8B-A1B's stack of gated short convolutions and grouped-query attention
over a dense and then a sparse expert FFN, as the configuration file cuts it
to its first ``num_hidden_layers`` layers (the sizes are the published
``config.json``'s, the wiring is what the file lists under ``assumed``):
straightforward ``jax.numpy`` at ``highest`` matmul precision, no kernel, no
cache, no state, no batching, no scan, nothing imported from the program.

* A layer.  ``h = x + op(norm(x))``, ``y = h + ffn(norm(h))``; every norm an
  RMSNorm (scales at one), no bias anywhere.
* ``op`` of a ``conv`` layer.  ``[B, C, u] = split3(n W_in)``; ``z = B * u``;
  ``c_t = sum_j w[j] * z_{t - (L-1) + j}`` over the ``L = conv_L_cache``
  taps (depthwise over the channels, causal, zeros before position 0; the
  taps lie ``[L, D]``); ``op = (C * c) W_out``.
* ``op`` of a ``full_attention`` layer.  ``q = n W_q`` (32 heads of 64), ``k
  = n W_k``, ``v = n W_v`` (8 heads of 64; query head ``i`` reads key head
  ``i // 4``); q and k normed over each head, then rotated (theta 1e6, the
  whole head, half-split pairs); causal softmax of ``q.k / sqrt(64)``;
  ``op = (P v) W_o``.
* ``ffn`` of the leading ``num_dense_layers`` layers: ``W_down(silu(W_gate
  n) * W_up n)``.  Of the others: ``s = sigmoid(n W_r)`` over all experts;
  the ``num_experts_per_tok`` chosen are the top of ``s + b`` (the layer's
  selection bias); ``w = s_chosen / (sum s_chosen + router_eps) *
  routed_scaling_factor``; ``ffn = sum_e w_e down_e(silu(gate_e n) * up_e
  n)``.  No shared expert.
* After the last layer one RMSNorm, then the head, which is the embedding.
  The output at position ``i`` predicts the token at ``i + 1``.

**Every position here is a row of one full causal forward**: what the
program's prefill, its two kinds of state and its decode steps must
reproduce.  ``forward(seqs, positions)`` gives the logits at the named
positions.  ``generate(ids)`` follows its own greedy trajectory, a full
forward a token (the CPU tests compare the program's with it).
``replay(ids, tokens)`` is **teacher-forced on the program's own tokens**
(on random weights the largest of 65,536 logits changes on bfloat16's
rounding): one forward over ``ids + tokens[:-1]``, read at the prompt's last
position (which chose the first new token), at the position after it (the
first decode step: it reads what the prefill handed over and nothing else)
and at the last but one of the whole (which has read every cached key).

The weights are the bfloat16 values the configuration describes (a layer
from ``fold_in(PRNGKey(weights_seed), layer)``, a key a leaf, an expert's
draw under its id), upcast; made a layer and an expert at a time.  An expert
is computed for the tokens that chose it, gathered by index.
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

_OUTER = 1 << 16
_LEAF_KEYS = {"w_o": 6, "w_gate": 7, "w_up": 8, "w_down": 9, "router": 10,
              "router_bias": 11, "we_gate": 12, "we_up": 13, "we_down": 14,
              "embed": 18, "w_q": 20, "w_k": 21, "w_v": 22, "w_in": 25,
              "conv_w": 26, "w_out": 27}
CONV = "conv"


def _draw(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _layer_key(cfg: dict, layer):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg["served"]["weights_seed"]), layer)


def _fan(n: int) -> float:
    return 1.0 / math.sqrt(n)


def kinds(cfg: dict) -> list:
    """The operator of each layer held: ``layer_types``' first
    ``num_hidden_layers`` entries."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _leaves(cfg: dict, layer, shapes: dict) -> dict:
    root = _layer_key(cfg, layer)
    return {name: _draw(jax.random.fold_in(root, _LEAF_KEYS[name]), shape,
                        scale) for name, (shape, scale) in shapes.items()}


def op_weights(cfg: dict, layer, kind: str) -> dict:
    """The operator's matrices of one layer (``layer`` may be traced)."""
    D, H, Hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    dh, L = cfg["assumed"]["head_dim"], cfg["conv_L_cache"]
    if kind == CONV:
        return _leaves(cfg, layer, {
            "w_in": ((D, 3 * D), _fan(D)), "conv_w": ((L, D), _fan(L)),
            "w_out": ((D, D), _fan(D))})
    return _leaves(cfg, layer, {
        "w_q": ((D, H, dh), _fan(D)), "w_k": ((D, Hkv, dh), _fan(D)),
        "w_v": ((D, Hkv, dh), _fan(D)), "w_o": ((H, dh, D), _fan(H * dh))})


def dense_weights(cfg: dict, layer) -> dict:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    return _leaves(cfg, layer, {
        "w_gate": ((D, F), _fan(D)), "w_up": ((D, F), _fan(D)),
        "w_down": ((F, D), _fan(F))})


def router_weights(cfg: dict, layer) -> dict:
    """The router and the selection bias (zeros without ``use_expert_bias``)
    of an expert layer."""
    D, E = cfg["hidden_size"], cfg["num_experts"]
    w = _leaves(cfg, layer, {"router": ((D, E), 0.02),
                             "router_bias": ((E,), 0.01)})
    if not cfg["use_expert_bias"]:
        w["router_bias"] = jnp.zeros((E,), jnp.float32)
    return w


def expert_weights(cfg: dict, layer, expert) -> dict:
    """Expert ``expert`` of ``layer`` (either may be traced)."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    root = _layer_key(cfg, layer)

    def leaf(name, shape, scale):
        key = jax.random.fold_in(
            jax.random.fold_in(root, _LEAF_KEYS[name]), expert)
        return _draw(key, shape, scale)

    return {"gate": leaf("we_gate", (D, F), _fan(D)),
            "up": leaf("we_up", (D, F), _fan(D)),
            "down": leaf("we_down", (F, D), _fan(F))}


def embedding(cfg: dict):
    """``[V,D]``: the embedding, and transposed the head."""
    key = jax.random.fold_in(_layer_key(cfg, _OUTER), _LEAF_KEYS["embed"])
    return _draw(key, (cfg["vocab_size"], cfg["hidden_size"]), 0.02)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(x, w, cfg: dict):
    """``x [S,D]`` of one sequence -> ``(x + the gated short convolution,
    z [S,D])``: ``z`` is what a conv layer's state holds the last rows of."""
    S, L = x.shape[0], cfg["conv_L_cache"]
    n = _rmsnorm(x, cfg["norm_eps"])
    gate_in, gate_out, u = jnp.split(n @ w["w_in"], 3, axis=-1)
    z = gate_in * u
    padded = jnp.pad(z, ((L - 1, 0), (0, 0)))
    c = sum(w["conv_w"][j] * padded[j:j + S] for j in range(L))
    return x + (gate_out * c) @ w["w_out"], z


def attention(x, w, cfg: dict):
    """``x [S,D]`` of one sequence -> ``x`` plus the layer's attention."""
    eps, dh = cfg["norm_eps"], cfg["assumed"]["head_dim"]
    S = x.shape[0]
    n = _rmsnorm(x, eps)
    q = _rmsnorm(jnp.einsum("sd,dhk->hsk", n, w["w_q"]), eps)
    k = _rmsnorm(jnp.einsum("sd,dhk->hsk", n, w["w_k"]), eps)
    v = jnp.einsum("sd,dhk->hsk", n, w["w_v"])
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(dh // 2, dtype=np.float32) / (dh // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    q, k = (_rotate(t, jnp.cos(ang), jnp.sin(ang)) for t in (q, k))
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(dh)
    pos = jnp.arange(S)
    p = jax.nn.softmax(
        jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", p, v)
    return x + jnp.einsum("hsk,hkd->sd", o, w["w_o"])


def dense_ffn(x, w, cfg: dict):
    n = _rmsnorm(x, cfg["norm_eps"])
    return x + (jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])) @ w["w_down"]


def route(x, w, cfg: dict):
    """``x [S,D]`` -> ``(n = norm(x), idx [S,k], weights [S,k], s [S,E],
    s + b [S,E])``: sigmoid scores over all experts, the top k of score +
    bias, the chosen scores over their sum + ``router_eps``, times
    ``routed_scaling_factor``."""
    n = _rmsnorm(x, cfg["norm_eps"])
    s = jax.nn.sigmoid(n @ w["router"])
    chosen_by = s + w["router_bias"]
    _, idx = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    return n, idx, chosen_weights(s, idx, cfg), s, chosen_by


def chosen_weights(s, idx, cfg: dict):
    """The weights of the experts ``idx [...,k]`` from the scores ``s
    [...,E]`` alone."""
    xp = jnp if isinstance(s, jax.Array) else np
    picked = xp.take_along_axis(s, idx, axis=-1)
    return (picked / (picked.sum(-1, keepdims=True)
                      + cfg["assumed"]["router_eps"])
            * cfg["routed_scaling_factor"])


def told_route(s, chosen_by, chosen, cfg: dict):
    """Tokens routed as the program chose: this reference's own scores ``s
    [...,E]`` and ``chosen_by = s + b [...,E]``, ``chosen [...,k]`` ->
    ``(weights [...,k], how far the least of the chosen lies under this
    reference's k-th in ``chosen_by``, as a share of that [...])``: 0 where
    the choice is the reference's own."""
    kth = np.sort(chosen_by, axis=-1)[..., -chosen.shape[-1]]
    least = np.take_along_axis(chosen_by, chosen, axis=-1).min(-1)
    return chosen_weights(s, chosen, cfg), np.maximum(0.0,
                                                      (kth - least) / kth)


def add_expert(y, h, rows, gates, we):
    """``y[rows] += gates * expert(h[rows])``; padding rows carry gate 0."""
    t = h[rows]
    out = (jax.nn.silu(t @ we["gate"]) * (t @ we["up"])) @ we["down"]
    return y.at[rows].add(gates[:, None] * out)


#: rows an expert takes in one call, padded: one program serves every expert
ROWS_A_CALL = 1024


class Reference:
    """Sequences of one length go through a layer in one call of the
    one-sequence functions above.  Weights are drawn by programs of their
    own with the layer and the expert traced (the chip's compiler takes
    half a minute where a draw is fused into a ``highest`` matmul), so that
    a dozen programs serve a whole comparison."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self._embedding = jax.jit(lambda: embedding(cfg))
        self._op_weights = {kind: jax.jit(
            lambda layer, kind=kind: op_weights(cfg, layer, kind))
            for kind in set(kinds(cfg))}
        self._dense_weights = jax.jit(lambda layer: dense_weights(cfg, layer))
        self._router_weights = jax.jit(
            lambda layer: router_weights(cfg, layer))
        self._expert = jax.jit(
            lambda layer, expert: expert_weights(cfg, layer, expert))
        self._conv = jax.jit(
            lambda xs, w: jax.vmap(lambda x: short_conv(x, w, cfg))(xs))
        # one after the other: a sequence's f32 scores are 38 MB
        self._attention = jax.jit(
            lambda xs, w: jax.lax.map(lambda x: attention(x, w, cfg), xs))
        self._dense = jax.jit(
            lambda xs, w: jax.vmap(lambda x: dense_ffn(x, w, cfg))(xs))
        self._route = jax.jit(
            lambda xs, w: jax.vmap(lambda x: route(x, w, cfg))(xs))
        self._add_expert = jax.jit(add_expert, donate_argnums=0)
        self._take = jax.jit(lambda embed, ids: jnp.take(embed, ids, axis=0))
        self._head_at = jax.jit(lambda xs, at, embed: _rmsnorm(
            jnp.take(xs, at, axis=1), cfg["norm_eps"]) @ embed.T)

    def forward(self, seqs, positions, keep: dict = None,
                routes=None) -> dict:
        """``seqs [N,S]`` ids, ``positions`` (the same for every sequence)
        -> ``{"logits" [N,len(positions),V], "route_shortfall" [N,S]}``.
        ``keep`` (a dict, for tests) receives ``"z"``: a conv layer's ``z
        [N,S,D]`` (``None`` for an attention layer), and ``"expert_rows"``:
        pairs on each expert ``[N,E]``, an expert layer each.  With
        ``routes [N,S,expert layers,k]`` every token is routed as told
        (``told_route``), and its shortfall is the largest over the layers;
        without, it is 0."""
        cfg = self.cfg
        E, first = cfg["num_experts"], cfg["num_dense_layers"]
        gc.collect()  # whatever held the device before is let go first
        ids = np.clip(np.asarray(seqs), 0, cfg["vocab_size"] - 1).astype(
            np.int32)
        N, S = ids.shape
        short = np.zeros((N, S))
        if keep is not None:
            keep.update(z=[], expert_rows=[])
        with jax.default_matmul_precision("highest"):
            embed = self._embedding()
            xs = self._take(embed, ids)
            for layer, kind in enumerate(kinds(cfg)):
                w = self._op_weights[kind](layer)
                z = None
                if kind == CONV:
                    xs, z = self._conv(xs, w)
                else:
                    xs = self._attention(xs, w)
                if keep is not None:
                    keep["z"].append(None if z is None else np.asarray(z))
                if layer < first:
                    xs = self._dense(xs, self._dense_weights(layer))
                    continue
                n, idx, gates, s, chosen_by = self._route(
                    xs, self._router_weights(layer))
                h = n.reshape(N * S, -1)
                idx, gates = np.asarray(idx), np.asarray(gates)
                if routes is not None:
                    idx = np.asarray(routes)[:, :, layer - first]
                    gates, under = told_route(
                        np.asarray(s), np.asarray(chosen_by), idx, cfg)
                    short = np.maximum(short, under)
                idx = idx.reshape(N * S, -1)
                gates = gates.reshape(N * S, -1)
                y = jnp.zeros_like(h)
                for expert in range(E):
                    rows, slot = np.nonzero(idx == expert)
                    we = self._expert(layer, expert) if len(rows) else None
                    for lo in range(0, len(rows), ROWS_A_CALL):
                        part = slice(lo, lo + ROWS_A_CALL)
                        pad = ROWS_A_CALL - len(rows[part])
                        y = self._add_expert(
                            y, h, np.pad(rows[part], (0, pad)).astype(
                                np.int32),
                            np.pad(gates[rows[part], slot[part]],
                                   (0, pad)).astype(np.float32), we)
                xs = xs + y.reshape(xs.shape)
                if keep is not None:
                    keep["expert_rows"].append(np.stack([np.bincount(
                        idx[n * S:(n + 1) * S].ravel(), minlength=E)
                        for n in range(N)]))
            logits = np.asarray(self._head_at(
                xs, np.asarray(positions, np.int32), embed))
        return {"logits": logits, "route_shortfall": short}

    def generate(self, ids) -> dict:
        """``ids [N,P]`` -> ``{"tokens" [N,G], "logits" [N,G,V]}``: greedy,
        on this reference's own trajectory, one full forward a token."""
        seqs = np.asarray(ids)
        tokens, rows = [], []
        for _ in range(self.cfg["served"]["new_tokens"]):
            row = self.forward(seqs, [seqs.shape[1] - 1])["logits"][:, 0]
            tokens.append(row.argmax(-1).astype(np.int32))
            rows.append(row)
            seqs = np.concatenate([seqs, tokens[-1][:, None]], axis=1)
        return {"tokens": np.stack(tokens, axis=1),
                "logits": np.stack(rows, axis=1)}

    def replay(self, ids, tokens, routes=None) -> dict:
        """``ids [N,P]`` and the program's ``tokens [N,G]`` -> ``{"logits"
        [N,3,V], "route_shortfall" [N,P + G - 1]}`` at the prompt's last position
        (which chose ``tokens[:, 0]``), at the next (the first decode step,
        which chose ``tokens[:, 1]``) and at the last but one position of
        prompt and answer together (which chose ``tokens[:, -1]``); with one
        new token the three rows are one, with two the last two.  **Every
        position is routed as the program routed it** (``routes [N,P + G -
        1,expert layers,k]``): where the reference's k-th and next expert
        lie within bfloat16's rounding the program takes the other, an
        expert exchanged moves the token's row by more than the precision
        compared here does, and what a token carries reaches the next two
        through every conv layer.  What keeps the program's choices honest
        is the shortfall, of every token."""
        ids, tokens = np.asarray(ids), np.asarray(tokens)
        P, G = ids.shape[1], tokens.shape[1]
        seqs = np.concatenate([ids, tokens[:, :G - 1]], axis=1)
        last = P + G - 2
        return self.forward(seqs, [P - 1, min(P, last), last], routes=routes)
