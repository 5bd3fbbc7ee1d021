"""Plain float32 reference of what the ``trinity_mini`` configuration serves.

Trinity-Mini's stack (AFMoE) as the configuration file cuts it to its first
``num_hidden_layers`` layers (the sizes are the published ``config.json``'s,
the wiring is what the file lists under ``assumed``): straightforward
``jax.numpy`` at ``highest`` matmul precision, no kernel, no cache, no
batching, no scan, nothing imported from the program.

* ``h = embed(ids) * sqrt(hidden_size)`` (``mup_enabled``).
* A layer: ``x = x + n2(attn(n1(x)))``, then ``x = x + n4(ffn(n3(x)))``:
  four RMSNorms of their own (scales at one).
* ``attn(h)``: ``q = h W_q`` (32 heads of 128), ``k = h W_k``, ``v = h W_v``
  (4 heads; query head ``i`` reads key head ``i // 8``); q and k normed over
  each head; **a sliding layer** rotates them (``rope_theta``, the whole
  head, half-split pairs) and query ``t`` sees key ``s`` where ``t - window <
  s <= t``; **a full layer** has no positional encoding and sees every
  ``s <= t``; softmax of ``q.k / sqrt(128)``; ``o = (P v) * sigmoid(h
  W_g)`` (one gate a head and value channel); ``attn = o W_o``.  Computed a
  block of queries at a time, a sliding layer's against the keys of the
  block's windows alone.
* ``ffn`` of the leading ``num_dense_layers`` layers: ``W_down(silu(W_gate
  h) * W_up h)``.  Of the others: ``s = sigmoid(h W_r)`` over all experts;
  the ``num_experts_per_tok`` chosen are the top of ``s + b`` (the layer's
  selection bias); ``w = s_chosen / (sum s_chosen + router_eps) *
  route_scale``; ``ffn = shared(h) + sum_e w_e down_e(silu(gate_e h) * up_e
  h)``.  Each expert is computed for the tokens routed to it.
* After the last layer one RMSNorm, then the untied head.

``forward(seqs)`` gives the last position's logits.  ``replay(ids, routes)``
routes **every token of every expert layer as the program routed it**
(``ROUTES``): on random weights the 8th and 9th of 128 sigmoid scores lie
within bfloat16's rounding, and an expert exchanged at one token reaches the
last row through every later key.  It weights the told experts by its own
scores and reports how far the least of them lies under its own 8th.

The weights are the bfloat16 values the configuration describes (a layer
from ``fold_in(PRNGKey(weights_seed), layer)``, a key a leaf, an expert's
draw under its id), upcast; made a layer and an expert at a time.
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
              "ws_gate": 15, "ws_up": 16, "ws_down": 17, "embed": 18,
              "head": 19, "w_q": 20, "w_k": 21, "w_v": 22, "w_g": 28}
SLIDING = "sliding_attention"
#: query rows a block of a full layer's attention (its f32 scores are
#: heads x rows x S: 1.07 GB at 32 heads and 32,768 keys)
FULL_ROWS = 256
#: query rows a block of a sliding layer's, against rows + window keys
WINDOW_ROWS = 512
#: rows an expert takes in one call, padded: one program serves them all
ROWS_A_CALL = 4096


def _draw(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _layer_key(cfg: dict, layer):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg["served"]["weights_seed"]), layer)


def _fan(n: int) -> float:
    return 1.0 / math.sqrt(n)


def kinds(cfg: dict) -> list:
    """The attention of each layer held: ``layer_types``' first
    ``num_hidden_layers`` entries."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _leaves(cfg: dict, layer, shapes: dict) -> dict:
    root = _layer_key(cfg, layer)
    return {name: _draw(jax.random.fold_in(root, _LEAF_KEYS[name]), shape,
                        scale) for name, (shape, scale) in shapes.items()}


def attention_weights(cfg: dict, layer) -> dict:
    """A layer's attention matrices (``layer`` may be traced)."""
    D, H, Hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    return _leaves(cfg, layer, {
        "w_q": ((D, H, dh), _fan(D)), "w_k": ((D, Hkv, dh), _fan(D)),
        "w_v": ((D, Hkv, dh), _fan(D)), "w_g": ((D, H, dh), _fan(D)),
        "w_o": ((H, dh, D), _fan(H * dh))})


def dense_weights(cfg: dict, layer) -> dict:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    return _leaves(cfg, layer, {
        "w_gate": ((D, F), _fan(D)), "w_up": ((D, F), _fan(D)),
        "w_down": ((F, D), _fan(F))})


def router_weights(cfg: dict, layer) -> dict:
    """The router, its selection bias and the shared expert."""
    D, E = cfg["hidden_size"], cfg["num_experts"]
    Fs = cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
    return _leaves(cfg, layer, {
        "router": ((D, E), 0.02), "router_bias": ((E,), 0.01),
        "ws_gate": ((D, Fs), _fan(D)), "ws_up": ((D, Fs), _fan(D)),
        "ws_down": ((Fs, D), _fan(Fs))})


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


def outer_weight(cfg: dict, name: str):
    """``embed [V,D]`` or ``head [D,V]``."""
    V, D = cfg["vocab_size"], cfg["hidden_size"]
    key = jax.random.fold_in(_layer_key(cfg, _OUTER), _LEAF_KEYS[name])
    return _draw(key, (V, D) if name == "embed" else (D, V), 0.02)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(x, rows: int):
    """``x [H,S,...]`` -> ``[S_pad / rows, H, rows, ...]``, S padded."""
    S = x.shape[1]
    pad = -S % rows
    x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    return jnp.moveaxis(x.reshape(x.shape[0], -1, rows, *x.shape[2:]), 1, 0)


def attention(x, w, cfg: dict, sliding: bool):
    """``x [S,D]`` of one sequence -> ``x`` plus the normed gated
    attention of a sliding or a full layer."""
    eps, dh, W = cfg["rms_norm_eps"], cfg["head_dim"], cfg["sliding_window"]
    S = x.shape[0]
    n = _rmsnorm(x, eps)
    q = _rmsnorm(jnp.einsum("sd,dhk->hsk", n, w["w_q"]), eps)
    k = _rmsnorm(jnp.einsum("sd,dhk->hsk", n, w["w_k"]), eps)
    v = jnp.einsum("sd,dhk->hsk", n, w["w_v"])
    if sliding:
        inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
            np.arange(dh // 2, dtype=np.float32) / (dh // 2))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        q, k = (_rotate(t, jnp.cos(ang), jnp.sin(ang)) for t in (q, k))
    rows = min(WINDOW_ROWS if sliding else FULL_ROWS, S)
    if sliding:
        # each block's keys: the window before its first row and its own
        k = jnp.pad(k, ((0, 0), (W, -S % rows), (0, 0)))
        v = jnp.pad(v, ((0, 0), (W, -S % rows), (0, 0)))
    Hkv = k.shape[0]

    def block(args):
        i, qb = args
        t = i * rows + jnp.arange(rows)
        if sliding:
            kb = jax.lax.dynamic_slice_in_dim(k, i * rows, rows + W, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, i * rows, rows + W, 1)
            s_pos = i * rows - W + jnp.arange(rows + W)
        else:
            kb, vb, s_pos = k, v, jnp.arange(S)
        seen = (s_pos[None, :] <= t[:, None]) & (s_pos[None, :] >= 0)
        if sliding:
            seen = seen & (t[:, None] - s_pos[None, :] < W)
        # query head i reads key/value head i // group
        qb = qb.reshape(Hkv, -1, rows, dh)
        scores = jnp.einsum("gjqd,gkd->gjqk", qb, kb) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gjqk,gkd->gjqd", p, vb).reshape(-1, rows, dh)

    qs = _blocks(q, rows)
    o = jax.lax.map(block, (jnp.arange(qs.shape[0]), qs))
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape[0], -1, dh)[:, :S]
    o = o * jax.nn.sigmoid(jnp.einsum("sd,dhk->hsk", n, w["w_g"]))
    return x + _rmsnorm(jnp.einsum("hsk,hkd->sd", o, w["w_o"]), eps)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_ffn(x, w, cfg: dict):
    eps = cfg["rms_norm_eps"]
    n = _rmsnorm(x, eps)
    return x + _rmsnorm(_swiglu(n, w["w_gate"], w["w_up"], w["w_down"]),
                        eps)


def route(x, w, cfg: dict):
    """``x [S,D]`` -> ``(n = norm(x), idx [S,k], s [S,E], s + b [S,E],
    shared(n) [S,D])``: sigmoid scores over all experts, the top k of score
    + bias, and the shared expert's part."""
    n = _rmsnorm(x, cfg["rms_norm_eps"])
    s = jax.nn.sigmoid(n @ w["router"])
    chosen_by = s + w["router_bias"]
    _, idx = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    return (n, idx, s, chosen_by,
            _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"]))


def chosen_weights(s, idx, cfg: dict):
    """The weights of the experts ``idx [...,k]`` from the scores ``s
    [...,E]`` alone."""
    xp = jnp if isinstance(s, jax.Array) else np
    picked = xp.take_along_axis(s, idx, axis=-1)
    return (picked / (picked.sum(-1, keepdims=True)
                      + cfg["assumed"]["router_eps"]) * cfg["route_scale"])


def told_route(s, chosen_by, chosen, cfg: dict):
    """Tokens routed as the program chose: this reference's own scores ``s
    [...,E]`` and ``chosen_by = s + b``, ``chosen [...,k]`` -> ``(weights
    [...,k], how far the least of the chosen lies under this reference's
    k-th in ``chosen_by``, as a share of that [...])``: 0 where the choice
    is the reference's own."""
    k = chosen.shape[-1]
    kth = -np.partition(-chosen_by, k - 1, axis=-1)[..., k - 1]
    least = np.take_along_axis(chosen_by, chosen, axis=-1).min(-1)
    return chosen_weights(s, chosen, cfg), np.maximum(0.0,
                                                      (kth - least) / kth)


def add_expert(y, h, rows, gates, we):
    """``y[rows] += gates * expert(h[rows])``; padding rows carry gate 0."""
    t = h[rows]
    out = _swiglu(t, we["gate"], we["up"], we["down"])
    return y.at[rows].add(gates[:, None] * out)


class Reference:
    """Sequences of one length go through a layer one after the other, in
    one call of the one-sequence functions above.  Weights are drawn by
    programs of their own with the layer and the expert traced, so that a
    dozen programs serve a whole comparison."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self._outer = {name: jax.jit(lambda name=name: outer_weight(cfg,
                                                                    name))
                       for name in ("embed", "head")}
        self._attention_weights = jax.jit(
            lambda layer: attention_weights(cfg, layer))
        self._dense_weights = jax.jit(lambda layer: dense_weights(cfg, layer))
        self._router_weights = jax.jit(
            lambda layer: router_weights(cfg, layer))
        self._expert = jax.jit(
            lambda layer, expert: expert_weights(cfg, layer, expert))
        self._attention = {sliding: jax.jit(
            lambda xs, w, sliding=sliding: jax.lax.map(
                lambda x: attention(x, w, cfg, sliding), xs))
            for sliding in (False, True)}
        self._dense = jax.jit(lambda xs, w: jax.lax.map(
            lambda x: dense_ffn(x, w, cfg), xs))
        self._route = jax.jit(lambda xs, w: jax.lax.map(
            lambda x: route(x, w, cfg), xs))
        self._add_expert = jax.jit(add_expert, donate_argnums=0)
        self._close = jax.jit(lambda xs, y: xs + _rmsnorm(
            y.reshape(xs.shape), cfg["rms_norm_eps"]))
        self._embed = jax.jit(lambda embed, ids: jnp.take(
            embed, ids, axis=0) * (math.sqrt(cfg["hidden_size"])
                                   if cfg["mup_enabled"] else 1.0))
        self._head = jax.jit(lambda xs, head: _rmsnorm(
            xs[:, -1], cfg["rms_norm_eps"]) @ head)

    def forward(self, seqs, routes=None, keep: dict = None) -> dict:
        """``seqs [N,S]`` ids -> ``{"logits" [N,1,V] (the last
        position's), "route_shortfall" [N,S]}``.  With ``routes [N, expert
        layers, S, k]`` every token is routed as told (``told_route``), and
        its shortfall is the largest over the layers; without, it is 0.
        ``keep`` (a dict, for tests) receives ``"expert_rows"``: pairs on
        each expert ``[N,E]``, an expert layer each."""
        cfg = self.cfg
        E, first = cfg["num_experts"], cfg["num_dense_layers"]
        gc.collect()  # whatever held the device before is let go first
        ids = np.clip(np.asarray(seqs), 0, cfg["vocab_size"] - 1).astype(
            np.int32)
        N, S = ids.shape
        short = np.zeros((N, S))
        if keep is not None:
            keep["expert_rows"] = []
        with jax.default_matmul_precision("highest"):
            xs = self._embed(self._outer["embed"](), ids)
            for layer, kind in enumerate(kinds(cfg)):
                xs = self._attention[kind == SLIDING](
                    xs, self._attention_weights(layer))
                if layer < first:
                    xs = self._dense(xs, self._dense_weights(layer))
                    continue
                n, idx, s, chosen_by, shared = self._route(
                    xs, self._router_weights(layer))
                idx = np.asarray(idx)
                if routes is not None:
                    idx = np.asarray(routes)[:, layer - first]
                    gates, under = told_route(
                        np.asarray(s), np.asarray(chosen_by), idx, cfg)
                    short = np.maximum(short, under)
                else:
                    gates = np.asarray(chosen_weights(s, idx, cfg))
                del s, chosen_by
                h = n.reshape(N * S, -1)
                idx = idx.reshape(N * S, -1)
                gates = gates.reshape(N * S, -1)
                # the shared expert's part, then each routed expert's
                y = shared.reshape(N * S, -1)
                del shared
                order = np.argsort(idx, axis=None, kind="stable")
                counts = np.bincount(idx.ravel(), minlength=E)
                for expert, end in enumerate(np.cumsum(counts)):
                    pairs = order[end - counts[expert]:end]
                    if not len(pairs):
                        continue
                    we = self._expert(layer, expert)
                    rows, slot = np.divmod(pairs, idx.shape[1])
                    for lo in range(0, len(rows), ROWS_A_CALL):
                        part = slice(lo, lo + ROWS_A_CALL)
                        pad = ROWS_A_CALL - len(rows[part])
                        y = self._add_expert(
                            y, h, np.pad(rows[part], (0, pad)).astype(
                                np.int32),
                            np.pad(gates[rows[part], slot[part]],
                                   (0, pad)).astype(np.float32), we)
                del h, n
                xs = self._close(xs, y)
                del y
                if keep is not None:
                    keep["expert_rows"].append(np.stack([np.bincount(
                        idx[r * S:(r + 1) * S].ravel(), minlength=E)
                        for r in range(N)]))
            logits = np.asarray(self._head(xs, self._outer["head"]()))
        return {"logits": logits[:, None], "route_shortfall": short}

    def replay(self, ids, routes) -> dict:
        """``ids [N,S]`` and the program's ``routes [N, expert layers, S,
        k]`` -> ``forward``'s answer with every token routed as the program
        routed it."""
        return self.forward(ids, routes=routes)
