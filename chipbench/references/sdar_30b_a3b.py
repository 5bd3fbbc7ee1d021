"""Plain float32 reference of what the ``sdar_30b_a3b`` configuration serves.

SDAR-30B-A3B-Chat's layer (Qwen3-MoE's) and its generation by diffusion over
blocks, as the configuration file cuts it to one pipeline stage:
straightforward ``jax.numpy`` at ``highest`` matmul precision, no kernel, no
cache, no batching, nothing imported from the program.

* The layer.  ``h = norm(x)``; ``q = h W_q`` (32 heads of 128), ``k = h
  W_k``, ``v = h W_v`` (4 heads of 128; query head ``i`` reads key head ``i
  // 8``); q and k normed over each head's 128 (scales at one), then rotated
  (theta 1e6, the whole head, half-split pairs); scores ``q.k / sqrt(128)``
  under the mask that is causal over blocks of ``B`` positions (``j // B <=
  i // B``), softmax, ``x += (P v) W_o``.  ``h = norm(x)``; ``p = softmax(h
  W_r)`` over all 128 experts; the top 8; ``w = p_top / sum p_top``; ``x +=
  sum w_e down_e(silu(gate_e h) * up_e h)``.  Final norm, untied head.  The
  output at position ``i`` predicts the token at ``i``.
* The loop (the family's published ``generate``, sizes under
  ``assumed.generation``).  After the prompt, a block of ``B`` MASK ids is
  run ``denoising_steps`` times; each pass commits, among the positions
  still masked, the ``B / denoising_steps`` most confident (confidence: the
  largest softmax probability; token: the arg-max) and every one over
  ``confidence_threshold`` where that is set, and stops early when none is
  left.  **Every pass here is a full forward over the prompt, the finished
  blocks and the block's state**: what the program's cache and commit pass
  must reproduce.

Two entry points.  ``generate(ids)`` follows its own trajectory (the CPU
tests compare the program's with it).  ``replay(ids, tokens, commit_pass)``
is **teacher-forced on the program's own tokens and order** and returns the
two logit rows the program returns: on random weights the largest of 151,936
logits and the most confident of four positions change on bfloat16's
rounding, so on the chip the trajectory is the program's and the logits are
compared.

The weights are the bfloat16 values the configuration describes (a layer
from ``fold_in(PRNGKey(weights_seed), layer)``, a key a leaf, an expert's
draw under its id), upcast; made a layer and an expert at a time.  An expert
is computed for the tokens that chose it, gathered by index (the dense form
costs 16 times as much).
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

_OUTER = 1 << 16
_LEAF_KEYS = {"w_o": 6, "router": 10, "we_gate": 12, "we_up": 13,
              "we_down": 14, "embed": 18, "head": 19, "w_q": 20, "w_k": 21,
              "w_v": 22}


def _draw(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _layer_key(cfg: dict, layer: int):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg["served"]["weights_seed"]), layer)


def _fan(n: int) -> float:
    return 1.0 / math.sqrt(n)


def layer_weights(cfg: dict, layer: int) -> dict:
    """Every leaf of one layer but the experts, in float32."""
    D, H, Hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    root = _layer_key(cfg, layer)

    def leaf(name, shape, scale):
        return _draw(jax.random.fold_in(root, _LEAF_KEYS[name]), shape, scale)

    return {"w_q": leaf("w_q", (D, H, dh), _fan(D)),
            "w_k": leaf("w_k", (D, Hkv, dh), _fan(D)),
            "w_v": leaf("w_v", (D, Hkv, dh), _fan(D)),
            "w_o": leaf("w_o", (H, dh, D), _fan(H * dh)),
            "router": leaf("router", (D, cfg["num_experts"]), 0.02)}


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


def outer_weights(cfg: dict, name: str):
    """``"embed"`` ``[V,D]`` or ``"head"`` ``[D,V]``: one at a time, each
    1.2 GB in float32 at the published size."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    key = jax.random.fold_in(_layer_key(cfg, _OUTER), _LEAF_KEYS[name])
    return _draw(key, (V, D) if name == "embed" else (D, V), 0.02)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, cfg: dict):
    """``x [S,D]`` of one sequence -> ``x`` plus the layer's attention."""
    eps, dh = cfg["rms_norm_eps"], cfg["head_dim"]
    B = cfg["assumed"]["generation"]["block_length"]
    S = x.shape[0]
    h = _rmsnorm(x, eps)
    q = _rmsnorm(jnp.einsum("sd,dhk->hsk", h, w["w_q"]), eps)
    k = _rmsnorm(jnp.einsum("sd,dhk->hsk", h, w["w_k"]), eps)
    v = jnp.einsum("sd,dhk->hsk", h, w["w_v"])
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(dh // 2, dtype=np.float32) / (dh // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    q, k = (_rotate(t, jnp.cos(ang), jnp.sin(ang)) for t in (q, k))
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(dh)
    pos = jnp.arange(S)
    seen = pos[None, :] // B <= pos[:, None] // B
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", p, v)
    return x + jnp.einsum("hsk,hkd->sd", o, w["w_o"])


def route(x, w, cfg: dict):
    """``(h = norm(x), idx [S,k] of all experts, weights [S,k], p [S,E])``:
    the softmax over all experts, its top k, their weights renormalised."""
    h = _rmsnorm(x, cfg["rms_norm_eps"])
    p = jax.nn.softmax(h @ w["router"], axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return h, idx, top / top.sum(-1, keepdims=True), p


def told_route(p, chosen):
    """One token's routing as the program chose it: ``p [E]`` (this
    reference's own probabilities), ``chosen [k]`` -> ``(weights [k], how
    far the least probable of the chosen lies under this reference's k-th,
    as a share of that)``: 0 where the choice is the reference's own."""
    picked = p[chosen]
    kth = np.sort(p)[-len(chosen)]
    return picked / picked.sum(), float(max(0.0, (kth - picked.min()) / kth))


def add_expert(y, h, rows, gates, we):
    """``y[rows] += gates * expert(h[rows])``; padding rows carry gate 0."""
    t = h[rows]
    out = (jax.nn.silu(t @ we["gate"]) * (t @ we["up"])) @ we["down"]
    return y.at[rows].add(gates[:, None] * out)


#: rows an expert takes in one call, padded: one program serves every expert
ROWS_A_CALL = 4096


class Reference:
    """Sequences of one length go through a layer in one call of the
    one-sequence functions above.  Every program is a ``jax.jit`` of those
    functions with the layer and the expert traced, and weights are drawn
    by programs of their own, so that a dozen programs serve a whole
    comparison (the chip's compiler takes 11 s for three ``highest``
    matmuls, and half a minute where a draw is fused into one)."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.gen = cfg["assumed"]["generation"]
        eps = cfg["rms_norm_eps"]
        draw_outer = outer_weights  # as it is now: a test scales the head
        self._outer = jax.jit(lambda name: draw_outer(cfg, name),
                              static_argnums=0)
        self._layer = jax.jit(lambda layer: layer_weights(cfg, layer))
        self._expert = jax.jit(
            lambda layer, expert: expert_weights(cfg, layer, expert))
        self._embed = jax.jit(lambda embed, ids, start, like: jnp.take(
            embed, jax.lax.dynamic_slice(ids, (start,), (like.size,)),
            axis=0).reshape(like.shape + embed.shape[1:]))

        def attend_route(xs, w):
            # one after the other: a sequence's f32 scores are 143 MB
            xs = jax.lax.map(lambda x: attention(x, w, cfg), xs)
            return (xs,) + jax.vmap(lambda x: route(x, w, cfg))(xs)

        self._attend_route = jax.jit(attend_route)
        self._flat = jax.jit(lambda hs: jnp.concatenate(
            [h.reshape(-1, h.shape[-1]) for h in hs]))
        self._add_expert = jax.jit(add_expert, donate_argnums=0)
        self._add_part = jax.jit(lambda xs, y, start: xs + jax.lax.dynamic_slice(
            y, (start, 0), (xs.shape[0] * xs.shape[1], xs.shape[2])
        ).reshape(xs.shape))
        self._head_at = jax.jit(lambda xs, at, head: _rmsnorm(
            jnp.take_along_axis(xs, at[..., None], axis=1), eps) @ head)

    def logits(self, seqs: list, positions: list, expert_rows: list = None,
               told: list = None) -> list:
        """Each sequence of ids ``[S_i]`` through every layer, then the
        final norm and the head at its ``positions`` -> ``[len(positions_i),
        V]`` a sequence (sequences of one length ask for equally many).
        The sequences share each layer's and each expert's weights, which
        exist one at a time.  ``expert_rows`` (a list, for tests) receives
        ``[sequence, expert]`` pair counts a layer.  ``told`` holds, a
        sequence, ``(position, experts [L,k])``: that one token is routed
        as told (``told_route``) and the sequence's entry becomes
        ``(position, experts, largest shortfall over the layers)``."""
        cfg = self.cfg
        E = cfg["num_experts"]
        gc.collect()  # whatever held the device before is let go first
        # sequences of one length form a group; tokens lie group by group
        groups, first_row = {}, {}
        for n, seq in enumerate(seqs):
            groups.setdefault(len(seq), []).append(n)
        row = 0
        for S, members in groups.items():
            for n in members:
                first_row[n], row = row, row + S
        with jax.default_matmul_precision("highest"):
            ids = np.clip(np.concatenate([seqs[n] for members in
                                          groups.values() for n in members]),
                          0, cfg["vocab_size"] - 1).astype(np.int32)
            embed = self._outer("embed")
            xs = {S: self._embed(embed, ids, first_row[members[0]],
                                 np.empty((len(members), S), np.int8))
                  for S, members in groups.items()}
            del embed
            for layer in range(cfg["num_hidden_layers"]):
                w = self._layer(layer)
                routed = {S: self._attend_route(x, w) for S, x in xs.items()}
                h = self._flat([r[1] for r in routed.values()])
                idx, gates = (np.concatenate(
                    [np.asarray(r[i]).reshape(-1, r[i].shape[-1])
                     for r in routed.values()]) for i in (2, 3))
                for n, entry in enumerate(told or ()):
                    at, chosen = entry[0], np.asarray(entry[1])
                    S = len(seqs[n])
                    p = np.asarray(routed[S][4][groups[S].index(n), at])
                    idx[first_row[n] + at] = chosen[layer]
                    gates[first_row[n] + at], short = told_route(
                        p, chosen[layer])
                    told[n] = (at, chosen, max(short, *entry[2:]))
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
                xs = {S: self._add_part(routed[S][0], y,
                                        first_row[members[0]])
                      for S, members in groups.items()}
                if expert_rows is not None:
                    expert_rows.append(np.stack([np.bincount(
                        idx[first_row[n]:first_row[n] + len(seq)].ravel(),
                        minlength=E) for n, seq in enumerate(seqs)]))
            out, head = [None] * len(seqs), self._outer("head")
            for S, members in groups.items():
                at = np.array([positions[n] for n in members], np.int32)
                for n, rows in zip(members, np.asarray(
                        self._head_at(xs[S], at, head))):
                    out[n] = rows
        return out

    def generate(self, ids) -> dict:
        """One prompt ``ids [P]`` -> ``{"TOKENS" [G], "COMMIT_PASS" [G],
        "LOGITS" [2,V], "passes"}``, every pass a full forward."""
        gen, G = self.gen, self.cfg["served"]["new_tokens"]
        B, T, mask_id = (gen["block_length"], gen["denoising_steps"],
                         gen["mask_token_id"])
        threshold = gen["confidence_threshold"]
        seq = [int(t) for t in np.asarray(ids).reshape(-1)]
        when, rows, passes = [], [None, None], 0
        for n in range(G // B):
            block, masked = [mask_id] * B, [True] * B
            block_when = [0] * B
            for t in range(T):
                if not any(masked):
                    break
                at = len(seq) + np.arange(B)
                lg = self.logits([np.array(seq + block)], [at])[0]
                passes += 1
                p = np.asarray(jax.nn.softmax(jnp.asarray(lg), axis=-1))
                conf = np.where(masked, p.max(-1), -np.inf)
                commit = np.zeros(B, bool)
                commit[np.argsort(-conf, kind="stable")[:B // T]] = True
                if threshold is not None:
                    commit |= conf > threshold
                commit &= np.array(masked)
                first = int(np.argmax(commit))
                if n == 0 and t == 0:
                    rows[0] = lg[first]
                rows[1] = lg[first]
                for i in np.nonzero(commit)[0]:
                    block[i], masked[i] = int(lg[i].argmax()), False
                    block_when[i] = t
            seq += block
            when += block_when
        P = len(seq) - G
        return {"TOKENS": np.array(seq[P:], np.int32),
                "COMMIT_PASS": np.array(when, np.int32),
                "LOGITS": np.stack(rows).astype(np.float32),
                "passes": passes}

    def replay(self, ids, tokens, commit_pass, routes) -> dict:
        """``ids [N,P]`` and the program's ``tokens [N,G]``, ``commit_pass
        [N,G]`` and ``routes [N,2,L,k]`` -> ``{"logits" [N,2,V],
        "route_shortfall" [N,2]}``: the logits, at the program's own state,
        of the first position (lowest index) committed at block 0's pass 0
        and of the first committed at the last block's last pass.  **That
        position is routed as the program routed it**: the 8th and 9th of
        128 probabilities lie within bfloat16's rounding of each other in
        one layer in four, and an expert exchanged moves the row by a
        twentieth, more than the precision compared here does (``PERF.md``
        §2).  What keeps the program's choice honest is the shortfall: how
        far the least probable expert it chose lies under this reference's
        own 8th, as a share of it (0 where the two agree; rounding leaves a
        few hundredths; an expert chosen at random reads 0.5 and more).
        Two forwards a request: the prompt and a block of MASK; the prompt,
        the finished blocks and the last block as it stood before its last
        pass."""
        gen = self.gen
        B, mask_id = gen["block_length"], gen["mask_token_id"]
        seqs, told = [], []
        for prompt, toks, when, chosen in zip(
                np.asarray(ids), np.asarray(tokens), np.asarray(commit_pass),
                np.asarray(routes)):
            P, G = len(prompt), len(toks)
            seqs.append(np.concatenate([prompt, [mask_id] * B]))
            told.append((P + int(np.argmax(when[:B] == 0)), chosen[0], 0.0))
            last = when[G - B:]
            state = np.where(last < last.max(), toks[G - B:], mask_id)
            seqs.append(np.concatenate([prompt, toks[:G - B], state]))
            told.append((P + G - B + int(np.argmax(last == last.max())),
                         chosen[1], 0.0))
        rows = self.logits(seqs, [[entry[0]] for entry in told], told=told)
        return {"logits": np.concatenate(rows).reshape(len(seqs) // 2, 2, -1),
                "route_shortfall": np.array(
                    [entry[2] for entry in told]).reshape(-1, 2)}
