"""Plain float32 reference of what the ``hy4_preview`` configuration serves.

Hy4-preview's block at its published widths, as the configuration file cuts
it to one chip: straightforward ``jax.numpy`` at ``highest`` matmul
precision, no kernels, no batching, no cache, nothing imported from the
program.  The configuration's ``assumed`` lists every line below that the
config's keys do not fix.

* Four residual streams a token, four copies of its embedding at first.
  Around each sublayer F: ``x = vec(X) / rms(vec(X))``, ``a = x Phi``,
  ``H_pre = sigmoid(alpha_0 a_pre + b_pre)``, ``H_post = hc_magnitude *
  sigmoid(alpha_1 a_post + b_post)``, ``H_res`` = 20 Sinkhorn iterations
  (rows, then columns) of ``exp(alpha_2 a_res + b_res)``; F reads
  ``norm(sum_i H_pre_i X_i)`` and ``X <- H_res X + H_post^T F``.
* Attention: ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` (a part without
  position beside a rotary part a head), ``h W_kva`` gives the kv latent
  (normed) and one rotary key a token; ``c_kv W_kb`` and ``c_kv W_vb`` the
  heads' keys and values; default rotary at ``rope_theta`` (half-split
  pairs); scores ``q.k / sqrt(qk_head_dim)`` over the chosen keys alone,
  with a learned sink in the denominator; the output times
  ``sigmoid(h W_g)``, then ``W_o``.
* The indexer (layers marked ``full`` and the MTP module): ``q^I = c_q
  W_qI``, ``k^I = LayerNorm(h W_kI)``, rotated on their first
  ``qk_rope_head_dim``; ``w = h W_w / sqrt(index_n_heads)``; ``I_ts = sum_j
  w_tj ReLU(q^I_tj . k^I_s / sqrt(index_head_dim))`` for ``s <= t``; the
  chosen keys are the top ``min(t + 1, index_topk)`` of each row.  A
  ``shared`` layer attends over the keys of the full layer before it.
* SwiGLU clamped: ``silu(min(g, limit)) * clip(u, -limit, limit)``.  An
  expert layer: sigmoid scores over all published experts, the top k of
  score + bias, weights from the scores, renormalised and scaled; every
  held expert computed for every token and masked to those that chose it;
  the shared expert for every token.

**Told the program's choices.**  On random weights the index scores near a
row's 2,048th, and the 8th and 9th of 256 router scores, lie within
bfloat16's rounding of each other: a program that rounds takes other keys and
experts than float32 would, each exchange moves a row by more than the
rounding compared here, and a choice made at one position reaches every
later one through the keys and values.  So ``replay`` attends over the keys
the program's full indexers chose (its ``CHOSEN`` bit planes) and routes
every token to the program's experts (its ``ROUTES``), weighting them by its
own scores; what keeps those choices honest is how far the least of them
lies under this reference's own k-th score (``index_shortfall``,
``told_route``).
* After the last layer the streams are summed; the last position's logits
  over the held rows of the head.  The MTP module: ``eh_proj [norm(Emb(
  x_{t+1})); norm(h_t)]``, one expert block with its own indexer, a norm,
  the head; ``x_{t+1}`` past the prompt is the token the caller gives (the
  program's own first token: teacher-forced).

The weights are the bfloat16 values the configuration describes (each block
from ``fold_in(PRNGKey(weights_seed), block)``, the MTP module block
``num_hidden_layers``, a key a leaf, an expert's draw under its id; f32
normal times the scale, rounded to bfloat16 once), used upcast.  They are
made one block at a time, an expert at a time, and ``PROMPTS_A_PASS``
prompts go through a block before the next block's weights exist.
"""

from __future__ import annotations

import functools
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
    "w_g": 28, "sink": 29, "w_qi": 30, "w_ki": 31, "w_wi": 32,
    "hc_phi": 33, "hc_alpha": 34, "hc_bias": 35, "eh_proj": 36,
}
QUERY_BLOCK = 256
PROMPTS_A_PASS = 2


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _draw(key, shape, scale):
    """f32 normal times ``scale``, rounded to bfloat16 once, as one
    compiled program: the same rounding of the normal's last bit as the
    program's draw."""
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _block_key(cfg: dict, block: int):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg["served"]["weights_seed"]), block)


def kinds(cfg: dict):
    """``(mlp, indexer)`` of each block: the layers held, then the MTP
    module."""
    L = cfg["num_hidden_layers"]
    out = list(zip(cfg["mlp_layer_types"][:L], cfg["indexer_types"][:L]))
    return out + [("sparse", "full")] * cfg["num_nextn_predict_layers"]


def block_weights(cfg: dict, block: int) -> dict:
    """Every leaf of one block but the routed experts, in float32."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    n = cfg["hc_mult"]
    root = _block_key(cfg, block)

    def leaf(name, shape, scale):
        return _draw(jax.random.fold_in(root, _LEAF_KEYS[name]), shape, scale)

    fan = lambda k: 1.0 / math.sqrt(k)  # noqa: E731
    w = {"w_qa": leaf("w_qa", (D, rq), fan(D)),
         "w_qb_nope": leaf("w_qb_nope", (rq, H, dn), fan(rq)),
         "w_qb_rope": leaf("w_qb_rope", (rq, H, dr), fan(rq)),
         "w_kva": leaf("w_kva", (D, rkv + dr), fan(D)),
         "w_kb": leaf("w_kb", (rkv, H, dn), fan(rkv)),
         "w_vb": leaf("w_vb", (rkv, H, dv), fan(rkv)),
         "w_o": leaf("w_o", (H, dv, D), fan(H * dv)),
         "w_g": leaf("w_g", (D, H, dv), fan(D)),
         "sink": leaf("sink", (H,), 1.0),
         "hc_phi": leaf("hc_phi", (2, n * D, n * (n + 2)), fan(n * D)),
         "hc_alpha": leaf("hc_alpha", (2, 3), 0.1),
         "hc_bias": leaf("hc_bias", (2, n * (n + 2)), 1.0)}
    mlp, indexer = kinds(cfg)[block]
    if indexer == "full":
        Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
        w.update({"w_qi": leaf("w_qi", (rq, Hi, Di), fan(rq)),
                  "w_ki": leaf("w_ki", (D, Di), fan(D)),
                  "w_wi": leaf("w_wi", (D, Hi), fan(D))})
    if mlp == "dense":
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
    if block >= cfg["num_hidden_layers"]:
        w["eh_proj"] = leaf("eh_proj", (2 * D, D), fan(2 * D))
    return w


def expert_weights(cfg: dict, block: int, expert: int) -> dict:
    """Routed expert ``expert`` (its id among all published) of ``block``."""
    D, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    root = _block_key(cfg, block)

    def leaf(name, shape, scale):
        key = jax.random.fold_in(
            jax.random.fold_in(root, _LEAF_KEYS[name]), expert)
        return _draw(key, shape, scale)

    return {"gate": leaf("we_gate", (D, Fe), 1.0 / math.sqrt(D)),
            "up": leaf("we_up", (D, Fe), 1.0 / math.sqrt(D)),
            "down": leaf("we_down", (Fe, D), 1.0 / math.sqrt(Fe))}


def outer_weights(cfg: dict) -> dict:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    root = _block_key(cfg, _OUTER)
    return {"embed": _draw(jax.random.fold_in(root, _LEAF_KEYS["embed"]),
                           (V, D), 0.02),
            "head": _draw(jax.random.fold_in(root, _LEAF_KEYS["head"]),
                          (D, V), 0.02)}


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotary(cfg: dict, S: int):
    dim = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinkhorn(m, iterations: int, eps: float):
    for _ in range(iterations):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def mixing(X, w, which: int, cfg: dict):
    """``X [S,n,D]`` -> ``(H_pre [S,n], H_post [S,n], H_res [S,n,n])``."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    flat = X.reshape(X.shape[0], -1)
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    a = x @ w["hc_phi"][which]
    alpha, b = w["hc_alpha"][which], w["hc_bias"][which]
    pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + b[:n])
    post = cfg["hc_magnitude"] * jax.nn.sigmoid(alpha[1] * a[:, n:2 * n]
                                                + b[n:2 * n])
    res = jnp.exp(alpha[2] * a[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    return pre, post, sinkhorn(res, cfg["assumed"]["sinkhorn_iterations"],
                               eps)


def unpack(bits, S: int):
    """The served bit planes ``[..., W]`` int32 (key ``s`` is bit ``s // W``
    of word ``s % W``) -> ``[..., S]`` bool."""
    bits = np.asarray(bits).view(np.uint32)
    W = bits.shape[-1]
    planes = -(-S // W)
    out = np.empty(bits.shape[:-1] + (planes, W), bool)
    for p in range(planes):
        out[..., p, :] = (bits >> np.uint32(p)) & 1
    return out.reshape(bits.shape[:-1] + (planes * W,))[..., :S]


def index_shortfall(h, c_q, w, cfg: dict, cos, sin, chosen):
    """How far the least-scored of the keys ``chosen [S,S]`` (a query's
    row) lies under this reference's own ``min(t + 1, index_topk)``-th
    causal index score, in standard deviations of the row's causal scores:
    ``[S]``, 0 where the chosen keys are this reference's own top k (ties
    aside)."""
    S = h.shape[0]
    Hi, Di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                  cfg["qk_rope_head_dim"])
    k = min(cfg["index_topk"], S)
    iq = jnp.einsum("sr,rhd->hsd", c_q, w["w_qi"])
    iq = jnp.concatenate([_rotate(iq[..., :dr], cos, sin), iq[..., dr:]], -1)
    ik = h @ w["w_ki"]
    mean = ik.mean(-1, keepdims=True)
    ik = (ik - mean) * jax.lax.rsqrt(((ik - mean) ** 2).mean(-1, keepdims=True)
                                     + cfg["assumed"]["index_norm_eps"])
    ik = jnp.concatenate([_rotate(ik[:, :dr], cos, sin), ik[:, dr:]], -1)
    wt = h @ w["w_wi"] / math.sqrt(Hi)
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    keys = jnp.arange(S)

    def one(start):
        q = jax.lax.dynamic_slice_in_dim(iq, start, block, axis=1)
        scores = jnp.einsum("hqd,sd->hqs", q, ik) / math.sqrt(Di)
        wq = jax.lax.dynamic_slice_in_dim(wt, start, block, axis=0)
        scores = jnp.einsum("hqs,qh->qs", jax.nn.relu(scores), wq)
        seen = (start + jnp.arange(block))[:, None] >= keys[None, :]
        # -inf where a row holds fewer than k keys: no shortfall there
        kth = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)[0][:, -1]
        told = jax.lax.dynamic_slice_in_dim(chosen, start, block, axis=0)
        least = jnp.min(jnp.where(told & seen, scores, jnp.inf), axis=-1)
        n = seen.sum(-1)
        mean = jnp.where(seen, scores, 0.0).sum(-1) / n
        sd = jnp.sqrt(jnp.where(seen, (scores - mean[:, None]) ** 2,
                                0.0).sum(-1) / n)
        gap = jnp.maximum(kth - least, 0.0)
        return jnp.where(gap > 0, gap / sd, 0.0)

    return jax.lax.map(one, jnp.arange(0, S, block)).reshape(S)


def attention(h, w, cfg: dict, chosen):
    """``h [S,D]`` normed -> the sublayer's output ``[S,D]``; over the keys
    ``chosen [S,S]``."""
    eps, rkv = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    S = h.shape[0]
    c_q = _rmsnorm(h @ w["w_qa"], eps)
    q_nope = jnp.einsum("sr,rhk->hsk", c_q, w["w_qb_nope"])
    q_rope = jnp.einsum("sr,rhk->hsk", c_q, w["w_qb_rope"])
    kva = h @ w["w_kva"]
    c_kv, k_rope = _rmsnorm(kva[:, :rkv], eps), kva[:, rkv:]
    k_nope = jnp.einsum("sc,chk->hsk", c_kv, w["w_kb"])
    v = jnp.einsum("sc,chk->hsk", c_kv, w["w_vb"])
    cos, sin = _rotary(cfg, S)
    q = jnp.concatenate([q_nope, _rotate(q_rope, cos, sin)], -1)
    k_rope = jnp.broadcast_to(_rotate(k_rope, cos, sin)[None],
                              k_nope.shape[:2] + k_rope.shape[-1:])
    k = jnp.concatenate([k_nope, k_rope], -1)
    scale = q.shape[-1] ** -0.5
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    z = w["sink"][:, None, None]

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seen = jax.lax.dynamic_slice_in_dim(chosen, start, block, axis=0)
        scores = jnp.where(seen[None], jnp.einsum("hqd,hkd->hqk", qb, k)
                           * scale, -jnp.inf)
        m = jnp.maximum(scores.max(-1, keepdims=True), z)
        p = jnp.exp(scores - m)
        p = p / (jnp.exp(z - m) + p.sum(-1, keepdims=True))
        return jnp.einsum("hqk,hkd->hqd", p, v)

    o = jax.lax.map(one, jnp.arange(0, S, block))   # [blocks,H,block,dv]
    o = o.transpose(1, 0, 2, 3).reshape(q.shape[0], S, -1)
    gate = jax.nn.sigmoid(jnp.einsum("sd,dhk->hsk", h, w["w_g"]))
    return jnp.einsum("hsk,hkd->sd", o * gate, w["w_o"])


def _swiglu(h, gate, up, down, limit):
    g, u = h @ gate, h @ up
    return (jax.nn.silu(jnp.minimum(g, limit))
            * jnp.clip(u, -limit, limit)) @ down


def route(h, w, cfg: dict):
    """``(idx [S,k] among all published experts, weights [S,k])``."""
    scores = jax.nn.sigmoid(h @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    return idx, chosen_weights(scores, idx, cfg)


def chosen_weights(s, idx, cfg: dict):
    """The weights of the experts ``idx [...,k]`` from the scores ``s
    [...,E]`` alone, renormalised over the k and scaled."""
    xp = jnp if isinstance(s, jax.Array) else np
    picked = xp.take_along_axis(s, idx, axis=-1)
    return (picked / (picked.sum(-1, keepdims=True)
                      + cfg["assumed"]["router_eps"])
            * cfg["routed_scaling_factor"])


def told_route(s, chosen_by, chosen, cfg: dict):
    """Tokens routed as the program chose: this reference's own scores ``s
    [S,E]`` and ``chosen_by = s + b``, ``chosen [S,k]`` -> ``(weights
    [S,k], how far the least of the chosen lies under this reference's k-th
    in ``chosen_by``, as a share of that [S])``: 0 where the choice is the
    reference's own."""
    kth = np.sort(chosen_by, axis=-1)[..., -chosen.shape[-1]]
    least = np.take_along_axis(chosen_by, chosen, axis=-1).min(-1)
    return chosen_weights(s, chosen, cfg), np.maximum(0.0,
                                                      (kth - least) / kth)


def held_ids(cfg: dict):
    first = cfg["deployment"]["first_expert"]
    return range(first, first + cfg["n_routed_experts"])


class Reference:
    """``replay`` runs the sampled prompts block by block."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        eps, limit = cfg["rms_norm_eps"], cfg["swiglu_limit"]
        n = cfg["hc_mult"]

        def pre(X, w, which):
            h_pre, post, res = mixing(X, w, which, cfg)
            h = _rmsnorm(jnp.einsum("sn,snd->sd", h_pre, X), eps)
            return h, post, res

        def post(X, y, h_post, res):
            return (jnp.einsum("sij,sjd->sid", res, X)
                    + h_post[..., None] * y[:, None])

        def attend(X, w, chosen):
            h, h_post, res = pre(X, w, 0)
            return post(X, attention(h, w, cfg, chosen), h_post, res)

        def shortfall(X, w, chosen):
            h, _, _ = pre(X, w, 0)
            c_q = _rmsnorm(h @ w["w_qa"], eps)
            cos, sin = _rotary(cfg, X.shape[0])
            return index_shortfall(h, c_q, w, cfg, cos, sin, chosen)

        def dense(X, w):
            h, h_post, res = pre(X, w, 1)
            y = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], limit)
            return post(X, y, h_post, res)

        def ffn_in(X, w):
            h, h_post, res = pre(X, w, 1)
            s = jax.nn.sigmoid(h @ w["router"])
            y = _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], limit)
            return h, h_post, res, s, s + w["router_bias"], y

        def expert(h, idx, weights, e, we):
            gate = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
            return gate[:, None] * _swiglu(h, we["gate"], we["up"],
                                           we["down"], limit)

        def mtp_in(h, nxt, w, outer):
            e = jnp.take(outer["embed"], nxt, axis=0)
            m = jnp.concatenate([_rmsnorm(e, eps), _rmsnorm(h, eps)], -1)
            return jnp.broadcast_to((m @ w["eh_proj"])[:, None],
                                    (h.shape[0], n, h.shape[1]))

        self._shortfall = jax.jit(shortfall)
        self._attend = jax.jit(attend)
        self._dense = jax.jit(dense)
        self._ffn_in = jax.jit(ffn_in)
        self._expert = jax.jit(expert)
        self._post = jax.jit(post)
        self._mtp_in = jax.jit(mtp_in)

    def _block(self, xs, chosen, block: int, told_bits, told_routes):
        """``xs`` (the streams of each prompt) through one block, a full
        block attending over ``told_bits`` (each prompt's ``[S,W]``) and an
        expert block routing as ``told_routes`` (``[S,k]``) say; returns
        them with each prompt's keys chosen and each prompt's largest
        shortfall of the keys and of the experts in this block."""
        cfg = self.cfg
        mlp, indexer = kinds(cfg)[block]
        w = block_weights(cfg, block)
        index_short = route_short = np.zeros(len(xs))
        if indexer == "full":
            S = xs[0].shape[0]
            chosen = [jnp.asarray(unpack(b, S)) for b in told_bits]
            index_short = np.array([float(self._shortfall(X, w, c).max())
                                    for X, c in zip(xs, chosen)])
        xs = [self._attend(X, w, c) for X, c in zip(xs, chosen)]
        if mlp == "dense":
            return ([self._dense(X, w) for X in xs], chosen, index_short,
                    route_short)
        parts, told, route_short = [], [], []
        for X, idx in zip(xs, told_routes):
            h, h_post, res, s, chosen_by, y = self._ffn_in(X, w)
            idx = np.asarray(idx)
            weights, under = told_route(np.asarray(s), np.asarray(chosen_by),
                                        idx, cfg)
            route_short.append(float(under.max()))
            parts.append((h, h_post, res, y))
            told.append((jnp.asarray(idx), jnp.asarray(weights, jnp.float32)))
        ys = [p[3] for p in parts]
        for e in held_ids(cfg):
            we = expert_weights(cfg, block, e)
            ys = [y + self._expert(p[0], idx, weights, e, we)
                  for y, p, (idx, weights) in zip(ys, parts, told)]
        xs = [self._post(X, y, p[1], p[2]) for X, y, p in zip(xs, ys, parts)]
        return xs, chosen, index_short, np.array(route_short)

    def replay(self, ids, first_tokens, chosen, routes) -> dict:
        """``ids [N,S]`` int32, the program's own next tokens ``[N]``, its
        full indexers' bit planes ``chosen [N, full blocks, S, W]`` and its
        routes ``[N, expert blocks, S, k]`` -> ``{"logits": [N, 2, V],
        "index_shortfall": [N], "route_shortfall": [N]}``: the main head's
        last-position row and the MTP module's, teacher-forced on
        ``first_tokens``, every block attending and routing as the program
        did; each prompt's largest shortfall of the keys and of the experts
        chosen."""
        cfg = self.cfg
        ids = np.clip(np.asarray(ids), 0, cfg["vocab_size"] - 1)
        first = np.clip(np.asarray(first_tokens), 0, cfg["vocab_size"] - 1)
        chosen_all, routes_all = np.asarray(chosen), np.asarray(routes)
        S = ids.shape[1]
        logits, index_short, route_short = [], [], []
        gc.collect()  # whatever held the device before is let go first
        with jax.default_matmul_precision("highest"):
            for lo in range(0, len(ids), PROMPTS_A_PASS):
                group = ids[lo:lo + PROMPTS_A_PASS]
                bits = chosen_all[lo:lo + len(group)]
                told = routes_all[lo:lo + len(group)]
                outer = outer_weights(cfg)
                xs = [jnp.broadcast_to(
                    jnp.take(outer["embed"], jnp.asarray(row),
                             axis=0)[:, None],
                    (S, cfg["hc_mult"], cfg["hidden_size"])) for row in group]
                chosen = None
                by_index = by_route = np.zeros(len(group))
                f = e = 0   # the next full block's planes, expert block's routes
                L = cfg["num_hidden_layers"]
                eps = cfg["rms_norm_eps"]
                for block, (mlp, indexer) in enumerate(kinds(cfg)):
                    if block == L:   # the MTP module on the summed streams
                        hs = [X.sum(1) for X in xs]
                        rows = [[np.asarray(_rmsnorm(h[-1], eps)
                                            @ outer["head"])] for h in hs]
                        w = block_weights(cfg, L)
                        xs = [self._mtp_in(h, jnp.asarray(np.append(
                            row[1:], t)), w, outer) for h, row, t in zip(
                                hs, group, first[lo:lo + len(group)])]
                    xs, chosen, i_short, r_short = self._block(
                        xs, chosen, block,
                        bits[:, f] if indexer == "full" else None,
                        told[:, e] if mlp == "sparse" else None)
                    by_index = np.maximum(by_index, i_short)
                    by_route = np.maximum(by_route, r_short)
                    f += indexer == "full"
                    e += mlp == "sparse"
                if cfg["num_nextn_predict_layers"]:
                    for X, out in zip(xs, rows):
                        out.append(np.asarray(_rmsnorm(X[-1].sum(0), eps)
                                              @ outer["head"]))
                else:
                    rows = [[np.asarray(_rmsnorm(X.sum(1)[-1], eps)
                                        @ outer["head"])] for X in xs]
                logits += [np.stack(r) for r in rows]
                index_short += list(by_index)
                route_short += list(by_route)
                del xs, outer
        return {"logits": np.stack(logits),
                "index_shortfall": np.asarray(index_short),
                "route_shortfall": np.asarray(route_short)}
