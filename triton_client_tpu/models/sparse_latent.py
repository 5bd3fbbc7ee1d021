"""Learned sparse attention over latent attention, four residual streams:
Hy4's block.

The sixth block of the zoo, beside ``latent_moe.py``'s, whose latent
projections, router and held-expert layer it shares.  What it has that the
others have not:

* **An indexer that picks the keys a query reads** (DeepSeek-V3.2's
  lightning indexer).  From the query latent ``c_q`` and the normed input
  ``h``: ``q^I = c_q W_qI`` (``index_n_heads`` x ``index_head_dim``),
  ``k^I = LayerNorm(h W_kI)``, both rotated on their first
  ``qk_rope_head_dim``, ``w = h W_w / sqrt(index_n_heads)``; the score of key
  ``s <= t`` is ``I_ts = sum_j w_tj ReLU(q^I_tj . k^I_s /
  sqrt(index_head_dim))``
  and ``S_t`` is the top ``min(t + 1, index_topk)`` keys by it (ties to the
  lower position, as ``lax.top_k``).  The threshold is found exactly, a bit
  of the score's sortable key at a time (:func:`_kth_largest`), and the
  choice travels as bit planes (``ops/sparse_attention.pack``).
* **Indices reused across layers** (IndexCache): ``indexer_types`` marks a
  layer ``full`` (it runs an indexer) or ``shared`` (it attends over the
  keys the nearest full layer before it chose, at the same query).
* **Attention over the chosen keys alone**, with a learned sink a head in
  the softmax's denominator and an elementwise sigmoid gate on its output:
  ``out = (o * sigmoid(h W_g)) W_o`` (``ops/sparse_attention.py``).
* **Four residual streams** (``hc_mult``), mixed around every sublayer F by
  coefficients the token computes (the mHC form): from ``x = vec(X) /
  rms(vec(X))``, ``[a_pre, a_post, a_res] = x Phi``; ``H_pre = sigmoid(
  alpha_0 a_pre + b_pre)``, ``H_post = hc_magnitude * sigmoid(alpha_1 a_post
  + b_post)``, ``H_res = Sinkhorn(exp(alpha_2 a_res + b_res))`` (4 x 4,
  rows and columns summing to one); F reads ``RMSNorm(sum_i H_pre_i X_i)``
  and ``X <- H_res X + H_post^T F``.  The streams start as four copies of
  the embedding and are summed after the last block.  They travel as
  ``X [B,S,n·D]``, a token's streams one after another; on a TPU each side
  of the mixing is one pass over them, a kernel of
  ``ops/stream_mixing.py``.
* **SwiGLU clamped** at ``swiglu_limit`` (``parts.gated``) in every
  FFN.
* **A multi-token-prediction module** (DeepSeek-V3's form): ``m_t = W_eh
  [RMSNorm(Emb(x_{t+1})); RMSNorm(h_t)]`` (``h_t`` the main stack's summed
  stream, ``x_{t+1}`` the next prompt token and, at the last position, the
  program's own greedy token), one expert block with its own indexer on
  streams expanded from ``m``, a norm, the shared head: the draft of the
  token after next.

The streams, every norm, the mixing coefficients, the router's scores, the
softmax with its sink, the index scores and the logits are float32; the
matrices see bfloat16.  The forward returns the main head's and the MTP
head's last-position logits, the greedy tokens, every full indexer's bit
planes (what the attention read), every token's routes, and what the device
counted.
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
from ..ops import sparse_attention as sa
from ..ops import stream_mixing as sm

FULL, SHARED = "full", "shared"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class SparseLatentConfig:
    """The source's keys under the source's names (``n_routed_experts`` and
    ``vocab_size`` are what this chip holds, ``routed_experts_total`` the
    router's width), then what the configuration file lists under
    ``assumed``, and the served shape."""

    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    n_routed_experts: int          # held here
    routed_experts_total: int      # the router's outputs
    first_expert: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    vocab_size: int                # rows of the vocabulary held here
    rms_norm_eps: float
    rope_theta: float
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    indexer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    hc_mult: int
    hc_magnitude: float
    hc_eps: float
    swiglu_limit: float
    num_nextn_predict_layers: int
    seq_len: int
    weights_seed: int
    sinkhorn_iterations: int = 20
    index_norm_eps: float = 1e-6   # the indexer key's LayerNorm
    router_eps: float = 1e-20      # added to the chosen scores' sum

    # how ``latent_moe.route`` scores: sigmoid, the top k of score + bias
    scoring_func = "sigmoid"
    router_bias = True

    def __post_init__(self):
        kinds = self.layer_kinds
        if len(self.indexer_types) < self.num_hidden_layers \
                or len(self.mlp_layer_types) < self.num_hidden_layers:
            raise ValueError("indexer_types and mlp_layer_types name every "
                             "layer held")
        if any(k not in ((m, i) for m in (DENSE, SPARSE)
                         for i in (FULL, SHARED)) for k in kinds):
            raise ValueError(f"layer kinds {sorted(set(kinds))}: 'dense' or "
                             "'sparse' by 'full' or 'shared'")
        if kinds[0][1] != FULL:
            raise ValueError("the first layer runs an indexer (full): a "
                             "shared layer reuses the one before it")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module, or none")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("the indexer's head holds the rotary part")

    @classmethod
    def from_file(cls, cfg: dict) -> "SparseLatentConfig":
        """A configuration file of ``chipbench/configs`` (the source's keys
        at the top, ``indexer_types`` and ``mlp_layer_types`` whole: the
        first ``num_hidden_layers`` entries are the layers held)."""
        names = {f.name for f in dataclasses.fields(cls)}
        top = {k: v for k, v in cfg.items() if k in names}
        L = cfg["num_hidden_layers"]
        dep, assumed = cfg["deployment"], cfg["assumed"]
        return cls(**dict(
            top, indexer_types=tuple(cfg["indexer_types"][:L]),
            mlp_layer_types=tuple(cfg["mlp_layer_types"][:L]),
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            routed_experts_total=dep["published"]["n_routed_experts"],
            first_expert=dep["first_expert"],
            sinkhorn_iterations=assumed["sinkhorn_iterations"],
            index_norm_eps=assumed["index_norm_eps"],
            router_eps=assumed["router_eps"],
            seq_len=cfg["served"]["seq_len"],
            weights_seed=cfg["served"]["weights_seed"]))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """``(mlp, indexer)`` of every block: the layers held, then the MTP
        module's (an expert block with an indexer of its own)."""
        kinds = tuple(zip(self.mlp_layer_types[:self.num_hidden_layers],
                          self.indexer_types[:self.num_hidden_layers]))
        return kinds + ((SPARSE, FULL),) * self.num_nextn_predict_layers

    @property
    def n_blocks(self) -> int:
        return self.num_hidden_layers + self.num_nextn_predict_layers

    @property
    def index_k(self) -> int:
        return min(self.index_topk, self.seq_len)

    @property
    def full_blocks(self) -> int:
        return sum(kind[1] == FULL for kind in self.layer_kinds)

    @property
    def expert_blocks(self) -> int:
        return sum(kind[0] == SPARSE for kind in self.layer_kinds)

    # what ``tr.serve_mesh`` asks of a configuration
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_experts(self) -> int:
        return self.n_routed_experts

    moe = True


#: Hy4-preview's ``config.json`` cut to rank 0 of an EP32 x DP-attention
#: prefill pool: the dense layer and layers 1-4 (one period of
#: ``indexer_types``), 8 of 256 routed experts, an eighth of the
#: vocabulary, the MTP module; every width as published
#: (``chipbench/configs/hy4_preview.json`` states the cut and what is
#: assumed of the wiring).
HY4_PREVIEW_EP32_SHARE = SparseLatentConfig(
    hidden_size=6144, num_hidden_layers=5, num_attention_heads=64,
    q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, intermediate_size=18432,
    moe_intermediate_size=2048, n_shared_experts=1, n_routed_experts=8,
    routed_experts_total=256, first_expert=0, num_experts_per_tok=8,
    routed_scaling_factor=2.827, vocab_size=15104, rms_norm_eps=1e-5,
    rope_theta=1e7, index_n_heads=32, index_head_dim=128, index_topk=2048,
    indexer_types=(FULL, FULL, SHARED, SHARED, SHARED),
    mlp_layer_types=(DENSE,) + (SPARSE,) * 4, hc_mult=4, hc_magnitude=2,
    hc_eps=1e-6, swiglu_limit=10, num_nextn_predict_layers=1, seq_len=8192,
    weights_seed=43)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: SparseLatentConfig, block: int) -> Dict[str, Tuple]:
    """``{leaf: (shape, scale of the normal draw)}`` of one block (the MTP
    module is block ``num_hidden_layers``); a routed expert's leaves are per
    expert."""
    D, H, dv = cfg.hidden_size, cfg.num_attention_heads, cfg.v_head_dim
    n = cfg.hc_mult
    fan = lambda k: 1.0 / math.sqrt(k)  # noqa: E731
    mlp, indexer = cfg.layer_kinds[block]
    # latent_moe's attention and FFN leaves under their names
    shapes = lm.leaf_shapes(cfg, mlp == DENSE)
    shapes.update({
        "w_g": ((D, H, dv), fan(D)),
        # a logit a head; the published initial value is 0, drawn here so
        # that each head's sink differs
        "sink": ((H,), 1.0),
        # the mixing of the streams around the attention (0) and the FFN (1)
        "hc_phi": ((2, n * D, n * (n + 2)), fan(n * D)),
        "hc_alpha": ((2, 3), 0.1),
        "hc_bias": ((2, n * (n + 2)), 1.0),
    })
    if indexer == FULL:
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        rq = cfg.q_lora_rank
        shapes.update({"w_qi": ((rq, Hi, Di), fan(rq)),
                       "w_ki": ((D, Di), fan(D)), "w_wi": ((D, Hi), fan(D))})
    if block >= cfg.num_hidden_layers:
        shapes["eh_proj"] = ((2 * D, D), fan(2 * D))
    return shapes


def _block_params(cfg: SparseLatentConfig, block: int) -> Dict[str, jax.Array]:
    """One block's leaves in bfloat16 (the selection bias and the mixing's
    scalars upcast to f32), drawn from ``fold_in(PRNGKey(weights_seed),
    block)``, an expert under its id; norms are ones, the indexer key's
    LayerNorm bias zeros."""
    D = cfg.hidden_size
    shapes = _leaf_shapes(cfg, block)
    norms = lm.norms(cfg)
    if "w_ki" in shapes:
        norms["ln_ki"] = cfg.index_head_dim
    if "eh_proj" in shapes:
        norms.update(ln_e=D, ln_h=D, ln_out=D)
    out = parts.draw_layer(
        cfg.weights_seed, block, shapes, norms,
        cfg.first_expert + jnp.arange(cfg.n_routed_experts),
        ("router_bias", "hc_alpha", "hc_bias"))
    if "w_ki" in shapes:
        out["ln_ki_bias"] = jnp.zeros((cfg.index_head_dim,), jnp.bfloat16)
    return out


def groups(cfg: SparseLatentConfig):
    """The layers held as runs of one kind: ``[(kind, [layer, ...])]``; a
    run is one ``lax.scan`` over its layers' stacked leaves."""
    out = []
    for i, kind in enumerate(cfg.layer_kinds[:cfg.num_hidden_layers]):
        if out and out[-1][0] == kind:
            out[-1][1].append(i)
        else:
            out.append((kind, [i]))
    return out


def init_params(cfg: SparseLatentConfig, quantized: bool = False
                ) -> Dict[str, Any]:
    """``{"embed", "final_ln", "head", "groups": [stacked leaves of a run],
    "mtp": the module's block (none without one)}``; a run's leaves are
    stacked a block at a time."""
    prep = jax.jit(parts.quantize_weights) if quantized else (lambda x: x)
    stacks = []
    for _, layers in groups(cfg):
        stacks.append({})
        for i, layer in enumerate(layers):
            parts.stack(stacks[-1], prep(_block_params(cfg, layer)), i,
                        len(layers))
    return dict(parts.outer_params(cfg), groups=stacks,
                mtp=(prep(_block_params(cfg, cfg.num_hidden_layers))
                     if cfg.num_nextn_predict_layers else None))


# ---------------------------------------------------------------------------
# The four streams
# ---------------------------------------------------------------------------

def sinkhorn(m, iterations: int, eps: float, axes=(-1, -2)):
    """``m`` positive, ``n x n`` matrices on ``axes`` (a row runs along the
    first, a column along the second: ``[..., n, n]`` by default) -> rows
    then columns normalised, ``iterations`` times: close to doubly
    stochastic."""
    row, column = axes

    def once(_, m):
        m = m / (jnp.sum(m, axis=row, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=column, keepdims=True) + eps)

    return lax.fori_loop(0, iterations, once, m)


def mixing(blk, X, which: int, cfg: SparseLatentConfig):
    """``X [B,S,n,D]`` (or ``[B,S,n·D]``) f32 -> ``(H_pre [B,S,n], H_post
    [B,S,n], H_res [B,S,n,n])`` of sublayer ``which`` (0 attention, 1
    FFN)."""
    n = cfg.hc_mult
    flat = X.reshape(X.shape[:2] + (-1,))
    x = flat * lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                         + cfg.hc_eps)
    phi = blk["hc_phi"][which]
    a = jnp.dot(x.astype(phi.dtype), phi, preferred_element_type=jnp.float32)
    alpha, bias = blk["hc_alpha"][which], blk["hc_bias"][which]
    pre = jax.nn.sigmoid(alpha[0] * a[..., :n] + bias[:n])
    post = cfg.hc_magnitude * jax.nn.sigmoid(
        alpha[1] * a[..., n:2 * n] + bias[n:2 * n])
    res = jnp.exp(alpha[2] * a[..., 2 * n:] + bias[2 * n:])
    res = sinkhorn(res.reshape(res.shape[:-1] + (n, n)),
                   cfg.sinkhorn_iterations, cfg.hc_eps)
    return pre, post, res


def _streams(x, n: int):
    """``x [..., D]`` -> ``n`` streams that start as copies of it, ``[...,
    n·D]``."""
    return jnp.concatenate([x] * n, axis=-1)


def _summed(X, n: int):
    """``X [..., n·D]`` -> the streams' sum ``[..., D]``."""
    D = X.shape[-1] // n
    return sum(X[..., i * D:(i + 1) * D] for i in range(n))


def post_and_res(a, blk, which: int, cfg: SparseLatentConfig):
    """``a [T, n (n + 2)]`` (every token's ``x Phi``) -> ``(H_post [T, n],
    H_res [T, n, n])`` of sublayer ``which``, formed with the tokens last
    (``mixing``'s arithmetic)."""
    n = cfg.hc_mult
    alpha, bias = blk["hc_alpha"][which], blk["hc_bias"][which]
    a = a.T
    post = cfg.hc_magnitude * jax.nn.sigmoid(
        alpha[1] * a[n:2 * n] + bias[n:2 * n, None])
    res = jnp.exp(alpha[2] * a[2 * n:] + bias[2 * n:, None])
    res = sinkhorn(res.reshape((n, n) + a.shape[1:]), cfg.sinkhorn_iterations,
                   cfg.hc_eps, axes=(1, 0))
    return post.T, jnp.moveaxis(res, -1, 0)


def _sublayer(blk, X, which: int, ln: str, cfg: SparseLatentConfig, dt, fn,
              interpret: bool = False):
    """``X <- H_res X + H_post^T fn(RMSNorm(sum_i H_pre_i X_i))`` on the
    streams ``X [B,S,n·D]`` f32 (a token's streams one after another);
    ``fn`` takes the normed input in ``dt`` and returns ``(y [B,S,D] f32,
    extra)``.  The two kernels of ``ops/stream_mixing.py`` on a TPU backend
    (``interpret`` runs them in the pallas interpreter), the plain form
    elsewhere."""
    n = cfg.hc_mult
    B, S = X.shape[:2]
    if interpret or jax.default_backend() == "tpu":
        X = X.reshape(B * S, -1)
        with jax.named_scope("hc.pre"):
            h, a = sm.hc_pre(X, blk["hc_phi"][which], blk["hc_alpha"][which],
                             blk["hc_bias"][which], blk[ln], n=n,
                             hc_eps=cfg.hc_eps, eps=cfg.rms_norm_eps, dt=dt,
                             interpret=interpret)
            post, res = post_and_res(a, blk, which, cfg)
        y, extra = fn(h.reshape(B, S, -1))
        with jax.named_scope("hc.post"):
            X = sm.hc_post(X, y.reshape(B * S, -1), post, res,
                           interpret=interpret)
        return X.reshape(B, S, -1), extra
    X = X.reshape(B, S, n, -1)
    with jax.named_scope("hc.pre"):
        pre, post, res = mixing(blk, X, which, cfg)
        u = sum(pre[..., i, None] * X[:, :, i] for i in range(n))
        h = parts.rmsnorm(u, blk[ln], cfg.rms_norm_eps).astype(dt)
    y, extra = fn(h)
    with jax.named_scope("hc.post"):
        X = jnp.stack([sum(res[..., i, j, None] * X[:, :, j] for j in range(n))
                       + post[..., i, None] * y for i in range(n)], axis=2)
    return X.reshape(B, S, -1), extra


# ---------------------------------------------------------------------------
# The indexer and the choice of keys
# ---------------------------------------------------------------------------

def _rope_part(x, cos, sin, dr: int):
    """``x [..., S, Di]`` with its first ``dr`` rotated (half-split pairs)."""
    return jnp.concatenate([parts.rotate(x[..., :dr], cos, sin), x[..., dr:]],
                           axis=-1)


def sortable(x):
    """f32 -> uint32 that orders as the floats do."""
    b = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _kth_largest(u, k: int):
    """The ``k``-th largest of ``u [..., S]`` uint32 along the last axis,
    exactly: a bit at a time from the top, the largest key that at least
    ``k`` values reach."""
    def bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        reach = jnp.sum(u >= cand[..., None], axis=-1)
        return jnp.where(reach >= k, cand, t)

    return lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))


def choose(scores, rows, k: int):
    """``scores [B,Q,S]`` f32 of the queries at positions ``rows [Q]`` ->
    ``[B,Q,S]`` bool: the top ``min(t + 1, k)`` keys ``s <= t`` of each,
    ties to the lower position."""
    S = scores.shape[-1]
    causal = jnp.arange(S)[None, None, :] <= rows[None, :, None]
    u = sortable(jnp.where(causal, scores, -jnp.inf))
    t = _kth_largest(u, k)[..., None]
    above = u > t
    tie = u == t
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(tie.astype(jnp.int32), axis=-1) <= room
    return causal & (above | (tie & first))


def index_scores(iq, ik, w, cfg: SparseLatentConfig):
    """``iq [B,Q,Hi,Di]``, ``ik [B,S,Di]``, ``w [B,Q,Hi]`` f32 -> the
    index scores ``[B,Q,S]`` f32 (every key; the caller masks)."""
    dots = jnp.einsum("bqhd,bsd->bqhs", iq, ik,
                      preferred_element_type=jnp.float32)
    relu = jax.nn.relu(dots / math.sqrt(cfg.index_head_dim))
    return jnp.sum(relu * w[..., None], axis=2)


def _query_block(S: int) -> int:
    """Queries scored at a time: the largest divisor of ``S`` up to 256."""
    return max(d for d in range(1, min(256, S) + 1) if S % d == 0)


def indexer(blk, h, c_q, cfg: SparseLatentConfig, cos, sin):
    """``h [B,S,D]``, ``c_q [B,S,rq]`` -> ``(bits [B,S,W] int32, pairs
    chosen by batch row [B] int32, counted from the bits)``.  The scores are
    made and thresholded a block of queries at a time."""
    B, S = h.shape[:2]
    dr, k = cfg.qk_rope_head_dim, cfg.index_k
    with jax.named_scope("dsa.indexer"):
        iq = jnp.einsum("bsr,rhd->bshd", c_q, parts.w(blk, "w_qi"))
        iq = _rope_part(iq.swapaxes(1, 2), cos, sin, dr).swapaxes(1, 2)
        ik = jnp.dot(h, parts.w(blk, "w_ki"),
                     preferred_element_type=jnp.float32)
        mean = jnp.mean(ik, axis=-1, keepdims=True)
        var = jnp.mean((ik - mean) ** 2, axis=-1, keepdims=True)
        ik = ((ik - mean) * lax.rsqrt(var + cfg.index_norm_eps)
              * blk["ln_ki"].astype(jnp.float32)
              + blk["ln_ki_bias"].astype(jnp.float32))
        ik = _rope_part(ik, cos, sin, dr).astype(iq.dtype)
        w = jnp.dot(h, blk["w_wi"], preferred_element_type=jnp.float32) \
            / math.sqrt(cfg.index_n_heads)
    Q = _query_block(S)

    def one(i):
        lo = i * Q
        with jax.named_scope("dsa.indexer"):
            scores = index_scores(lax.dynamic_slice_in_dim(iq, lo, Q, 1), ik,
                                  lax.dynamic_slice_in_dim(w, lo, Q, 1), cfg)
        with jax.named_scope("dsa.select"):
            return sa.pack(choose(scores, lo + jnp.arange(Q), k))

    bits = lax.map(one, jnp.arange(S // Q))
    with jax.named_scope("dsa.select"):
        bits = bits.swapaxes(0, 1).reshape(B, S, -1)
        pairs = jnp.sum(lax.population_count(bits), axis=(1, 2),
                        dtype=jnp.int32)
    return bits, pairs


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def _attention(blk, h, cfg: SparseLatentConfig, cos, sin, chosen):
    """``h [B,S,D]`` normed -> ``(out [B,S,D] f32, (bits, pairs))``: the
    block's own indexer's choice where it has one (a full block), else
    ``chosen``, the full block's before it."""
    with jax.named_scope("mla"):
        q_nope, q_rope, c_kv, k_rope = lm.latents(blk, h, cfg, cos, sin)
    if "w_qi" in blk:
        c_q = parts.rmsnorm(jnp.dot(h, parts.w(blk, "w_qa")), blk["ln_q"],
                            cfg.rms_norm_eps)
        chosen = indexer(blk, h, c_q, cfg, cos, sin)
    with jax.named_scope("mla"):
        with jax.named_scope("kv_proj"):
            k_nope = jnp.einsum("bsc,chk->bhsk", c_kv, parts.w(blk, "w_kb"))
            v = jnp.einsum("bsc,chk->bhsk", c_kv, parts.w(blk, "w_vb"))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None], k_nope.shape[:3]
                                      + k_rope.shape[-1:])], axis=-1)
    with jax.named_scope("dsa.attend"):
        o = sa.sparse_attention(q, k, v, chosen[0], blk["sink"],
                                sm_scale=1.0 / math.sqrt(cfg.qk_head_dim))
    with jax.named_scope("gate"):
        g = jnp.einsum("bsd,dhk->bhsk", h, parts.w(blk, "w_g"))
        o = (jax.nn.sigmoid(g.astype(jnp.float32)) * o.astype(jnp.float32)
             ).astype(h.dtype)
    with jax.named_scope("out_proj"):
        out = jnp.einsum("bhsk,hkd->bsd", o, parts.w(blk, "w_o"),
                         preferred_element_type=jnp.float32)
    return out, chosen


def _ffn(blk, h, mlp: str, cfg: SparseLatentConfig):
    """``h [B,S,D]`` normed -> ``(y [B,S,D] f32, None for the dense FFN,
    else (rows routed to each held expert by batch row [B,E], each token's
    experts of all routed [B,S,k]))``."""
    limit = cfg.swiglu_limit
    if mlp == DENSE:
        with jax.named_scope("dense_ffn"):
            return parts.swiglu(h, parts.w(blk, "w_gate"),
                                parts.w(blk, "w_up"), parts.w(blk, "w_down"),
                                limit), None
    B, S, D = h.shape
    flat = h.reshape(B * S, D)
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            idx, weights = lm.route(blk, flat, cfg)
        y, rows = lm.held_experts(blk, flat, idx, weights, cfg, batch=B,
                                  limit=limit)
        with jax.named_scope("shared_expert"):
            y = y + parts.swiglu(flat, parts.w(blk, "ws_gate"),
                                 parts.w(blk, "ws_up"),
                                 parts.w(blk, "ws_down"), limit)
    return y.reshape(B, S, D), (rows, idx.reshape(B, S, -1))


def block(blk, X, kind, cfg: SparseLatentConfig, dt, cos, sin, chosen):
    """One block on the streams ``X [B,S,n·D]`` f32 -> ``(X, the choice it
    attended over, its expert layer's routing (``_ffn``) or None)``."""
    mlp, _ = kind
    X, chosen = _sublayer(
        blk, X, 0, "ln_attn", cfg, dt,
        lambda h: _attention(blk, h, cfg, cos, sin, chosen))
    X, rows = _sublayer(blk, X, 1, "ln_ffn", cfg, dt,
                        lambda h: _ffn(blk, h, mlp, cfg))
    return X, chosen, rows


def _head(params, x, ln, cfg: SparseLatentConfig):
    """``x [B,D]`` f32 -> logits ``[B,V]`` f32: the head in f32
    (``enable_lm_head_fp32``)."""
    with jax.named_scope("head"):
        h = parts.rmsnorm(x, ln, cfg.rms_norm_eps)
        return jnp.dot(h, params["head"].astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)


def forward(params, tokens, cfg: SparseLatentConfig):
    """``tokens [B,S]`` -> the served step's answer:

    * ``tokens [B,2]`` int32: the greedy next token, then the MTP module's
      greedy draft of the one after it;
    * ``logits [B,2,V]`` f32: the two rows that chose them;
    * ``chosen [B, full blocks, S, words(S)]`` int32: the bit planes
      (``ops/sparse_attention.pack``) each full block's indexer made, the
      layers' then the MTP module's: what its attention, and the shared
      blocks after it, read;
    * ``routes [B, expert blocks, S, k]`` int32: each token's experts (of
      all routed) in each expert block, the layers' then the MTP module's;
    * ``counters``: ``expert_rows [B, expert blocks, E]``, ``dsa_queries
      [B]`` (query rows x attention blocks), ``dsa_pairs [B]`` ((query,
      key) pairs attended, from the bits), ``index_reused [B]`` (query rows
      x shared blocks)."""
    B, S = tokens.shape
    n = cfg.hc_mult
    dt = params["embed"].dtype
    cos, sin = parts.rotary(cfg.qk_rope_head_dim, cfg.rope_theta,
                            jnp.arange(S))
    e = parts.embed(params, tokens, cfg).astype(jnp.float32)
    X = _streams(e, n)
    chosen, pairs, rows, routes, planes = None, [], [], [], []
    for (kind, layers), stack in zip(groups(cfg), params["groups"]):
        def one(carry, blk, kind=kind):
            X, chosen = carry
            X, chosen, routed = block(blk, X, kind, cfg, dt, cos, sin, chosen)
            made = chosen[0] if kind[1] == FULL else None
            return (X, chosen), (routed, chosen[1], made)

        if kind[1] == FULL:   # a placeholder the first block replaces
            chosen = (jnp.zeros((B, S, sa.words(S)), jnp.int32),
                      jnp.zeros((B,), jnp.int32))
        (X, chosen), (routed, counted, made) = lax.scan(
            one, (X, chosen), stack)
        pairs.append(jnp.sum(counted, axis=0))
        if routed is not None:
            rows.append(routed[0].swapaxes(0, 1))
            routes.append(routed[1].swapaxes(0, 1))
        if made is not None:
            planes.append(made.swapaxes(0, 1))
    h = _summed(X, n)
    logits = _head(params, h[:, -1], params["final_ln"], cfg)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out_logits, out_tokens = [logits], [token]
    blocks_run = cfg.num_hidden_layers
    if params["mtp"] is not None:
        mtp = params["mtp"]
        with jax.named_scope("mtp"):
            nxt = jnp.concatenate([tokens[:, 1:], token[:, None]], axis=1)
            e = parts.embed(params, nxt, cfg).astype(jnp.float32)
            m = jnp.concatenate(
                [parts.rmsnorm(e, mtp["ln_e"], cfg.rms_norm_eps),
                 parts.rmsnorm(h, mtp["ln_h"], cfg.rms_norm_eps)], axis=-1)
            # two-dimensional, so that the product comes out laid out as
            # the streams are (a [B,S,D] one came out turned, and the
            # streams made from it took a copy)
            m = jnp.dot(m.reshape(B * S, -1).astype(dt),
                        parts.w(mtp, "eh_proj"),
                        preferred_element_type=jnp.float32)
            X, chosen, routed = block(mtp, _streams(m, n).reshape(B, S, -1),
                                      (SPARSE, FULL), cfg, dt, cos, sin, None)
            draft = _head(params, _summed(X[:, -1], n), mtp["ln_out"], cfg)
        pairs.append(chosen[1])
        rows.append(routed[0][:, None])
        routes.append(routed[1][:, None])
        planes.append(chosen[0][:, None])
        out_logits.append(draft)
        out_tokens.append(jnp.argmax(draft, axis=-1).astype(jnp.int32))
        blocks_run += 1
    shared = sum(kind[1] == SHARED for kind in cfg.layer_kinds)
    full = jnp.full((B,), S, jnp.int32)
    return {
        "tokens": jnp.stack(out_tokens, axis=1),
        "logits": jnp.stack(out_logits, axis=1),
        "chosen": jnp.concatenate(planes, axis=1),
        "routes": jnp.concatenate(routes, axis=1),
        "counters": {
            "expert_rows": jnp.concatenate(rows, axis=1),
            "dsa_queries": full * blocks_run,
            "dsa_pairs": sum(pairs),
            "index_reused": full * shared,
        },
    }


# ---------------------------------------------------------------------------
# What a request needs
# ---------------------------------------------------------------------------

def selected_pairs(cfg: SparseLatentConfig) -> int:
    """(query, key) pairs a block attends over for one prompt."""
    k = cfg.index_k
    return sum(min(t + 1, k) for t in range(cfg.seq_len))


def flops_per_inference(cfg: SparseLatentConfig) -> float:
    """FLOPs one prompt of ``seq_len`` tokens needs: every matrix a token
    passes through (the held experts at even routing's expectation, the
    mixing, the MTP module's), the chosen pairs' scores and P·v, the full
    indexers' scores over the causal pairs, the two heads at the last
    position.  No padding, norms, rotary or selection."""
    D, H, S = cfg.hidden_size, cfg.num_attention_heads, cfg.seq_len
    n = cfg.hc_mult
    part = lm.layer_matmul_params(cfg)
    attention = part["mla"] + D * H * cfg.v_head_dim + 2 * n * D * n * (n + 2)
    index = cfg.q_lora_rank * cfg.index_n_heads * cfg.index_head_dim \
        + D * (cfg.index_head_dim + cfg.index_n_heads)
    per_token = 0
    for mlp, indexer_type in cfg.layer_kinds:
        per_token += attention + (index if indexer_type == FULL else 0)
        per_token += part["dense_ffn"] if mlp == DENSE else (
            part["router"] + part["shared_expert"] + part["held_experts"])
    per_token += cfg.num_nextn_predict_layers * 2 * D * D
    full = cfg.full_blocks
    attend = cfg.n_blocks * selected_pairs(cfg) * 2.0 * H * (
        cfg.qk_head_dim + cfg.v_head_dim)
    score = full * S * (S + 1) / 2 * 2.0 * cfg.index_n_heads \
        * cfg.index_head_dim
    heads = (1 + cfg.num_nextn_predict_layers) * 2.0 * D * cfg.vocab_size
    return 2.0 * S * per_token + attend + score + heads
