"""Plain float32 reference of what the ``bert_large`` configuration serves.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, one sequence
batch at a time, with no kernels, batching or sharding, and nothing imported
from the program.  It follows the configuration file: the widths are
BERT-large's; the block is the one the file's ``departures`` describe
(pre-norm RMSNorm, rotary positions on q and k, bidirectional softmax
attention, a bias-free two-matrix SiLU FFN, a final RMSNorm and a span head
of ``head_cols`` columns).  The weights are made here from the seed the
configuration states, by the same draws the configuration describes:
sixteen keys split from ``PRNGKey(weights_seed)``, normal values scaled by
the fan-in (0.02 for the embedding and the head), norms at one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _sizes(cfg: dict):
    served = cfg["served"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            served["head_dim"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


def make_weights(cfg: dict) -> dict:
    """Every weight in float32, from ``served.weights_seed``."""
    D, H, K, F, L, V = _sizes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(cfg["served"]["weights_seed"]),
                            16)

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, jnp.float32) * scale

    return {
        "embed": normal(keys[0], (V, D), 0.02),
        "wq": normal(keys[1], (L, D, H, K), 1.0 / math.sqrt(D)),
        "wk": normal(keys[2], (L, D, H, K), 1.0 / math.sqrt(D)),
        "wv": normal(keys[3], (L, D, H, K), 1.0 / math.sqrt(D)),
        "wo": normal(keys[4], (L, H, K, D), 1.0 / math.sqrt(H * K)),
        "head": normal(keys[5], (D, V), 0.02)[:, :cfg["served"]["head_cols"]],
        "w1": normal(keys[7], (L, D, F), 1.0 / math.sqrt(D)),
        "w2": normal(keys[8], (L, F, D), 1.0 / math.sqrt(F)),
    }


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    # x: [B, H, S, K]; rotate the two halves of K by position
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, cfg):
    served = cfg["served"]
    eps, theta = served["norm_eps"], served["rope_theta"]
    h = _rmsnorm(x, eps)
    q = _rope(jnp.einsum("bsd,dhk->bhsk", h, w["wq"]), theta)
    k = _rope(jnp.einsum("bsd,dhk->bhsk", h, w["wk"]), theta)
    v = jnp.einsum("bsd,dhk->bhsk", h, w["wv"])
    scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / math.sqrt(q.shape[-1])
    attn = jnp.einsum("bhqs,bhsk->bhqk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bhsk,hkd->bsd", attn, w["wo"])
    h = _rmsnorm(x, eps)
    return x + jnp.einsum("bsf,fd->bsd",
                          jax.nn.silu(jnp.einsum("bsd,df->bsf", h, w["w1"])),
                          w["w2"])


class Reference:
    """The reference with its weights on the device; ``outputs`` runs the
    forward in blocks of rows so that it fits beside nothing else."""

    BLOCK_ROWS = 32

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.weights = make_weights(cfg)
        self._layer = jax.jit(lambda x, w: _layer(x, w, cfg))

    def _forward(self, tokens):
        cfg, weights = self.cfg, self.weights
        with jax.default_matmul_precision("highest"):
            ids = jnp.clip(tokens, 0, cfg["vocab_size"] - 1)
            x = jnp.take(weights["embed"], ids, axis=0)
            for layer in range(cfg["num_hidden_layers"]):
                x = self._layer(x, {k: weights[k][layer] for k in (
                    "wq", "wk", "wv", "wo", "w1", "w2")})
            h = _rmsnorm(x, cfg["served"]["norm_eps"])
            return jnp.einsum("bsd,dv->bsv", h, weights["head"])

    def outputs(self, inputs: dict) -> dict:
        """``{"INPUT_IDS": [N, S] int32}`` -> ``{"LOGITS": [N, S, head_cols]
        float32}``, as numpy arrays under the configuration's names."""
        import numpy as np

        served = self.cfg["served"]
        tokens = inputs[served["inputs"][0]["name"]]
        blocks = [np.asarray(self._forward(jnp.asarray(
            tokens[i:i + self.BLOCK_ROWS])))
            for i in range(0, len(tokens), self.BLOCK_ROWS)]
        return {served["outputs"][0]["name"]: np.concatenate(blocks, axis=0)}
