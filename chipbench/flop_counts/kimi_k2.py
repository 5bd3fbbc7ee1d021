"""Operations one prompt through the ``kimi_k2`` configuration needs, and
what each kernel the configuration brought does in a call.

The yardstick's own count (the program keeps one in
``models/latent_moe.flops_per_inference``; this one may not move with it).
Only what the algorithm requires is counted: every matrix a token passes
through (the latent-attention projections, the leading layers' SwiGLU, the
router, the shared expert), the causal half of the scores and of P·v, the
(token, expert) pairs on held experts at the 8 x 12/384 = 0.25 a token that
even routing gives, and the head at the last position.  Padding, the
embedding lookup, norms and rotary are not.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return D, H, dqk, cfg["v_head_dim"], cfg["served"]["seq_len"]


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one prompt of ``served.seq_len`` tokens."""
    D, H, dqk, dv, S = _sizes(cfg)
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mla = (D * rq + rq * H * dqk + D * (rkv + cfg["qk_rope_head_dim"])
           + rkv * H * (cfg["qk_nope_head_dim"] + dv) + H * dv * D)
    dense = 3 * D * cfg["intermediate_size"]
    expert = 3 * D * cfg["moe_intermediate_size"]
    total = cfg["deployment"]["published"]["n_routed_experts"]
    pairs_a_token = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                     / total)
    layers = cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    per_token = 2.0 * (layers * mla + n_dense * dense + (layers - n_dense) * (
        D * total + expert * cfg["n_shared_experts"]
        + expert * pairs_a_token))
    # a query at position t sees t + 1 keys
    attention = 2.0 * layers * H * (dqk + dv) * S * (S + 1) / 2
    head = 2.0 * D * cfg["vocab_size"]
    return S * per_token + attention + head


def kernel_work(cfg: dict, kernel: str) -> dict:
    """``{"flops", "bytes"}`` of one call of ``kernel`` for one prompt (a
    call on a batch of b prompts does b times this).

    ``mla_attention``: ``ops/flash_attention.py``'s looped form at q, k
    ``[H,S,192]``, v ``[H,S,128]``, causal: the causal half of QK^T and of
    P·v; q, k, v read and the output written once, in bfloat16."""
    D, H, dqk, dv, S = _sizes(cfg)
    if kernel == "mla_attention":
        return {"flops": 2.0 * H * (dqk + dv) * S * (S + 1) / 2,
                "bytes": 2.0 * H * S * (2 * dqk + 2 * dv)}
    raise KeyError(f"no kernel {kernel!r} in this configuration")
