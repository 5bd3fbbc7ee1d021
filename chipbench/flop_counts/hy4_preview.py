"""Operations one prompt through the ``hy4_preview`` configuration needs, and
what each kernel the configuration brought does in a call.

The yardstick's own count (the program keeps one in
``models/sparse_latent.flops_per_inference``; this one may not move with
it).  Only what the algorithm requires is counted: every matrix a token
passes through (the latent-attention projections with the gate, the full
indexers' projections, the streams' mixing coefficients, the leading
layer's SwiGLU, the routers, the shared experts, the MTP module's input
projection), the (token, expert) pairs on held experts at the 8 x 8/256 =
0.25 a token that even routing gives, the attention's scores and P·v over
the **chosen** pairs alone (``min(t + 1, index_topk)`` keys a query), the
full indexers' scores over the causal pairs, and the two heads at the last
position.  Padding, the embedding lookup, norms, rotary, the mixing's
elementwise sums, the selection and the keys the masked kernel walks and
throws away are not.
"""

from __future__ import annotations


def _blocks(cfg: dict):
    """``(mlp, indexer)`` of each block: the layers held, then the MTP."""
    L = cfg["num_hidden_layers"]
    kinds = list(zip(cfg["mlp_layer_types"][:L], cfg["indexer_types"][:L]))
    return kinds + [("sparse", "full")] * cfg["num_nextn_predict_layers"]


def chosen_pairs(cfg: dict) -> int:
    """(query, key) pairs an attention block reads for one prompt."""
    S, k = cfg["served"]["seq_len"], cfg["index_topk"]
    # a query at position t reads min(t + 1, k) keys
    m = min(S, k)
    return m * (m + 1) // 2 + (S - m) * k


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one prompt of ``served.seq_len`` tokens."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    S, n = cfg["served"]["seq_len"], cfg["hc_mult"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    attention = (D * rq + rq * H * (dn + dr) + D * (rkv + dr)
                 + rkv * H * (dn + dv) + H * dv * D      # W_o
                 + D * H * dv                            # the gate
                 + 2 * n * D * n * (n + 2))              # Phi, two sublayers
    indexer = rq * Hi * Di + D * Di + D * Hi
    expert = 3 * D * cfg["moe_intermediate_size"]
    total = cfg["deployment"]["published"]["n_routed_experts"]
    pairs_a_token = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                     / total)
    per_token = 0.0
    for mlp, index in _blocks(cfg):
        per_token += attention + (indexer if index == "full" else 0)
        per_token += (3 * D * cfg["intermediate_size"] if mlp == "dense" else
                      D * total + expert * cfg["n_shared_experts"]
                      + expert * pairs_a_token)
    per_token += cfg["num_nextn_predict_layers"] * 2 * D * D
    blocks = _blocks(cfg)
    attend = len(blocks) * kernel_work(cfg, "dsa_attention")["flops"]
    full = sum(index == "full" for _, index in blocks)
    score = full * S * (S + 1) / 2 * 2.0 * Hi * Di
    heads = (1 + cfg["num_nextn_predict_layers"]) * 2.0 * D * cfg["vocab_size"]
    return 2.0 * S * per_token + attend + score + heads


def kernel_work(cfg: dict, kernel: str) -> dict:
    """``{"flops", "bytes"}`` of one call of ``kernel`` for one prompt (a
    call on a batch of b prompts does b times this).

    ``dsa_attention``: ``ops/sparse_attention.py``'s kernel at q, k
    ``[H,S,256]``, v ``[H,S,256]``: QK^T and P·v over the **chosen** pairs
    alone, ``sum_t min(t + 1, index_topk)`` a head, whatever the kernel
    walks; q, k, v read and the output written once in bfloat16, the bit
    planes once."""
    H, S = cfg["num_attention_heads"], cfg["served"]["seq_len"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    if kernel == "dsa_attention":
        return {"flops": 2.0 * H * (dqk + dv) * chosen_pairs(cfg),
                "bytes": 2.0 * H * S * (2 * dqk + 2 * dv) + S * S / 8}
    raise KeyError(f"no kernel {kernel!r} in this configuration")
