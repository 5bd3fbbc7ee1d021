"""Operations one prompt through the ``trinity_mini`` configuration needs, and
what the windowed attention kernel does in a call.

The yardstick's own count (the program keeps one in
``models/window_moe.flops_per_inference``; this one may not move with it).
Only what the algorithm requires is counted: every matrix a token passes
through (q, k, v, the gate and W_o in every layer, the dense SwiGLU of the
leading layers, the router, the shared expert and the ``num_experts_per_tok``
routed experts in the others), QK^T and P·v over the (query, key) pairs each
layer attends (``min(t + 1, sliding_window)`` keys a query in a sliding
layer, ``t + 1`` in a full one), and the head at the last position.
Padding, the embedding lookup, norms, rotary, the gate's sigmoid, the
softmax and the keys a kernel walks and throws away are not.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def kinds(cfg: dict) -> list:
    """The attention of each layer held."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def attended_pairs(cfg: dict, kind: str) -> int:
    """(query, key) pairs a layer of ``kind`` attends for one prompt."""
    S = cfg["served"]["seq_len"]
    if kind != SLIDING:
        return S * (S + 1) // 2
    m = min(S, cfg["sliding_window"])
    return m * (m + 1) // 2 + (S - m) * cfg["sliding_window"]


def layer_params(cfg: dict) -> dict:
    """Matrix elements a token passes through, by part of a layer."""
    D, H, Hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    expert = 3 * D * cfg["moe_intermediate_size"]
    return {"attention": D * (2 * H + 2 * Hkv) * dh + H * dh * D,
            "dense_ffn": 3 * D * cfg["intermediate_size"],
            "moe": D * cfg["num_experts"]
            + (cfg["num_experts_per_tok"] + cfg["num_shared_experts"])
            * expert}


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one prompt of ``served.seq_len`` tokens."""
    part = layer_params(cfg)
    dense = cfg["num_dense_layers"]
    per_token = (cfg["num_hidden_layers"] * part["attention"]
                 + dense * part["dense_ffn"]
                 + (cfg["num_hidden_layers"] - dense) * part["moe"])
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    attention = sum(2.0 * H * 2 * dh * attended_pairs(cfg, kind)
                    for kind in kinds(cfg))
    return (2.0 * per_token * cfg["served"]["seq_len"] + attention
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def kernel_work(cfg: dict, kernel: str) -> dict:
    """``{"flops", "bytes"}`` of one call of ``kernel`` for one prompt (a
    call on a batch of b prompts does b times this).

    ``window_attention``: ``ops/flash_attention.py``'s windowed form at q
    ``[H,S,dh]``, k and v ``[Hkv,S,dh]``: QK^T and P·v over the pairs a
    sliding layer attends, ``sum_t min(t + 1, sliding_window)`` a head,
    whatever the kernel walks; q, k, v read and the output written once in
    bfloat16."""
    H, Hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    S = cfg["served"]["seq_len"]
    if kernel == "window_attention":
        return {"flops": 2.0 * H * 2 * dh * attended_pairs(cfg, SLIDING),
                "bytes": 2.0 * S * dh * (2 * H + 2 * Hkv)}
    raise KeyError(f"no kernel {kernel!r} in this configuration")
