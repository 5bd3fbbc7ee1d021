"""Operations one forward pass of a dense transformer encoder needs.

The yardstick's own count (the program keeps one in
``models/language.forward_flops_per_token``; this one may not move with it).
Only what the algorithm requires is counted: the matrix multiplications of
every layer, the attention scores and values, and the head's executed
columns.  Padding rows, the embedding lookup, norms and activations are not.
"""

from __future__ import annotations


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one sequence of ``served.seq_len`` tokens."""
    served = cfg["served"]
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    heads = cfg["num_attention_heads"]
    head_dim = served["head_dim"]
    seq = served["seq_len"]
    per_layer_weights = 4 * d * heads * head_dim + 2 * d * cfg["intermediate_size"]
    matmul = 2.0 * (layers * per_layer_weights + d * served["head_cols"])
    attention = 4.0 * layers * heads * head_dim * seq  # QK^T and PV
    return seq * (matmul + attention)
