"""Operations and bytes one request through the ``lfm2_8b_a1b`` configuration
needs.

The yardstick's own count (the program keeps one in
``models/hybrid_conv.flops_per_inference``; this one may not move with it).
Only what the algorithm requires is counted:

* every token of the prompt and of the answer but the last (which is never
  fed back) through its layer's operator (a conv layer's two matrices and
  its ``conv_L_cache`` taps, or an attention layer's four), through the
  dense SwiGLU of the leading layers, and through the router and its
  ``num_experts_per_tok`` experts in the others;
* the causal half of the prefill's scores and of P·v, and a decode step's
  against the keys so far, every head of the attention layers alone;
* the head (the embedding, transposed) for each of the ``new_tokens``.

Padding, the embedding lookup, norms, rotary, the softmaxes and the gates'
products are not counted.  A decode step is bound by the weights it reads,
so it has a byte count (``step_bytes``): the experts its batch **touched**
(from the device's counter, not all of them), every other matrix once, the
keys and values up to its position in the attention layers, and the
``conv_L_cache - 1`` rows of state of the conv layers, read and written.
"""

from __future__ import annotations

BYTES = 2  # bfloat16, weights and state alike
CONV, ATTENTION = "conv", "full_attention"


def kinds(cfg: dict) -> list:
    """The operator of each layer held."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def op_params(cfg: dict) -> dict:
    """Matrix elements of one operator, by kind."""
    D, H, Hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    dh = cfg["assumed"]["head_dim"]
    return {CONV: 4 * D * D + cfg["conv_L_cache"] * D,
            ATTENTION: D * (H + 2 * Hkv) * dh + H * dh * D}


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def n_expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def other_params(cfg: dict) -> int:
    """Every matrix a step reads whatever its rows chose: the operators,
    the dense FFNs, the routers, and the head."""
    part = op_params(cfg)
    return (sum(part[kind] for kind in kinds(cfg))
            + cfg["num_dense_layers"] * dense_ffn_params(cfg)
            + n_expert_layers(cfg) * cfg["hidden_size"] * cfg["num_experts"]
            + cfg["hidden_size"] * cfg["vocab_size"])


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one prompt of ``served.seq_len`` ids answered by
    ``served.new_tokens`` greedy tokens."""
    D, H, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["assumed"]["head_dim"])
    P, G = cfg["served"]["seq_len"], cfg["served"]["new_tokens"]
    per_token = (other_params(cfg) - D * cfg["vocab_size"]
                 + n_expert_layers(cfg) * cfg["num_experts_per_tok"]
                 * expert_params(cfg))
    pairs = P * (P + 1) // 2 + sum(P + i for i in range(1, G))
    return (2.0 * per_token * (P + G - 1)
            + kinds(cfg).count(ATTENTION) * H * 2 * 2.0 * dh * pairs
            + G * 2.0 * D * cfg["vocab_size"])


def cache_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position: every attention layer's."""
    return BYTES * kinds(cfg).count(ATTENTION) * 2 \
        * cfg["num_key_value_heads"] * cfg["assumed"]["head_dim"]


def conv_state_bytes(cfg: dict) -> int:
    """The rows of ``z`` one sequence keeps: every conv layer's."""
    return BYTES * kinds(cfg).count(CONV) * (cfg["conv_L_cache"] - 1) \
        * cfg["hidden_size"]


def prefill_bytes(cfg: dict, sequences: float) -> float:
    """Bytes a prefill of ``sequences`` prompts must move: every matrix
    once (all experts: thousands of tokens leave none out), the prompts'
    embedding rows, and both kinds of state, written."""
    D, P = cfg["hidden_size"], cfg["served"]["seq_len"]
    weights = other_params(cfg) + n_expert_layers(cfg) \
        * cfg["num_experts"] * expert_params(cfg)
    return (BYTES * (weights + sequences * P * D)
            + sequences * (P * cache_bytes_per_position(cfg)
                           + conv_state_bytes(cfg)))


def step_bytes(cfg: dict, batch: float, touched: float,
               context: float) -> float:
    """Bytes one decode step of ``batch`` sequences must move: the weights
    of the ``touched`` experts (summed over the layers; each read once
    however many rows chose it), every other matrix, the keys and values of
    ``context`` positions a sequence and the position it writes, and the
    conv layers' rows of state, read and written."""
    return (BYTES * (touched * expert_params(cfg) + other_params(cfg)
                     + batch * cfg["hidden_size"])
            + batch * ((context + 1) * cache_bytes_per_position(cfg)
                       + 2 * conv_state_bytes(cfg)))


def generation_bytes(cfg: dict, batch: float, touched: float) -> float:
    """One execution of ``batch`` sequences: the prefill and its
    ``new_tokens - 1`` decode steps, step ``i`` reading ``seq_len + i - 1``
    cached positions; ``touched`` is the experts the decode steps touched,
    summed over steps and layers."""
    P, G = cfg["served"]["seq_len"], cfg["served"]["new_tokens"]
    return (prefill_bytes(cfg, batch)
            + sum(step_bytes(cfg, batch, 0.0, P + i - 1)
                  for i in range(1, G))
            + BYTES * touched * expert_params(cfg))
