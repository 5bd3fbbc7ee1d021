"""Operations and bytes one request through the ``ouro_2_6b`` configuration
needs.

The yardstick's own count (the program keeps one in
``models/looped.flops_per_inference``; this one may not move with it).
Only what the algorithm requires is counted:

* every token of the prompt and of the answer but the last (which is never
  fed back) through the seven matrices of every layer, ``total_ut_steps``
  times;
* the causal half of the prefill's scores and of P·v, and a decode step's
  against the keys so far, every head of every layer at every loop step;
* the head over the whole vocabulary for each of the ``new_tokens``.

Padding, the embedding lookup, norms, rotary, the softmaxes and the exit
gate are not counted.  A decode step is bound by the weights it reads
``total_ut_steps`` times, so it has a byte count (``step_bytes``), and so
has the prefill (``prefill_bytes``).
"""

from __future__ import annotations

BYTES = 2  # bfloat16, weights and cache alike


def _sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["served"]["seq_len"], cfg["served"]["new_tokens"])


def layer_params(cfg: dict) -> int:
    """The seven matrices of one layer."""
    D, H, dh = _sizes(cfg)[:3]
    return 4 * D * H * dh + 3 * D * cfg["intermediate_size"]


def cache_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position: every layer at every loop step."""
    _, H, dh, L, T = _sizes(cfg)[:5]
    return BYTES * T * L * 2 * H * dh


def weight_bytes_a_pass(cfg: dict) -> int:
    """What the prefill and each decode step read of the weights: every
    layer's matrices once a loop step, and the head."""
    D, _, _, L, T = _sizes(cfg)[:5]
    return BYTES * (T * L * layer_params(cfg) + D * cfg["vocab_size"])


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one prompt of ``served.seq_len`` ids answered by
    ``served.new_tokens`` greedy tokens."""
    D, H, dh, L, T, P, G = _sizes(cfg)
    pairs = P * (P + 1) // 2 + sum(P + i for i in range(1, G))
    return (T * 2.0 * L * layer_params(cfg) * (P + G - 1)
            + T * L * H * 2 * 2.0 * dh * pairs
            + G * 2.0 * D * cfg["vocab_size"])


def prefill_bytes(cfg: dict, sequences: float) -> float:
    """Bytes a prefill of ``sequences`` prompts must move: every layer's
    weights once a loop step, the head once, the prompts' embedding rows,
    and the cache it writes."""
    D, P = cfg["hidden_size"], cfg["served"]["seq_len"]
    return (weight_bytes_a_pass(cfg) + BYTES * sequences * P * D
            + sequences * P * cache_bytes_per_position(cfg))


def step_bytes(cfg: dict, batch: float, context: float) -> float:
    """Bytes one decode step of ``batch`` sequences must move: every layer's
    weights once a loop step, the head, the keys and values of ``context``
    positions a sequence at every loop step, and the position it writes."""
    return (weight_bytes_a_pass(cfg)
            + batch * (context + 1) * cache_bytes_per_position(cfg))


def generation_bytes(cfg: dict, batch: float) -> float:
    """One execution of ``batch`` sequences: the prefill and its
    ``new_tokens - 1`` decode steps, step ``i`` reading ``seq_len + i``
    positions."""
    P, G = _sizes(cfg)[5:]
    return prefill_bytes(cfg, batch) + sum(
        step_bytes(cfg, batch, P + i) for i in range(1, G))
