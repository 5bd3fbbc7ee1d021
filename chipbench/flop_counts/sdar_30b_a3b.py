"""Operations and bytes one request through the ``sdar_30b_a3b``
configuration needs.

The yardstick's own count (the program keeps one in
``models/block_diffusion.flops_per_inference``; this one may not move with
it).  Only what the algorithm requires is counted:

* the prompt through every matrix a token passes (the attention
  projections, the router, its 8 experts) with the block-causal part of the
  scores and of P·v (a row sees the keys up to the end of its block), no
  head;
* then ``denoising_steps`` passes and one commit pass a block, each the
  block's rows through every layer against the keys so far; the
  ``denoising_steps`` of them through the head, over the whole vocabulary.

Padding, the embedding lookup, norms, rotary and the softmaxes are not
counted.  A pass is bound by the weights it reads, so it has a byte count
too (``pass_bytes``), and the prefill reads every weight once
(``prefill_bytes``).
"""

from __future__ import annotations

BYTES = 2  # bfloat16, weights and cache alike


def _sizes(cfg: dict):
    gen = cfg["assumed"]["generation"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["num_hidden_layers"], gen["block_length"],
            gen["denoising_steps"], cfg["served"]["seq_len"],
            cfg["served"]["new_tokens"])


def _attention_params(cfg: dict) -> int:
    D, H, Hkv, dh = _sizes(cfg)[:4]
    return D * (H + 2 * Hkv) * dh + H * dh * D


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def flops_per_inference(cfg: dict) -> float:
    """FLOPs of one prompt of ``served.seq_len`` ids answered by
    ``served.new_tokens`` tokens under the static rule."""
    D, H, _, dh, L, B, T, P, G = _sizes(cfg)
    per_token = 2.0 * L * (
        _attention_params(cfg) + D * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * _expert_params(cfg))

    def scores(rows, keys):  # q.k and p.v, every head of every layer
        return 2.0 * L * H * 2 * dh * rows * keys

    total = P * per_token + sum(scores(B, end) for end in range(B, P + 1, B))
    head = 2.0 * D * cfg["vocab_size"]
    for n in range(G // B):
        keys = P + (n + 1) * B
        total += (T + 1) * (B * per_token + scores(B, keys)) + T * B * head
    return total


def expert_bytes(cfg: dict) -> int:
    """One expert's three matrices."""
    return BYTES * _expert_params(cfg)


def prefill_bytes(cfg: dict, sequences: float) -> float:
    """Bytes a prefill of ``sequences`` prompts must move: every layer's
    weights once (all experts: 16,384 tokens leave none out), the prompts'
    embedding rows, and the cache it writes."""
    D, _, Hkv, dh, L, _, _, P, _ = _sizes(cfg)
    weights = L * (_attention_params(cfg) + D * cfg["num_experts"]
                   + cfg["num_experts"] * _expert_params(cfg))
    cache = sequences * L * 2 * Hkv * P * dh
    return BYTES * (weights + sequences * P * D + cache)


def pass_bytes(cfg: dict, sequences: float, touched: float, context: float,
               head: bool = True) -> float:
    """Bytes one pass of ``sequences`` blocks must move: the weights of the
    ``touched`` experts (summed over the layers; each read once however many
    rows chose it), every layer's attention and router weights, the head's
    (a commit pass has none), and the keys and values of ``context``
    positions a sequence."""
    D, _, Hkv, dh, L = _sizes(cfg)[:5]
    weights = (touched * _expert_params(cfg)
               + L * (_attention_params(cfg) + D * cfg["num_experts"])
               + (D * cfg["vocab_size"] if head else 0))
    cache = sequences * L * 2 * Hkv * context * dh
    return BYTES * (weights + cache)
