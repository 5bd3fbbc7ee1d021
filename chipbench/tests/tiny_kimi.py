"""The ``kimi_k2`` configuration at a size a CPU test can hold, every ratio
kept (a dense layer first, fewer experts held than routed over, q·k wider
than v, a slice of the vocabulary), and the program's model built from it."""

from __future__ import annotations

TINY_KIMI = {
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3, "q_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 8, "type": "yarn"},
    "v_head_dim": 12, "vocab_size": 64,
    "deployment": {"first_expert": 4,
                   "published": {"num_hidden_layers": 61,
                                 "n_routed_experts": 16, "vocab_size": 512}},
    "served": {
        "model": "kimi_k2",
        "factory": "chipbench.tests.tiny_kimi:make_tiny_kimi", "env": {},
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32"}],
        "outputs": [{"name": "LOGITS", "datatype": "FP32"}],
        "requests": "token_ids", "seq_len": 24, "max_batch_size": 2,
        "batch_buckets": [1, 2], "weights_seed": 28,
    },
    "reference": "kimi_k2", "compare": "logit_rel_l2_by_request",
    "flops": "kimi_k2",
    "control": {"env": {"TRITON_TPU_QUANT": "int8"}},
    # at these widths, over eight sets of eight prompts on the CPU, the
    # median request reads 0.0145-0.0212 in bfloat16 and 0.033-0.066 under
    # the int8 control; the worst 0.02-0.36 (a held expert's choice flips)
    # and 0.06-0.85
    "limits": {"logit_rel_l2_median": 0.027, "logit_rel_l2_worst": 0.9,
               "logit_rel_l2": 0.5},
}


def program_config(cfg: dict):
    """The program's ``LatentMoEConfig`` for a configuration file."""
    from triton_client_tpu.models.latent_moe import LatentMoEConfig

    rope = cfg["rope_scaling"]
    return LatentMoEConfig(
        **{key: cfg[key] for key in (
            "hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_shared_experts",
            "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "vocab_size", "rms_norm_eps")},
        rope_theta=float(cfg["rope_theta"]),
        routed_experts_total=cfg["deployment"]["published"][
            "n_routed_experts"],
        first_expert=cfg["deployment"]["first_expert"],
        rope_factor=float(rope["factor"]),
        rope_original_max_position=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        seq_len=cfg["served"]["seq_len"],
        weights_seed=cfg["served"]["weights_seed"])


def make_tiny_kimi():
    from triton_client_tpu.models import language

    return language.make_kimi_k2(program_config(TINY_KIMI))
