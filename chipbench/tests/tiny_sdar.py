"""The ``sdar_30b_a3b`` configuration at a size a CPU test can hold, every
ratio kept (two query heads a key/value head, fewer experts a token than
experts, a block of 4, an untied head), and the program's model built from
it."""

from __future__ import annotations

TINY_SDAR = {
    "head_dim": 16, "hidden_size": 64, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "vocab_size": 512, "n_routed_experts": 8,
    "assumed": {"generation": {
        "block_length": 4, "denoising_steps": 4, "mask_token_id": 500,
        "confidence_threshold": None}},
    "served": {
        "model": "sdar_30b_a3b",
        "factory": "chipbench.tests.tiny_sdar:make_tiny_sdar", "env": {},
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32"}],
        "outputs": [{"name": "TOKENS", "datatype": "INT32"},
                    {"name": "COMMIT_PASS", "datatype": "INT32"},
                    {"name": "LOGITS", "datatype": "FP32"},
                    {"name": "ROUTES", "datatype": "INT32"}],
        "requests": "token_ids", "seq_len": 16, "new_tokens": 12,
        "max_batch_size": 16, "batch_buckets": [8, 16], "weights_seed": 32,
    },
    "reference": "sdar_30b_a3b", "compare": "logit_rel_l2_replayed",
    "flops": "sdar_30b_a3b",
    "control": {"env": {"TRITON_TPU_QUANT": "int8"}},
    # at these widths on the CPU, over four sets of eight prompts, the
    # median row reads 0.0088-0.0100 in bfloat16 and 0.027-0.037 under the
    # int8 control; the worst 0.11-0.49 and 0.13-0.72 (with 8 experts and 2
    # a token a choice flips in most sets)
    "limits": {"logit_rel_l2_median": 0.017, "logit_rel_l2_worst": 0.9,
               "route_shortfall_worst": 0.5, "commit_inconsistent": 0,
               "logit_rel_l2": 0.9},
}


def program_config(cfg: dict):
    """The program's ``BlockDiffusionConfig`` for a configuration file."""
    from triton_client_tpu.models.block_diffusion import BlockDiffusionConfig

    return BlockDiffusionConfig.from_file(cfg)


def make_tiny_sdar():
    from triton_client_tpu.models import language

    return language.make_sdar_30b_a3b(program_config(TINY_SDAR))
