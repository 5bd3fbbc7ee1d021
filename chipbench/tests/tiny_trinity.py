"""The ``trinity_mini`` configuration at a size a CPU test can hold, its
ratios kept (eight query heads a key/value head in the published model, two
here; a dense FFN three times the hidden size, experts half of it and one
shared beside them; sliding layers three to one full, after a leading dense
sliding layer; an untied head; a selection bias), with a window of 8 over a
prompt of 40 and 8 experts of which a token takes 2; and the program's
model built from it."""

from __future__ import annotations

_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

TINY_TRINITY = {
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 192,
    # the pattern as the published file has it, longer than the layers
    # held: the first ``num_hidden_layers`` entries are the layers held
    "layer_types": ["sliding_attention"] + _PERIOD * 2,
    "moe_intermediate_size": 32, "mup_enabled": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "num_shared_experts": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 8,
    "vocab_size": 256, "n_routed_experts": 8,
    "assumed": {"router_eps": 1e-20},
    "served": {
        "model": "trinity_mini",
        "factory": "chipbench.tests.tiny_trinity:make_tiny_trinity",
        "env": {},
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32"}],
        "outputs": [{"name": "TOKENS", "datatype": "INT32"},
                    {"name": "LOGITS", "datatype": "FP32"},
                    {"name": "ROUTES", "datatype": "INT32"}],
        "requests": "token_ids", "seq_len": 40, "max_batch_size": 1,
        "batch_buckets": [1], "weights_seed": 47,
    },
    "reference": "trinity_mini", "compare": "logit_rel_l2_routed",
    "flops": "trinity_mini",
    "control": {"env": {"TRITON_TPU_QUANT": "int8"}},
    # read at these widths on the CPU (``tests/test_window_moe.py`` has the
    # readings beside the assertions that hold them)
    "limits": {"logit_rel_l2_median": 0.02, "logit_rel_l2_worst": 0.04,
               "route_shortfall_worst": 0.2, "token_inconsistent": 0},
}


def program_config(cfg: dict):
    """The program's ``WindowMoEConfig`` for a configuration file."""
    from triton_client_tpu.models.window_moe import WindowMoEConfig

    return WindowMoEConfig.from_file(cfg)


def make_tiny_trinity():
    from triton_client_tpu.models import language

    return language.make_trinity_mini(program_config(TINY_TRINITY))
