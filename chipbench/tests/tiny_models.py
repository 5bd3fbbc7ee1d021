"""Throwaway models for the benchmark's own tests: the program's shared
transformer stack at a size a CPU test can hold, sound and with a fault
planted where the answer is produced."""

from __future__ import annotations

TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 128, "vocab_size": 256,
    "served": {
        "model": "tiny_encoder", "factory": None, "env": {},
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32"}],
        "outputs": [{"name": "LOGITS", "datatype": "FP32"}],
        "requests": "token_ids",
        "seq_len": 16, "head_dim": 16, "head_cols": 2,
        "max_batch_size": 8, "batch_buckets": [1, 2, 4, 8],
        "compute_dtype": "bfloat16", "weights_seed": 24,
        "rope_theta": 10000.0, "norm_eps": 1e-6,
    },
    "reference": "bert_large", "compare": "logit_rel_l2",
    "flops": "transformer_encoder",
    "control": {"env": {"TRITON_TPU_QUANT": "int8"}},
    "limits": {"logit_rel_l2": 0.015},
}


def _make(alter=None):
    from triton_client_tpu.models import language
    from triton_client_tpu.models import transformer as tr
    from triton_client_tpu.server.model import JaxModel, make_config

    served = TINY["served"]
    tcfg = tr.TransformerConfig(
        vocab_size=TINY["vocab_size"], d_model=TINY["hidden_size"],
        n_layers=TINY["num_hidden_layers"],
        n_heads=TINY["num_attention_heads"], head_dim=served["head_dim"],
        d_ff=TINY["intermediate_size"], n_experts=0, causal=False)
    config = make_config(
        served["model"],
        inputs=[("INPUT_IDS", "INT32", [served["seq_len"]])],
        outputs=[("LOGITS", "FP32", [served["seq_len"], 2])],
        max_batch_size=served["max_batch_size"],
        preferred_batch_sizes=served["batch_buckets"],
        max_queue_delay_us=3000, instance_kind="KIND_TPU")
    run = language._LazyTransformer(
        tcfg, seed=served["weights_seed"], model_name=served["model"],
        head_cols=served["head_cols"])

    def fn(INPUT_IDS):
        import jax.numpy as jnp

        logits = run(jnp.clip(INPUT_IDS, 0, tcfg.vocab_size - 1))
        logits = logits.astype(jnp.float32)
        if alter is not None:
            logits = alter(logits)
        return {"LOGITS": logits}

    return JaxModel(config, fn, jit=False)


def make_tiny():
    return _make()


def make_tiny_altered():
    """One answer of every execution altered where it is produced."""
    return _make(lambda logits: logits.at[0].multiply(1.5))


def make_tiny_rows_swapped():
    """Row i of a coalesced batch goes back to another caller."""
    return _make(lambda logits: logits[::-1])
