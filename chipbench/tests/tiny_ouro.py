"""The ``ouro_2_6b`` configuration at a size a CPU test can hold, every
ratio kept (as many key/value heads as query heads, a SwiGLU 2.75 times the
hidden size, an untied head, more than one loop step), and the program's
model built from it, sound and with the two faults a looped stack can have
planted in it."""

from __future__ import annotations

TINY_OURO = {
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 176,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "total_ut_steps": 3, "early_exit_threshold": 1, "vocab_size": 256,
    "served": {
        "model": "ouro_2_6b",
        "factory": "chipbench.tests.tiny_ouro:make_tiny_ouro", "env": {},
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32"}],
        "outputs": [{"name": "TOKENS", "datatype": "INT32"},
                    {"name": "LOGITS", "datatype": "FP32"},
                    {"name": "EXIT_PDF", "datatype": "FP32"}],
        "requests": "token_ids", "seq_len": 16, "new_tokens": 6,
        "max_batch_size": 16, "batch_buckets": [8, 16], "weights_seed": 34,
    },
    "reference": "ouro_2_6b", "compare": "logit_rel_l2_forced",
    "flops": "ouro_2_6b",
    "control": {"env": {"TRITON_TPU_QUANT": "int8"}},
    # at these widths on the CPU, over four sets of eight prompts, the
    # median row reads 0.0067-0.0081 in bfloat16 and 0.031-0.035 under the
    # int8 control; the worst 0.012-0.017 and 0.055-0.067; the exit
    # probabilities 0.0004-0.0006 and 0.0017-0.0023 (six layer-steps: the
    # published model's 192 read twenty times as much, ``PERF.md`` §2)
    "limits": {"logit_rel_l2_median": 0.016, "logit_rel_l2_worst": 0.5,
               "exit_pdf_abs_worst": 0.01, "token_inconsistent": 0,
               "logit_rel_l2": 0.25},
}


def program_config(cfg: dict):
    """The program's ``LoopedConfig`` for a configuration file."""
    from triton_client_tpu.models.looped import LoopedConfig

    return LoopedConfig.from_file(cfg)


def make_tiny_ouro():
    from triton_client_tpu.models import language

    return language.make_ouro_2_6b(program_config(TINY_OURO))


def _make_with(name: str, planted):
    """The tiny model with ``looped.<name>`` replaced by ``planted(the real
    one)`` while its generation is traced, and at no other time."""
    import jax

    from triton_client_tpu.models import language
    from triton_client_tpu.models import looped

    cfg = program_config(TINY_OURO)
    state = {}

    def generate(params, tokens):
        kept = getattr(looped, name)
        setattr(looped, name, planted(kept))
        try:
            return looped.generate(params, tokens, cfg)
        finally:
            setattr(looped, name, kept)

    def fn(INPUT_IDS):
        if not state:
            state["params"] = looped.init_params(cfg)
            state["run"] = jax.jit(generate)
        out = state["run"](state["params"], INPUT_IDS)
        return {"TOKENS": out["tokens"], "LOGITS": out["logits"],
                "EXIT_PDF": out["exit_pdf"],
                **{language.DEVICE_COUNTER + key: array
                   for key, array in out["counters"].items()}}

    return language._counting_model(
        language.make_ouro_2_6b(cfg).config, fn,
        cfg.seq_len + cfg.new_tokens - 1)


def make_tiny_ouro_stale_cache():
    """Every decode step finds the cache turned by one loop step: step
    ``t`` attends to what step ``t - 1`` wrote."""
    import jax.numpy as jnp

    def planted(decode_step):
        def stale(params, cache, token, pos, cfg):
            cache = tuple(jnp.roll(c, 1, axis=0) for c in cache)
            return decode_step(params, cache, token, pos, cfg)
        return stale

    return _make_with("decode_step", planted)


def make_tiny_ouro_no_final_norm():
    """The final norm is left out between the loop steps (the gate still
    reads the normed state)."""
    def planted(close_step):
        def open_step(params, x, cfg):
            return x, close_step(params, x, cfg)[1]
        return open_step

    return _make_with("_close_step", planted)
