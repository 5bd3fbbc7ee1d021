"""The ``lfm2_8b_a1b`` configuration at a size a CPU test can hold, every
ratio kept (four query heads a key/value head, a dense FFN 3.5 times the
hidden size and experts 0.875 times, conv layers and attention layers three
to one after two dense conv layers, a tied head, a selection bias), with
both kinds of layer, two whole periods and 8 experts of which a token takes
2; and the program's model built from it, sound and with the faults a
hand-over of two kinds of state can have planted in it."""

from __future__ import annotations

_PERIOD = ["full_attention", "conv", "conv", "conv"]

TINY_LFM2 = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
    "intermediate_size": 224,
    # the pattern whole, as the published file has it: the first
    # ``num_hidden_layers`` entries are the layers held
    "layer_types": ["conv", "conv"] + _PERIOD * 2 + ["full_attention",
                                                     "conv"],
    "moe_intermediate_size": 56, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 10,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 256,
    "n_routed_experts": 8,
    "assumed": {"head_dim": 8, "router_eps": 1e-6},
    "served": {
        "model": "lfm2_8b_a1b",
        "factory": "chipbench.tests.tiny_lfm2:make_tiny_lfm2", "env": {},
        "inputs": [{"name": "INPUT_IDS", "datatype": "INT32"}],
        "outputs": [{"name": "TOKENS", "datatype": "INT32"},
                    {"name": "LOGITS", "datatype": "FP32"},
                    {"name": "ROUTES", "datatype": "INT32"}],
        "requests": "token_ids", "seq_len": 16, "new_tokens": 6,
        "max_batch_size": 16, "batch_buckets": [8, 16], "weights_seed": 40,
    },
    "reference": "lfm2_8b_a1b", "compare": "logit_rel_l2_greedy",
    "flops": "lfm2_8b_a1b",
    "control": {"env": {"TRITON_TPU_QUANT": "int8"}},
    # read at these widths on the CPU (``tests/test_hybrid_conv.py`` has the
    # readings beside the assertions that hold them)
    "limits": {"logit_rel_l2_median": 0.06, "logit_rel_l2_worst": 0.5,
               "route_shortfall_worst": 0.2, "token_inconsistent": 0,
               "logit_rel_l2": 0.25},
}


def program_config(cfg: dict):
    """The program's ``HybridConvConfig`` for a configuration file."""
    from triton_client_tpu.models.hybrid_conv import HybridConvConfig

    return HybridConvConfig.from_file(cfg)


def make_tiny_lfm2():
    from triton_client_tpu.models import language

    return language.make_lfm2_8b_a1b(program_config(TINY_LFM2))


def _make_with(planted):
    """The tiny model with ``hybrid_conv.prefill`` replaced by
    ``planted(the real one)`` while its generation is traced, and at no
    other time."""
    import jax

    from triton_client_tpu.models import hybrid_conv, language

    cfg = program_config(TINY_LFM2)
    state = {}

    def generate(params, tokens):
        kept = hybrid_conv.prefill
        hybrid_conv.prefill = planted(kept)
        try:
            return hybrid_conv.generate(params, tokens, cfg)
        finally:
            hybrid_conv.prefill = kept

    def fn(INPUT_IDS):
        if not state:
            state["params"] = hybrid_conv.init_params(cfg)
            state["run"] = jax.jit(generate)
        out = state["run"](state["params"], INPUT_IDS)
        return {"TOKENS": out["tokens"], "LOGITS": out["logits"],
                "ROUTES": out["routes"],
                **{language.DEVICE_COUNTER + key: array
                   for key, array in out["counters"].items()}}

    return language._counting_model(
        language.make_lfm2_8b_a1b(cfg).config, fn,
        cfg.seq_len + cfg.new_tokens - 1)


def handed_over(alter_conv=None, alter_cache=None):
    """``planted`` for :func:`_make_with`: the prefill's state altered on
    its way to the decode steps, a conv layer's rows ``[n,b,L-1,D]`` by
    ``alter_conv`` and an attention layer's keys or values
    ``[n,b,Hkv,positions,dh]`` by ``alter_cache``."""
    import jax

    def alter(leaf):
        wanted = alter_conv if leaf.ndim == 4 else alter_cache
        return leaf if wanted is None else wanted(leaf)

    def planted(prefill):
        def wrong(params, tokens, cfg):
            logits, state, rows, chose = prefill(params, tokens, cfg)
            return (logits, jax.tree_util.tree_map(alter, state), rows,
                    chose)
        return wrong

    return planted


def make_tiny_lfm2_conv_rows_swapped():
    """Every conv layer is handed its last two rows in the wrong order."""
    return _make_with(handed_over(alter_conv=lambda rows: rows[:, :, ::-1]))


def make_tiny_lfm2_conv_rows_from_the_start():
    """Every conv layer is handed nothing: the rows before position 0
    (zeros), as a prefill that forgot the state would."""
    return _make_with(handed_over(alter_conv=lambda rows: rows * 0))


def make_tiny_lfm2_cache_of_another_row():
    """Every attention layer is handed the keys and values of the batch's
    row before."""
    import jax.numpy as jnp

    return _make_with(handed_over(
        alter_cache=lambda cache: jnp.roll(cache, 1, axis=1)))
