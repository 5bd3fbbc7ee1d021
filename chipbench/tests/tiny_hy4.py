"""The ``hy4_preview`` configuration at a size a CPU test can hold, its
structure kept: a dense layer with a full indexer, an expert layer with one,
two expert layers that reuse its choice, the MTP module, four streams, a
``index_topk`` under the prompt's length so that the choice binds, fewer
experts held than routed over, a slice of the vocabulary; and the program's
model built from it."""

from __future__ import annotations

import copy
import os

from chipbench.files import load_json

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tiny() -> dict:
    cfg = copy.deepcopy(load_json(_ROOT, "chipbench", "configs",
                                  "hy4_preview.json"))
    cfg.update({
        "hidden_size": 128, "intermediate_size": 96,
        "moe_intermediate_size": 32,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
        "v_head_dim": 24, "index_n_heads": 8, "index_head_dim": 32,
        "index_topk": 48, "num_hidden_layers": 4, "n_routed_experts": 4,
        "num_experts_per_tok": 4, "vocab_size": 64, "swiglu_limit": 3,
    })
    cfg["deployment"] = dict(cfg["deployment"], first_expert=4, published={
        "num_hidden_layers": 78, "n_routed_experts": 16, "vocab_size": 512})
    cfg["served"] = dict(cfg["served"], factory=(
        "chipbench.tests.tiny_hy4:make_tiny_hy4"), seq_len=64)
    # at these widths, over eleven sets of eight prompts on the CPU, the
    # reference told the program's keys and experts, bfloat16 | the int8
    # control: median row 0.0099-0.0145 | 0.0364-0.0481, worst 0.0168-0.0304
    # | 0.0505-0.1104, index shortfall 0.049-0.102 | 0.219-0.491, route
    # shortfall 0.0040-0.0085 | 0.0129-0.0350
    cfg["limits"] = {"logit_rel_l2_median": 0.025, "logit_rel_l2_worst": 0.045,
                     "index_shortfall_worst": 0.16,
                     "route_shortfall_worst": 0.011, "token_inconsistent": 0}
    return cfg


TINY_HY4 = _tiny()


def program_config(cfg: dict):
    """The program's ``SparseLatentConfig`` for a configuration file."""
    from triton_client_tpu.models.sparse_latent import SparseLatentConfig

    return SparseLatentConfig.from_file(cfg)


def make_tiny_hy4():
    from triton_client_tpu.models import language

    return language.make_hy4_preview(program_config(TINY_HY4))
