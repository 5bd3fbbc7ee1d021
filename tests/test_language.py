"""The served drawn blocks of ``models/language.py`` (``_served_block``) and
the seam between the blocks (``models/parts.py``), at the benchmark's tiny
configurations on the CPU."""

import ast
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from chipbench.tests import (tiny_hy4, tiny_kimi, tiny_lfm2,  # noqa: E402
                             tiny_ouro, tiny_sdar)
from triton_client_tpu.models import language  # noqa: E402

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "triton_client_tpu", "models")

#: each served block's factory and its tiny configuration
TINY = {"kimi_k2": (tiny_kimi, "TINY_KIMI"),
        "sdar_30b_a3b": (tiny_sdar, "TINY_SDAR"),
        "ouro_2_6b": (tiny_ouro, "TINY_OURO"),
        "lfm2_8b_a1b": (tiny_lfm2, "TINY_LFM2"),
        "hy4_preview": (tiny_hy4, "TINY_HY4")}

#: the drawn blocks, which share ``parts`` and reach into no sibling
BLOCKS = ("latent_moe", "block_diffusion", "looped", "hybrid_conv",
          "sparse_latent")


@pytest.mark.parametrize("name", list(TINY))
def test_the_cost_analysis_reads_the_program_that_ran(name):
    """A signature's cost comes from the step's own jitted program, lowered
    where it was traced (``_LazyBlock.lower``), and is what tracing the
    callable afresh with its weights as arguments gives."""
    from triton_client_tpu.server.costs import analyze_jax_callable

    module, tiny = TINY[name]
    cfg = module.program_config(getattr(module, tiny))
    model = getattr(language, "make_" + name)(cfg)
    rows = min(8, model.config.max_batch_size)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len)).astype(np.int32)
    model.execute({"INPUT_IDS": ids}, {})
    cost = model.analyze_cost({"INPUT_IDS": ids}, {})
    fn = model._fn
    assert cost is not None and hasattr(fn, "lower")
    del fn.lower
    afresh = analyze_jax_callable(fn, INPUT_IDS=ids)
    assert (cost.flops, cost.bytes_accessed) == (afresh.flops,
                                                 afresh.bytes_accessed)
    assert cost.flops > 0


def _private_reads(source: str, own: str):
    """What ``source`` (the module ``own``) reads of another models
    module's underscore names: ``alias._name`` and ``from .m import
    _name``."""
    siblings = {f[:-3] for f in os.listdir(MODELS) if f.endswith(".py")}
    tree = ast.parse(source)
    aliases, found = {}, []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        package = node.level == 1 and node.module is None \
            or node.module == "triton_client_tpu.models"
        for alias in node.names:
            if package and alias.name in siblings:
                aliases[alias.asname or alias.name] = alias.name
            elif alias.name.startswith("_") and (
                    node.level == 1 and node.module in siblings
                    or (node.module or "").startswith(
                        "triton_client_tpu.models.")):
                found.append(f"from {node.module} import {alias.name}")
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and aliases.get(node.value.id, own) != own \
                and node.attr.startswith("_") \
                and not node.attr.endswith("__"):
            found.append(f"{node.value.id}.{node.attr}")
    return found


def test_no_block_reads_a_siblings_private_name():
    """A part two blocks use lives in ``parts.py`` (or is a public name of
    the block that owns it): a drawn block reaches into no other models
    module's underscore names."""
    planted = "from . import latent_moe as lm\nx = lm._w\n"
    assert _private_reads(planted, "looped") == ["lm._w"]
    assert _private_reads(planted, "latent_moe") == []
    found = {}
    for block in BLOCKS:
        with open(os.path.join(MODELS, block + ".py")) as f:
            reads = _private_reads(f.read(), block)
        if reads:
            found[block] = reads
    assert found == {}
