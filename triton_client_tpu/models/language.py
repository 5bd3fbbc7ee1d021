"""Language-model zoo entries for BASELINE rows 4 and 5.

* ``bert_large`` — the BERT-large shape (24 layers, d_model 1024, 16 heads,
  d_ff 4096, ~340M params) served through the shared sharded-transformer
  stack (models/transformer.py) with a SQuAD-style [S,2] span head; dynamic
  batching per the reference's BERT perf config (BASELINE.md row 4; the
  reference drives this with perf_analyzer over async streaming gRPC +
  cudashm — here streaming gRPC + xla shm).
* ``llama_preprocess`` / ``llama_tpu`` / ``llama_postprocess`` +
  ``ensemble_llama`` — the Llama-architecture ensemble of BASELINE row 5
  (reference pattern: ensemble_image_client.py preprocess→model→postprocess,
  sequence/stream driven).  ``llama_tpu`` size is preset-selectable because
  the bench host has one v5e chip (Llama-3-8B bf16 weights alone are ~16GB
  = the whole HBM): ``TRITON_TPU_LLAMA_PRESET`` = ``tiny`` (CPU tests),
  ``1b`` (real-chip bench default), ``8b`` (full Llama-3-8B shape for
  multi-chip meshes — the 8-device dryrun path in __graft_entry__).

Tokenization is byte-level (every preset's vocab covers 0..255), so the
ensemble needs no external tokenizer assets.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

from ..server.costs import DEVICE_PEAKS, device_peaks
from ..server.model import EnsembleModel, JaxModel, PyModel, make_config
from . import transformer as tr

BERT_LARGE = tr.TransformerConfig(
    vocab_size=30522, d_model=1024, n_layers=24, n_heads=16,
    head_dim=64, d_ff=4096, n_experts=0,
    # encoder stack: bidirectional attention (BERT semantics); also halves
    # the wasted masked FLOPs the causal path spent at S=384
    causal=False,
)

# Llama-architecture presets (RMSNorm + RoPE + SiLU FFN — what the shared
# stack implements). "1b" fits one v5e chip with headroom; "8b" is the
# real Llama-3-8B shape (tr.LLAMA3_8B) for sharded meshes.
_LLAMA_PRESETS = {
    "tiny": tr.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=128, n_experts=0),
    # MoE variant: the decode/generate stacks serve mixture-of-experts
    # weights through the same KV cache (routed FFN in every step)
    "tiny-moe": tr.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=128, n_experts=4, moe_top_k=2),
    "1b": tr.TransformerConfig(
        vocab_size=128256, d_model=2048, n_layers=16, n_heads=16,
        head_dim=128, d_ff=8192, n_experts=0),
    "8b": tr.LLAMA3_8B,
}

BERT_SEQ_LEN = 384   # classic BERT-large SQuAD serving length
BERT_HEAD_COLS = 2   # span head (start/end logits) — see make_bert_large
LLAMA_SEQ_LEN = 128  # fixed context window for the generation ensemble

# Long-context scorer: attention dominates at this window, so serving runs
# through the pallas flash kernel (ops/flash_attention.py); the naive [S,S]
# fp32 score path would burn 64MB/head-batch of HBM per layer at 4096.
# Each preset carries its serving window so config and S can't drift.
_LONGCTX_PRESETS = {
    "tiny": (tr.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=128, n_experts=0), 512),
    "base": (tr.TransformerConfig(
        vocab_size=256, d_model=1024, n_layers=8, n_heads=16, head_dim=64,
        d_ff=4096, n_experts=0), 4096),
    # same model, doubled context: the naive [S,S] f32 score matrix would be
    # 256 MB per head-batch here — the flash kernel's tiling is what makes
    # the shape servable at all
    "xl": (tr.TransformerConfig(
        vocab_size=256, d_model=1024, n_layers=8, n_heads=16, head_dim=64,
        d_ff=4096, n_experts=0), 8192),
}


def _preset_platform() -> str:
    """The platform presets size themselves for.  Prefers the
    ``jax_platforms`` config value (``JAX_PLATFORMS``) — reading it does
    NOT initialize a backend — and only asks ``jax.default_backend()``
    (which does) when nothing pinned the platform."""
    import jax

    platforms = jax.config.jax_platforms
    if platforms:
        # ordered priority list (e.g. "tpu,cpu"): the FIRST entry wins
        return platforms.split(",")[0].strip()
    return jax.default_backend()


def _env_preset(var: str, presets, tpu_default: str, cpu_default: str) -> str:
    """Resolve a TRITON_TPU_*_PRESET env override, else pick by platform
    (:func:`_preset_platform`): the CPU gets the tiny preset the tests
    need, anything else the full width.  Unknown names fail loudly with
    the env var spelled out."""
    name = os.environ.get(var)
    if name is None:
        name = cpu_default if _preset_platform() == "cpu" else tpu_default
    if name not in presets:
        raise ValueError(
            f"{var}={name!r} is not a valid preset; choose one of "
            f"{sorted(presets)}")
    return name


def _longctx_preset() -> str:
    return _env_preset("TRITON_TPU_LONGCTX_PRESET", _LONGCTX_PRESETS,
                       tpu_default="base", cpu_default="tiny")


def longctx_cfg() -> tr.TransformerConfig:
    return _LONGCTX_PRESETS[_longctx_preset()][0]


def longctx_seq_len() -> int:
    return _LONGCTX_PRESETS[_longctx_preset()][1]


def n_params(cfg: tr.TransformerConfig) -> int:
    """Parameter count of a ``TransformerConfig`` with a dense FFN; wrong
    for any expert model, whose count depends on what a chip holds and what
    a token passes through (``latent_moe.layer_matmul_params``)."""
    per_layer = (
        4 * cfg.d_model * cfg.n_heads * cfg.head_dim  # wq wk wv wo
        + 2 * cfg.d_model                              # ln1 ln2
        + 2 * cfg.d_model * cfg.d_ff                   # w1 w2
    )
    embed = cfg.vocab_size * cfg.d_model
    head = cfg.d_model * cfg.vocab_size
    return cfg.n_layers * per_layer + embed + head + cfg.d_model


def forward_flops_per_token(cfg: tr.TransformerConfig, seq_len: int,
                            head_cols: int = None) -> float:
    """≈2·params matmul FLOPs per token + attention score/value terms, for
    a ``TransformerConfig`` with a dense FFN (the expert block counts its
    own: ``latent_moe.flops_per_inference``).

    ``head_cols`` must match the forward's (tr.make_forward): a model that
    projects only N head columns (bert_large's span head: 2, not 30522)
    must not count the full-vocab head it never executes — MFU numbers
    count executed FLOPs only."""
    matmul = 2.0 * (n_params(cfg) - cfg.vocab_size * cfg.d_model)  # embed lookup is free
    if head_cols is not None:
        # replace the full-vocab head term with the executed columns
        matmul += 2.0 * cfg.d_model * (head_cols - cfg.vocab_size)
    attn = 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * seq_len  # QK^T + PV (causal ≈ /2, keep upper bound)
    return matmul + attn


#: v5e bf16 peak (one chip), from the ``server.costs.DEVICE_PEAKS`` row
#: the live ``nv_tpu_live_mfu`` gauge divides by on that chip.
V5E_PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["bf16_flops"]


def serving_mfu(infer_per_sec: float, cfg: tr.TransformerConfig,
                seq_len: int, head_cols: int = None) -> float:
    """Model FLOPs utilization of a serving sweep: measured requests/sec ×
    seq_len tokens each × analytic forward FLOPs/token over the local
    device's bf16 peak (``server.costs.device_peaks`` — the table the
    live gauge uses, so the two cannot drift apart).  ``head_cols``
    follows the served forward (bert_large: 2 — the span head).  Raises
    on a device the table does not list: an offline MFU against another
    chip's peak is a wrong number, not a default."""
    peaks = device_peaks()
    if peaks is None:
        raise ValueError(
            "serving_mfu: the local device has no row in "
            "server.costs.DEVICE_PEAKS — no MFU can be stated for it")
    toks = infer_per_sec * seq_len
    return (toks * forward_flops_per_token(cfg, seq_len, head_cols)
            / peaks["bf16_flops"])


class _LazyTransformer:
    """Shared lazy init: mesh + params + jitted forward on first call.

    The mesh comes from ``TRITON_TPU_SERVE_MESH`` (tr.serve_mesh) — serving
    runs pjit-sharded over however many devices the deployment names, not
    pinned to one chip.  Batches are padded up to a multiple of the mesh's
    ``dp`` extent (the shard_map in_spec shards batch over dp) and sliced
    back after the forward; the dynamic batcher's preferred sizes keep the
    padded-shape set bounded so XLA compiles a handful of shapes."""

    def __init__(self, cfg: tr.TransformerConfig, seed: int,
                 model_name: str = None, head_cols: int = None):
        self.cfg = cfg
        self._seed = seed
        self._model_name = model_name
        self._head_cols = head_cols
        self._fwd = None
        self._params = None
        self._mesh = None
        self._dp = 1

    @property
    def mesh(self):
        self._ensure()
        return self._mesh

    def _ensure(self):
        import jax

        if self._fwd is None:
            self._mesh = tr.serve_mesh(self.cfg,
                                       model_name=self._model_name)
            params = tr.init_params(jax.random.PRNGKey(self._seed), self.cfg)
            # TRITON_TPU_QUANT[_<MODEL>]=int8: weight-only int8 storage +
            # dynamic activation quantization → the layer matmuls run on
            # the MXU's int8 path (2× bf16 peak on v5e); norms/embed/head
            # are not quantized (closeness proven in test_transformer.py)
            quant = tr.resolve_quant(self._model_name)
            if quant == "int8":
                params = tr.quantize_layer_weights(params, self.cfg)
            # held as the forward reads them (tr.serving_params), a leaf at
            # a time: each f32 leaf is let go before the next is cast, so
            # no f32 copy of the matrices stands beside the served one
            served = {}
            for k in list(params):
                served.update(
                    tr.serving_params({k: params.pop(k)}, self.cfg))
            self._params = tr.place_params(served, self._mesh, self.cfg)
            self._fwd = tr.make_forward(self._mesh, self.cfg,
                                        quantized=(quant == "int8"),
                                        head_cols=self._head_cols)
            self._dp = int(self._mesh.shape["dp"])

    def __call__(self, tokens):
        import jax.numpy as jnp

        self._ensure()
        b = tokens.shape[0]
        pad = -b % self._dp
        if pad:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((pad,) + tokens.shape[1:],
                                   tokens.dtype)], axis=0)
        out = self._fwd(self._params, tokens)
        return out[:b] if pad else out


def make_bert_large() -> JaxModel:
    """BASELINE row 4 model: INT32 input_ids [384] → FP32 span logits
    [384,2] (start/end), BERT-large-shaped stack, dynamic batching."""
    cfg = make_config(
        "bert_large",
        inputs=[("INPUT_IDS", "INT32", [BERT_SEQ_LEN])],
        outputs=[("LOGITS", "FP32", [BERT_SEQ_LEN, 2])],
        # deep batches are the MFU lever at S=384: 32×384 = 12288 tokens
        # per execution keeps the MXU fed (22% MFU measured at batch 8;
        # BASELINE row 4)
        max_batch_size=32,
        preferred_batch_sizes=[1, 2, 4, 8, 16, 32],
        max_queue_delay_us=3000,
        instance_kind="KIND_TPU",
        parameters={"flops_per_inference": str(
            BERT_SEQ_LEN * forward_flops_per_token(
                BERT_LARGE, BERT_SEQ_LEN, head_cols=BERT_HEAD_COLS))},
    )
    # span head: the forward projects ONLY the 2 start/end columns — a real
    # BERT-SQuAD head, not a sliced vocab projection.  BERT_HEAD_COLS feeds
    # the same value into the MFU accounting (serving_mfu) so the reported
    # efficiency counts executed FLOPs only.
    run = _LazyTransformer(BERT_LARGE, seed=24, model_name="bert_large",
                           head_cols=BERT_HEAD_COLS)

    def fn(INPUT_IDS):
        import jax.numpy as jnp

        tokens = jnp.clip(INPUT_IDS, 0, BERT_LARGE.vocab_size - 1)
        logits = run(tokens)  # [B, S, 2]
        return {"LOGITS": logits.astype(jnp.float32)}

    return JaxModel(cfg, fn, jit=False, analyzable=True)


def make_longctx_tpu() -> JaxModel:
    """Long-context document scorer: INT32 TOKENS [S] → FP32 LOGPROBS [S]
    (per-position logprob of the next provided token; last position 0).

    S is 4096 on TPU backends ("base" preset) — the long-context serving
    proof: attention dominates at this window and runs through the pallas
    flash kernel. Scoring (not generation) keeps it one forward per
    request, so it batches like bert_large rather than paying the
    per-token stream RTT of ensemble_llama."""
    S = longctx_seq_len()
    cfg = make_config(
        "longctx_tpu",
        inputs=[("TOKENS", "INT32", [S])],
        outputs=[("LOGPROBS", "FP32", [S])],
        max_batch_size=4,
        preferred_batch_sizes=[1, 2, 4],
        max_queue_delay_us=2000,
        instance_kind="KIND_TPU",
        parameters={"flops_per_inference": str(
            S * forward_flops_per_token(longctx_cfg(), S))},
    )
    run = _LazyTransformer(longctx_cfg(), seed=11, model_name="longctx_tpu")

    def fn(TOKENS):
        import jax
        import jax.numpy as jnp

        tokens = jnp.clip(TOKENS, 0, run.cfg.vocab_size - 1)
        logits = run(tokens)  # [B, S, vocab]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nxt = tokens[:, 1:]
        scores = jnp.take_along_axis(
            logp[:, :-1, :], nxt[..., None], axis=-1)[..., 0]
        return {"LOGPROBS": jnp.pad(scores, ((0, 0), (0, 1)))}

    return JaxModel(cfg, fn, jit=False, analyzable=True)


class _LazyBlock:
    """``_LazyTransformer``'s lazy first-request init for the blocks that
    hold their weights in bfloat16 (``module``: models/latent_moe.py,
    models/block_diffusion.py, models/looped.py, models/hybrid_conv.py,
    models/sparse_latent.py; ``_served_block`` serves them): mesh from
    ``tr.serve_mesh``, weights drawn on the device leaf by leaf by
    ``module.init_params``, one jitted ``step(params, tokens, cfg)``.
    Nothing is imported or allocated before the first call."""

    def __init__(self, cfg, model_name: str, module: str, step: str):
        self.cfg = cfg
        self._model_name = model_name
        self._module, self._step = module, step
        self._fwd = None
        self._params = None

    def _ensure(self):
        import importlib

        import jax

        if self._fwd is None:
            module = importlib.import_module(
                f"{__package__}.{self._module}")
            mesh = tr.serve_mesh(self.cfg, model_name=self._model_name)
            if mesh.size != 1:
                raise ValueError(
                    f"{self._model_name}: the block has no exchange between "
                    "chips yet (an expert layer computes the experts the "
                    "configuration says it holds); the serve mesh has "
                    f"{mesh.size} devices")
            quant = tr.resolve_quant(self._model_name)
            with jax.default_device(mesh.devices.flat[0]):
                self._params = module.init_params(
                    self.cfg, quantized=(quant == "int8"))
            cfg, step = self.cfg, getattr(module, self._step)
            self._fwd = jax.jit(
                lambda params, tokens: step(params, tokens, cfg))

    def __call__(self, tokens):
        self._ensure()
        return self._fwd(self._params, tokens)

    def lower(self, tokens):
        """The step as ``__call__`` runs it, lowered: after a call with
        these shapes the trace and the lowering are the jit's own, cached,
        and the compile is the compile cache's answer."""
        self._ensure()
        return self._fwd.lower(self._params, tokens)


#: prefix of the outputs in which a step carries what the device counted
#: on the way; the model's ``host_post`` takes them out of the answer for
#: ``ModelStats``' queue of device counters
DEVICE_COUNTER = "DEVICE_COUNTER."


def _counting_model(config, fn, tokens_per_row: int) -> JaxModel:
    """A ``JaxModel`` whose ``fn`` returns, beside its declared outputs,
    device counters under ``DEVICE_COUNTER + name`` (each with a leading
    axis of batch rows)."""

    def host_post(outputs, parameters):
        # the counts come from the device with the answer; a batched step's
        # parameters say how many of its rows are not padding
        counters = {name[len(DEVICE_COUNTER):]: outputs.pop(name)
                    for name in list(outputs)
                    if name.startswith(DEVICE_COUNTER)}
        rows = len(next(iter(counters.values())))
        model.stats.queue_device_counters(
            counters, parameters.get("real_batch", rows), tokens_per_row)
        return outputs

    model = JaxModel(config, fn, jit=False, host_post=host_post,
                     analyzable=True)
    return model


def _served_block(name: str, module: str, step: str, cfg, outputs,
                  tokens_per_row: int, counters="counters",
                  max_batch_size: int = 16) -> JaxModel:
    """A drawn block served as the model ``name``: INT32 INPUT_IDS
    [seq_len] -> ``outputs``, each ``(name, datatype, dims, key)``: ``key``
    of the step's answer (a dict's key, a tuple's index).  ``counters`` is
    where the answer holds the device counters (the key of their dict, or
    ``{counter: key}``); they go to ``ModelStats`` with ``tokens_per_row``,
    the tokens a row of the batch passes the expert layers with.  The
    batcher's buckets are ``max_batch_size`` and its half.  The step
    is ``module.step(params, tokens, cfg)``, one program a signature
    (``_LazyBlock``), and the cost analysis of a signature reads the program
    that ran it: it does not trace, lower and load the step a second time
    from ``fn`` (``costs.analyze_jax_callable``; 2.4-3.8 s a bucket at
    ``sdar_30b_a3b``)."""
    import importlib

    block = importlib.import_module(f"{__package__}.{module}")
    config = make_config(
        name,
        inputs=[("INPUT_IDS", "INT32", [cfg.seq_len])],
        outputs=[spec[:3] for spec in outputs],
        max_batch_size=max_batch_size,
        preferred_batch_sizes=[max_batch_size // 2, max_batch_size],
        max_queue_delay_us=2000,
        instance_kind="KIND_TPU",
        parameters={"flops_per_inference": str(
            block.flops_per_inference(cfg))},
    )
    run = _LazyBlock(cfg, name, module, step)

    def fn(INPUT_IDS):
        out = run(INPUT_IDS)
        counted = (out[counters] if isinstance(counters, str)
                   else {c: out[key] for c, key in counters.items()})
        return {**{spec[0]: out[spec[3]] for spec in outputs},
                **{DEVICE_COUNTER + c: array for c, array in counted.items()}}

    fn.lower = lambda INPUT_IDS: run.lower(INPUT_IDS)
    return _counting_model(config, fn, tokens_per_row)


def make_kimi_k2(cfg=None) -> JaxModel:
    """Kimi-K2-Instruct's block on one chip's share of an EP32 prefill pool
    (``latent_moe.KIMI_K2_EP32_SHARE``; a test passes a tiny ``cfg``):
    INT32 INPUT_IDS [S] → FP32 LOGITS [vocabulary slice] of the next token.
    One request is one prompt; a prefill pool answers it with one token's
    logits (and a cache handed on, which this model does not keep)."""
    if cfg is None:
        from .latent_moe import KIMI_K2_EP32_SHARE as cfg
    return _served_block(
        "kimi_k2", "latent_moe", "forward", cfg,
        [("LOGITS", "FP32", [cfg.vocab_size], 0)], cfg.seq_len,
        counters={"expert_rows": 1}, max_batch_size=2)


def make_sdar_30b_a3b(cfg=None) -> JaxModel:
    """SDAR-30B-A3B-Chat's block on one stage of an 8-stage pipeline
    (``block_diffusion.SDAR_30B_A3B_STAGE``; a test passes a tiny ``cfg``):
    INT32 INPUT_IDS [P] → INT32 TOKENS [G], INT32 COMMIT_PASS [G] (the
    pass, 0 .. ``denoising_steps - 1``, at which each position was
    committed), FP32 LOGITS [2, vocabulary] (the committed position's
    logits at block 0's pass 0 and at the last block's last pass) and INT32
    ROUTES [2, layers, experts a token] (the experts that position chose,
    for a reference that recomputes the row).  One request is one prompt,
    answered whole by ``G`` tokens generated by diffusion over blocks: a
    completion that does not stream."""
    if cfg is None:
        from .block_diffusion import SDAR_30B_A3B_STAGE as cfg
    G = cfg.new_tokens
    # every token of a request passes the expert layers once in the prefill
    # or once in each pass of its block and once more, final, riding the
    # next block's first pass, which the last block's tokens never do (the
    # published rule; a threshold that ends a block early makes this an
    # upper bound)
    return _served_block(
        "sdar_30b_a3b", "block_diffusion", "generate", cfg,
        [("TOKENS", "INT32", [G], "tokens"),
         ("COMMIT_PASS", "INT32", [G], "commit_pass"),
         ("LOGITS", "FP32", [2, cfg.vocab_size], "logits"),
         ("ROUTES", "INT32", [2, cfg.num_hidden_layers,
                              cfg.num_experts_per_tok], "routes")],
        cfg.seq_len + G * (cfg.denoising_steps + 1) - cfg.block_length)


def make_ouro_2_6b(cfg=None) -> JaxModel:
    """Ouro-2.6B's block, the whole model (``looped.OURO_2_6B``; a test
    passes a tiny ``cfg``): INT32 INPUT_IDS [P] → INT32 TOKENS [G] (greedy),
    FP32 LOGITS [2, vocabulary] (the logits that chose the first new token,
    from the prefill's last position, and those that chose the last, which
    have read every cached position of every loop step) and FP32 EXIT_PDF
    [2, loop steps] (the exit gate's probabilities at those two positions:
    at the published threshold of 1 the gate does not move the logits, so
    it is returned).  One request is one prompt, answered whole by ``G``
    tokens: a completion that does not stream."""
    if cfg is None:
        from .looped import OURO_2_6B as cfg
    G = cfg.new_tokens
    return _served_block(
        "ouro_2_6b", "looped", "generate", cfg,
        [("TOKENS", "INT32", [G], "tokens"),
         ("LOGITS", "FP32", [2, cfg.vocab_size], "logits"),
         ("EXIT_PDF", "FP32", [2, cfg.total_ut_steps], "exit_pdf")],
        cfg.seq_len + G - 1)


def make_lfm2_8b_a1b(cfg=None) -> JaxModel:
    """LFM2-8B-A1B's block on stage 0 of a 2-stage pipeline
    (``hybrid_conv.LFM2_8B_A1B_STAGE``; a test passes a tiny ``cfg``): INT32
    INPUT_IDS [P] → INT32 TOKENS [G] (greedy) and FP32 LOGITS [3,
    vocabulary] (the logits that chose the first new token, from the
    prefill's last position; the second, from the first decode step, which
    reads what the prefill handed over of both kinds of state; the last,
    which has read every cached key) and INT32 ROUTES [P + G - 1, expert
    layers, experts a token] (the experts every position of prompt and
    answer but the last chose, for a reference that recomputes the rows).
    One request is one prompt, answered whole by ``G`` tokens: a completion
    that does not stream."""
    if cfg is None:
        from .hybrid_conv import LFM2_8B_A1B_STAGE as cfg
    G = cfg.new_tokens
    # every token of prompt and answer but the last passes the expert layers
    return _served_block(
        "lfm2_8b_a1b", "hybrid_conv", "generate", cfg,
        [("TOKENS", "INT32", [G], "tokens"),
         ("LOGITS", "FP32", [3, cfg.vocab_size], "logits"),
         ("ROUTES", "INT32", [cfg.seq_len + G - 1, cfg.n_expert_layers,
                              cfg.num_experts_per_tok], "routes")],
        cfg.seq_len + G - 1)


def make_hy4_preview(cfg=None) -> JaxModel:
    """Hy4-preview's block on one chip's share of an EP32 prefill pool
    (``sparse_latent.HY4_PREVIEW_EP32_SHARE``; a test passes a tiny
    ``cfg``): INT32 INPUT_IDS [S] → INT32 TOKENS [2] (the greedy next token,
    then the multi-token-prediction module's greedy draft of the one after
    it), FP32 LOGITS [2, vocabulary slice] (the rows that chose them), INT32
    CHOSEN [full blocks, S, words(S)] (the bit planes each full indexer made
    and the attention read: key ``s`` of query ``t`` is bit ``s // W`` of
    word ``s % W`` of row ``t``) and INT32 ROUTES [expert blocks, S, k]
    (each token's experts, of all routed).  One request is one prompt; a
    prefill pool that drafts with MTP answers it with the first token and
    its draft (and a cache handed on, which this model does not keep)."""
    if cfg is None:
        from .sparse_latent import HY4_PREVIEW_EP32_SHARE as cfg
    from ..ops.sparse_attention import words

    S = cfg.seq_len
    return _served_block(
        "hy4_preview", "sparse_latent", "forward", cfg,
        [("TOKENS", "INT32", [2], "tokens"),
         ("LOGITS", "FP32", [2, cfg.vocab_size], "logits"),
         ("CHOSEN", "INT32", [cfg.full_blocks, S, words(S)], "chosen"),
         ("ROUTES", "INT32", [cfg.expert_blocks, S,
                              cfg.num_experts_per_tok], "routes")],
        S, max_batch_size=2)


# Mixture-of-experts scorer: serves the flagship stack's MoE FFN path
# (router top-k + per-expert FFN + psum combine over ep) — expert parallel
# in SERVING, not just the equivalence-tested training path.
_MOE_PRESETS = {
    "tiny": (tr.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=128, n_experts=4, moe_top_k=2), 128),
    "base": (tr.TransformerConfig(
        vocab_size=256, d_model=512, n_layers=4, n_heads=8, head_dim=64,
        d_ff=2048, n_experts=8, moe_top_k=2), 256),
}


def _moe_preset() -> str:
    return _env_preset("TRITON_TPU_MOE_PRESET", _MOE_PRESETS,
                       tpu_default="base", cpu_default="tiny")


def moe_cfg() -> tr.TransformerConfig:
    return _MOE_PRESETS[_moe_preset()][0]


def moe_seq_len() -> int:
    return _MOE_PRESETS[_moe_preset()][1]


def make_moe_tpu() -> JaxModel:
    """MoE next-token model: INT32 TOKENS [S] → INT32 NEXT_TOKEN [1] +
    FP32 NEXT_LOGIT [1], through the shared stack's expert-parallel FFN."""
    S = moe_seq_len()
    cfg = make_config(
        "moe_tpu",
        inputs=[("TOKENS", "INT32", [S])],
        outputs=[("NEXT_TOKEN", "INT32", [1]), ("NEXT_LOGIT", "FP32", [1])],
        max_batch_size=8,
        preferred_batch_sizes=[1, 2, 4, 8],
        max_queue_delay_us=2000,
        instance_kind="KIND_TPU",
    )
    run = _LazyTransformer(moe_cfg(), seed=17, model_name="moe_tpu")

    def fn(TOKENS):
        import jax.numpy as jnp

        tokens = jnp.clip(TOKENS, 0, run.cfg.vocab_size - 1)
        logits = run(tokens)[:, -1, :]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        best = jnp.max(logits, axis=-1).astype(jnp.float32)
        return {"NEXT_TOKEN": nxt[:, None], "NEXT_LOGIT": best[:, None]}

    return JaxModel(cfg, fn, jit=False, analyzable=True)


def _llama_preset() -> str:
    return _env_preset("TRITON_TPU_LLAMA_PRESET", _LLAMA_PRESETS,
                       tpu_default="1b", cpu_default="tiny")


def _llama_cfg() -> tr.TransformerConfig:
    return _LLAMA_PRESETS[_llama_preset()]


def resolved_presets() -> Dict[str, str]:
    """Platform and preset each env-preset transformer family resolved to
    — what the server logs at registration and ``chip_smoke.py`` checks."""
    return {"platform": _preset_platform(), "llama": _llama_preset(),
            "longctx": _longctx_preset(), "moe": _moe_preset()}


def make_llama_preprocess() -> PyModel:
    """BYTES TEXT [1] → INT32 TOKENS [128]: byte-level tokens, left-padded
    with 0 (works for every preset vocab)."""
    cfg = make_config(
        "llama_preprocess",
        inputs=[("TEXT", "BYTES", [1])],
        outputs=[("TOKENS", "INT32", [LLAMA_SEQ_LEN])],
        max_batch_size=8,
    )

    def fn(inputs, params):
        texts = np.asarray(inputs["TEXT"]).reshape(-1)
        out = np.zeros((len(texts), LLAMA_SEQ_LEN), np.int32)
        for i, t in enumerate(texts):
            raw = t if isinstance(t, (bytes, bytearray)) else str(t).encode()
            b = np.frombuffer(bytes(raw[-LLAMA_SEQ_LEN:]), np.uint8)
            out[i, LLAMA_SEQ_LEN - len(b):] = b
        return {"TOKENS": out.reshape(len(texts), LLAMA_SEQ_LEN)}

    return PyModel(cfg, fn)


def make_llama_tpu() -> JaxModel:
    """Llama-architecture next-token model: INT32 TOKENS [128] →
    INT32 NEXT_TOKEN [1] (+ FP32 NEXT_LOGIT [1]); greedy head, device-side
    argmax so only 8 bytes cross D2H per request."""
    cfg = make_config(
        "llama_tpu",
        inputs=[("TOKENS", "INT32", [LLAMA_SEQ_LEN])],
        outputs=[("NEXT_TOKEN", "INT32", [1]), ("NEXT_LOGIT", "FP32", [1])],
        max_batch_size=8,
        preferred_batch_sizes=[1, 2, 4, 8],
        max_queue_delay_us=2000,
        instance_kind="KIND_TPU",
        parameters={"flops_per_inference": str(
            LLAMA_SEQ_LEN * forward_flops_per_token(
                _llama_cfg(), LLAMA_SEQ_LEN))},
    )
    state: Dict[str, Any] = {}

    def fn(TOKENS):
        import jax.numpy as jnp

        if "run" not in state:
            state["run"] = _LazyTransformer(_llama_cfg(), seed=3, model_name="llama_tpu")
        run = state["run"]
        tokens = jnp.clip(TOKENS, 0, run.cfg.vocab_size - 1)
        logits = run(tokens)[:, -1, :]  # [B, vocab]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        best = jnp.max(logits, axis=-1).astype(jnp.float32)
        return {"NEXT_TOKEN": nxt[:, None], "NEXT_LOGIT": best[:, None]}

    return JaxModel(cfg, fn, jit=False, analyzable=True)


def make_llama_postprocess() -> PyModel:
    """INT32 NEXT_TOKEN [1] → BYTES OUT_TEXT [1] (byte detokenizer)."""
    cfg = make_config(
        "llama_postprocess",
        inputs=[("NEXT_TOKEN", "INT32", [1])],
        outputs=[("OUT_TEXT", "BYTES", [1])],
        max_batch_size=8,
    )

    def fn(inputs, params):
        toks = np.asarray(inputs["NEXT_TOKEN"]).reshape(-1)
        texts = np.array([bytes([int(t) % 256]) for t in toks], dtype=object)
        return {"OUT_TEXT": texts.reshape(len(toks), 1)}

    return PyModel(cfg, fn)


def make_ensemble_llama() -> EnsembleModel:
    """BASELINE row 5 ensemble: TEXT → preprocess → llama_tpu → postprocess
    → OUT_TEXT (+ NEXT_TOKEN surfaced for generation loops)."""
    cfg = make_config(
        "ensemble_llama",
        inputs=[("TEXT", "BYTES", [1])],
        outputs=[("OUT_TEXT", "BYTES", [1]), ("NEXT_TOKEN", "INT32", [1])],
        max_batch_size=8,
        platform="ensemble",
        backend="",
    )
    step = cfg.ensemble_scheduling.step.add()
    step.model_name = "llama_preprocess"
    step.input_map["TEXT"] = "TEXT"
    step.output_map["TOKENS"] = "_tokens"
    step = cfg.ensemble_scheduling.step.add()
    step.model_name = "llama_tpu"
    step.input_map["TOKENS"] = "_tokens"
    step.output_map["NEXT_TOKEN"] = "NEXT_TOKEN"
    step.output_map["NEXT_LOGIT"] = "_logit"
    step = cfg.ensemble_scheduling.step.add()
    step.model_name = "llama_postprocess"
    step.input_map["NEXT_TOKEN"] = "NEXT_TOKEN"
    step.output_map["OUT_TEXT"] = "OUT_TEXT"
    return EnsembleModel(cfg)
