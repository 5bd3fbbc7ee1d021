"""Flagship TPU-native transformer: explicit 5-axis SPMD sharding.

This is the framework's flagship served model family (the TPU analog of the
reference's ResNet-50 / BERT-large / Llama-3-8B baseline configs —
/root/repo/BASELINE.json; the reference itself ships no models, it is a
client SDK, SURVEY.md §2.7) and the vehicle for the multi-chip dry run.

Design (scaling-book recipe, hand-rolled collectives under ``jax.shard_map``):

* Mesh axes ``('dp','pp','ep','sp','tp')``:
    - **dp**  data parallel over batch.
    - **pp**  GPipe pipeline parallel over layer stages (``ppermute`` ring).
    - **ep**  expert parallel over MoE experts (per-expert FFN shards,
      combined with ``psum`` over ``ep``).
    - **sp**  sequence parallel via **ring attention**: K/V chunks circulate
      the ``sp`` ring with ``ppermute`` while a flash-style online softmax
      accumulates partial attention (causal).
    - **tp**  tensor parallel over attention heads and FFN hidden dim with
      ``psum`` reductions after the output projections.
* Everything runs in one ``shard_map``: forward, loss, backward (jax.grad
  through the collectives), per-parameter gradient synchronisation, and a
  manual AdamW update on the local shards.  Gradient sync rule: for every
  parameter leaf, ``psum`` over exactly the mesh axes the leaf is *replicated*
  over (untied-copy summation is the correct tied gradient; ranks whose copy
  is unused contribute zero).
* Static shapes throughout; layer loop is ``lax.scan`` over stacked layer
  params; pipeline and ring loops are ``lax.fori_loop`` — no Python control
  flow inside jit.
* bfloat16 activations/matmuls (MXU-friendly); float32 params/optimizer
  where the model is trained, and the matmul weights and the embedding
  stored once in the compute dtype where it is served (``serving_params``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import parallel
from .parts import rmsnorm

MESH_AXES = ("dp", "pp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 4
    n_heads: int = 4
    head_dim: int = 16
    d_ff: int = 128
    n_experts: int = 2        # 0 => dense FFN, >0 => MoE FFN
    moe_top_k: int = 2
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    # attention direction: decoder stacks (llama/longctx) are causal;
    # encoder stacks (bert_large) attend bidirectionally — both route
    # through the same flash kernel / ring attention, which take `causal`
    causal: bool = True

    @property
    def moe(self) -> bool:
        return self.n_experts > 0


# Llama-3-8B-shaped config for real-hardware serving/benching (same code path).
LLAMA3_8B = TransformerConfig(
    vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
    head_dim=128, d_ff=14336, n_experts=0,
)

TINY = TransformerConfig()


def mesh_shape_for(n_devices: int, cfg: TransformerConfig) -> Dict[str, int]:
    """Greedy factorization of ``n_devices`` onto the 5 mesh axes.

    Priority tp > sp > pp > ep > dp (ICI-friendly inner axes first); any
    non-power-of-two remainder lands on dp."""
    return parallel.factorize_mesh(
        n_devices,
        # the sharded model dim must be divisible by the axis size
        limits={
            "tp": cfg.n_heads,
            "sp": 4,  # seq chunks; callers pick seq lengths divisible by sp
            "pp": cfg.n_layers,
            "ep": max(cfg.n_experts, 1),
        },
        axes=MESH_AXES,
        priority=("tp", "sp", "pp", "ep"),
        remainder_axis="dp",
    )


def make_mesh(n_devices: Optional[int] = None,
              cfg: TransformerConfig = TINY,
              devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return parallel.build_mesh(
        mesh_shape_for(len(devices), cfg), MESH_AXES, devices)


def serve_mesh(cfg: TransformerConfig, spec: Optional[str] = None,
               model_name: Optional[str] = None) -> Mesh:
    """The mesh SERVED models place params/forward over, from
    ``TRITON_TPU_SERVE_MESH`` (or an explicit ``spec``).

    This is the server-side analog of the reference's per-model
    ``instance_group`` placement (its client has no device placement; the
    Triton server it targets does — SURVEY.md §2.4 "server side uses
    pjit-sharded model").  Accepted values:

    - ``"1"`` / unset — one device (``jax.devices()[0]``), the single-chip
      bench-host default.
    - ``"all"`` — every visible device, greedy 5-axis factorization
      (``mesh_shape_for``).
    - an integer ``N`` — the first N devices, greedy factorization.
    - an explicit shape ``"dp=1,pp=2,ep=2,sp=1,tp=2"`` — exact axis sizes
      (unlisted axes default to 1); lets deployments pin e.g. expert
      parallelism where the greedy split would not pick it.

    Per-model override (instance_group analog): when ``model_name`` is
    given, ``TRITON_TPU_SERVE_MESH_<MODEL_NAME>`` (upper-cased, non-
    alphanumerics as ``_``) wins over the global var — heterogeneous
    placement like bert on 4 chips while llama takes all 8.
    """
    var = "TRITON_TPU_SERVE_MESH"
    if spec is None:
        spec, var = resolve_serve_spec(model_name)
    spec = spec.strip().lower()
    devices = jax.devices()
    shape = parse_serve_shape(spec, var)
    if shape is not None:
        _check_axis_divisibility(shape, cfg, spec, var)
        n = math.prod(shape.values())
        if n > len(devices):
            raise ValueError(
                f"{var}={spec!r} needs {n} devices, "
                f"have {len(devices)}")
        return parallel.build_mesh(shape, MESH_AXES, devices[:n])
    return make_mesh(resolve_serve_count(spec, len(devices), var), cfg)


def serve_mesh_spec(model_name: Optional[str] = None) -> str:
    """Resolve the serve-mesh spec string: per-model env override first
    (``TRITON_TPU_SERVE_MESH_<NAME>``), then the global, then ``"1"``."""
    return resolve_serve_spec(model_name)[0]


def serve_mesh_env_key(model_name: str) -> str:
    return "TRITON_TPU_SERVE_MESH_" + "".join(
        c if c.isalnum() else "_" for c in model_name.upper())


def resolve_serve_spec(
        model_name: Optional[str] = None) -> Tuple[str, str]:
    """(spec, env var that supplied it) — errors must blame the variable
    the operator actually set, not always the global."""
    if model_name:
        key = serve_mesh_env_key(model_name)
        per_model = os.environ.get(key)
        if per_model is not None:
            return per_model, key
    return os.environ.get("TRITON_TPU_SERVE_MESH", "1"), \
        "TRITON_TPU_SERVE_MESH"


def parse_serve_shape(
        spec: str,
        var: str = "TRITON_TPU_SERVE_MESH") -> Optional[Dict[str, int]]:
    """Parse an explicit ``"dp=1,tp=2"`` mesh-shape spec into a full 5-axis
    shape dict (unlisted axes 1); returns None for count-style specs
    ("all" / an integer).  Axis sizes must be positive; axis names must be
    mesh axes — violations raise config-time ValueErrors rather than
    surfacing as opaque sharding errors at first request."""
    if "=" not in spec:
        return None
    shape = {}
    for part in spec.split(","):
        ax, _, v = part.partition("=")
        ax = ax.strip()
        if ax not in MESH_AXES:
            raise ValueError(
                f"{var}: unknown mesh axis {ax!r}; "
                f"valid axes are {MESH_AXES}")
        size = int(v)
        if size < 1:
            raise ValueError(
                f"{var}: axis {ax}={size} must be >= 1")
        shape[ax] = size
    for ax in MESH_AXES:
        shape.setdefault(ax, 1)
    return shape


def resolve_serve_count(spec: str, n_avail: int,
                        var: str = "TRITON_TPU_SERVE_MESH") -> int:
    """Resolve a count-style spec ("all" / integer) to a device count."""
    try:
        n = n_avail if spec == "all" else int(spec)
    except ValueError:
        raise ValueError(
            f"{var}={spec!r}: expected '1', 'all', a "
            "device count, or an explicit 'dp=..,tp=..' shape")
    if not 1 <= n <= n_avail:
        raise ValueError(
            f"{var}={spec!r}: need 1..{n_avail} devices")
    return n


def _check_axis_divisibility(shape: Dict[str, int], cfg: TransformerConfig,
                             spec: str,
                             var: str = "TRITON_TPU_SERVE_MESH") -> None:
    """Model-dimension divisibility for an explicit spec, checked at parse
    time so misconfiguration is a readable error, not a jit crash."""
    checks = [("tp", cfg.n_heads, "n_heads"), ("pp", cfg.n_layers,
                                               "n_layers")]
    if cfg.moe:
        checks.append(("ep", cfg.n_experts, "n_experts"))
    for ax, dim, dim_name in checks:
        if shape[ax] > 1 and dim % shape[ax] != 0:
            raise ValueError(
                f"{var}={spec!r}: {ax}={shape[ax]} must "
                f"divide {dim_name}={dim}")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

#: Shardings for the int8 ``*_scale`` siblings quantize_layer_weights
#: produces: the reduced (contraction) axes are singletons, the surviving
#: output-channel axes shard exactly like the weight's.
_SCALE_SPECS = {
    "wq_scale": P("pp", None, "tp", None),
    "wk_scale": P("pp", None, "tp", None),
    "wv_scale": P("pp", None, "tp", None),
    "wo_scale": P("pp", None, None, None),
    "w1_scale": P("pp", None, "tp"),
    "w2_scale": P("pp", None, None),
    "we1_scale": P("pp", "ep", None, "tp"),
    "we2_scale": P("pp", "ep", None, None),
}


def param_specs(cfg: TransformerConfig,
                quantized: bool = False) -> Dict[str, P]:
    """PartitionSpec per parameter leaf.  Layer-stacked leaves lead with the
    layer dim sharded over ``pp`` (each pipeline stage owns its layers).
    ``quantized`` adds the int8 ``*_scale`` sibling specs."""
    specs = {
        "embed": P(None, None),
        "wq": P("pp", None, "tp", None),
        "wk": P("pp", None, "tp", None),
        "wv": P("pp", None, "tp", None),
        "wo": P("pp", "tp", None, None),
        "ln1": P("pp", None),
        "ln2": P("pp", None),
        "final_ln": P(None),
        "head": P(None, None),
    }
    if cfg.moe:
        specs.update({
            "router": P("pp", None, None),
            "we1": P("pp", "ep", None, "tp"),
            "we2": P("pp", "ep", "tp", None),
        })
    else:
        specs.update({
            "w1": P("pp", None, "tp"),
            "w2": P("pp", "tp", None),
        })
    if quantized:
        specs.update({k: v for k, v in _SCALE_SPECS.items()
                      if k[:-len("_scale")] in specs})
    return specs


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict[str, jax.Array]:
    """Global (unsharded) float32 init; shard_map in_specs scatter them."""
    keys = jax.random.split(rng, 16)
    D, H, K, F, L, V = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                        cfg.n_layers, cfg.vocab_size)
    s = lambda *sh: 1.0 / math.sqrt(sh[-2] if len(sh) > 1 else sh[-1])
    # qkv projections: fan-in is d_model (dim 1 of [L, D, H, K])
    norm = lambda k, *sh: (jax.random.normal(k, sh, jnp.float32)
                           * (1.0 / math.sqrt(sh[1])))
    p = {
        "embed": jax.random.normal(keys[0], (V, D), jnp.float32) * 0.02,
        "wq": norm(keys[1], L, D, H, K),
        "wk": norm(keys[2], L, D, H, K),
        "wv": norm(keys[3], L, D, H, K),
        "wo": jax.random.normal(keys[4], (L, H, K, D), jnp.float32)
              * (1.0 / math.sqrt(H * K)),
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
        "final_ln": jnp.ones((D,), jnp.float32),
        "head": jax.random.normal(keys[5], (D, V), jnp.float32) * 0.02,
    }
    if cfg.moe:
        E = cfg.n_experts
        p["router"] = jax.random.normal(keys[6], (L, D, E), jnp.float32) * 0.02
        p["we1"] = jax.random.normal(keys[7], (L, E, D, F), jnp.float32) * s(D, F)
        p["we2"] = jax.random.normal(keys[8], (L, E, F, D), jnp.float32) * s(F, D)
    else:
        p["w1"] = jax.random.normal(keys[7], (L, D, F), jnp.float32) * s(D, F)
        p["w2"] = jax.random.normal(keys[8], (L, F, D), jnp.float32) * s(F, D)
    return p


def quantize_layer_weights(params, cfg: TransformerConfig):
    """Weight-only int8 quantization of the stacked layer matmul weights.

    Symmetric per-output-channel scales (over the contraction axes), stored
    as ``<name>_scale`` siblings; norms/embedding/head stay full precision.
    Serves two consumers: the KV-decode stack dequantizes on the fly
    (weight-bandwidth lever, models/decode.py ``_w``), and the encoder
    serving forward runs true int8×int8 MXU matmuls with dynamically
    quantized activations (compute lever, ``_int8_dot`` below)."""
    # reduce over each weight's CONTRACTION axes (after the stacked layer
    # axis 0) so every true output channel keeps its own scale — for
    # wq/wk/wv [L, D, H, K] the outputs are (head, k) pairs, so only the
    # d_model axis reduces
    contract_axes = {"wq": (1,), "wk": (1,), "wv": (1,),
                     "wo": (1, 2), "w1": (1,), "w2": (1,),
                     # MoE experts: [L, E, D, F] / [L, E, F, D] contract the
                     # middle dim per expert; the router stays fp (it picks
                     # experts — quantization noise there changes routing)
                     "we1": (2,), "we2": (2,)}
    out = dict(params)
    for k, axes in contract_axes.items():
        if k not in params:
            continue
        w = jnp.asarray(params[k], jnp.float32)
        amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        out[k] = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        out[k + "_scale"] = scale.astype(jnp.float32)
    return out


#: The leaves the forward reads through ``_cast(…, cfg.dtype)``: the
#: embedding (``make_forward``), the attention matrices (``_qkv_proj``,
#: ``_out_proj``) and the FFN's, dense or expert (``_ffn_apply``).  ``head``,
#: ``router``, the norms and the int8 ``*_scale`` siblings are read in f32.
_CAST_LEAVES = ("embed", "wq", "wk", "wv", "wo", "w1", "w2", "we1", "we2")


def serving_params(params, cfg: TransformerConfig):
    """``params`` as a served forward holds them: every leaf the forward
    would ``_cast`` to ``cfg.dtype`` stored in ``cfg.dtype``, once, where it
    is floating point and wider (an int8 leaf of ``quantize_layer_weights``
    stays int8); every other leaf as it is.  ``bf16(w)`` taken here is bit
    for bit ``bf16(w)`` taken in every forward, without the 6 bytes a
    parameter the cast moves each time.  Training keeps f32 (the master
    weights): this is for ``make_forward`` alone."""
    dtype = jnp.dtype(cfg.dtype)

    def stored(k, v):
        if (k in _CAST_LEAVES and jnp.issubdtype(v.dtype, jnp.floating)
                and v.dtype.itemsize > dtype.itemsize):
            return v.astype(dtype)
        return v

    return {k: stored(k, v) for k, v in params.items()}


def quant_env_key(model_name: str) -> str:
    return "TRITON_TPU_QUANT_" + "".join(
        c if c.isalnum() else "_" for c in model_name.upper())


def resolve_quant(model_name: Optional[str] = None) -> str:
    """Serving quantization mode: '' (bf16) or 'int8'.

    ``TRITON_TPU_QUANT_<MODEL>`` overrides the global ``TRITON_TPU_QUANT``
    (same per-model convention as the serve-mesh spec); unknown values fail
    loudly at config time with the variable that was set."""
    var = "TRITON_TPU_QUANT"
    val = os.environ.get(var, "")
    if model_name:
        key = quant_env_key(model_name)
        per_model = os.environ.get(key)
        if per_model is not None:
            var, val = key, per_model
    val = val.strip().lower()
    if val in ("", "none", "bf16"):
        return ""
    if val == "int8":
        return "int8"
    raise ValueError(f"{var}={val!r}: expected 'int8' or unset")


# ---------------------------------------------------------------------------
# Model math (runs INSIDE shard_map: all arrays are per-device local shards)
# ---------------------------------------------------------------------------

def _int8_quant(h, axes):
    """Dynamic symmetric int8 quantization of an activation over its
    contraction ``axes``: [...] -> (int8 values, f32 scale with the reduced
    axes kept as singletons).  Per-token scales (everything but the
    contraction dims survives) keep outliers local to their row."""
    amax = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=axes, keepdims=True)
    s = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(h.astype(jnp.float32) / s),
                 -127, 127).astype(jnp.int8)
    return q, s


# ``jax.named_scope`` below is metadata only: the scopes name the ops on the
# profiler's device plane (and in HLO dumps) after the part of the step they
# belong to, and leave the compiled programs and their cache keys alone.


@jax.named_scope("weight_cast")
def _cast(w, dtype):
    """A weight as the matmul reads it.  Training holds f32 master weights
    and casts them here in every step; a served model stores these leaves
    in ``dtype`` already (``serving_params``), so this is a no-op there and
    the scope names nothing in its program."""
    return w.astype(dtype)


def _rope(q, k, positions, theta):
    # q,k: [B, Hl, S, K]; positions: [S]
    Kd = q.shape[-1]
    half = Kd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _ring_attention(q, k, v, cfg: TransformerConfig):
    """Ring attention over the ``sp`` axis
    (parallel.collectives.ring_attention)."""
    return parallel.ring_attention(q, k, v, "sp", causal=cfg.causal)


def _flash_enabled() -> bool:
    return os.environ.get("TRITON_TPU_FLASH", "1") != "0"


def _int8_fused_mode() -> frozenset:
    """Which int8 FFN matmuls take the fused quantize+matmul pallas kernel
    (ops/int8_matmul.py): '0' (none), 'w1', 'w2' (the measured default),
    or '1'/'all' for both.  benchmarks/BERT_PROFILE.md §6: at the
    bert_large serving shape only the FFN-down matmul wins (58.4 vs
    59.8 ms/forward, weight-resident schedule); fusing w1 LOSES — XLA
    folds the quantize chain into the adjacent rmsnorm/silu passes, which
    the standalone-GEMM comparison couldn't see."""
    val = os.environ.get("TRITON_TPU_INT8_FUSED", "w2").strip().lower()
    if val in ("", "0"):
        return frozenset()
    if val in ("1", "all"):
        return frozenset(("w1", "w2"))
    mode = frozenset(v.strip() for v in val.split(",") if v.strip())
    unknown = mode - frozenset(("w1", "w2"))
    if unknown:
        # a typo'd knob must not silently fall back to the XLA path —
        # same loud-rejection policy as resolve_quant above
        raise ValueError(
            f"TRITON_TPU_INT8_FUSED={val!r}: unknown selector(s) "
            f"{sorted(unknown)}; expected '0', '1'/'all', 'w1', 'w2', "
            "or a comma list of w1/w2")
    return mode


# A layer's f32 scores [B, H, S, S] up to this size XLA keeps on the chip
# (v5e), and there the one-shard ring's fusions are the faster: bert_large's
# forward at [1,384] (9 MiB) 4.76 ms against 4.92 through the kernel, at
# [2,384] 6.53 against 6.61.  Past it they go through HBM three times a
# layer, and the kernel wins: [4,384] (36 MiB) 9.31 against 9.37 ms,
# [8,384] 15.57 against 16.08, [16,384] 27.84 against 38.94, [32,384] 54.44
# against 76.16 (my chip runs, PR 27; PERF.md §6).
_SCORES_ON_CHIP_BYTES = 32 << 20


@jax.named_scope("qkv_proj")
def _qkv_proj(blk, h):
    if "wq_scale" in blk:
        # int8 MXU path: activations quantized per token, weights already
        # int8 per output channel; the einsum runs int8×int8 with int32
        # accumulation (2× bf16 MXU peak on v5e) and the rescale is a
        # cheap elementwise epilogue XLA fuses into the consumer
        hq, hs = _int8_quant(h, (-1,))          # [B,S,D] i8, [B,S,1] f32

        def proj(name):
            out = jnp.einsum("bsd,dhk->bhsk", hq, blk[name],
                             preferred_element_type=jnp.int32)
            ws = blk[name + "_scale"]           # [1,H,K]
            return (out.astype(jnp.float32)
                    * hs[:, None, :, :] * ws[:, :, None, :]).astype(h.dtype)

        return proj("wq"), proj("wk"), proj("wv")
    q = jnp.einsum("bsd,dhk->bhsk", h, _cast(blk["wq"], h.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", h, _cast(blk["wk"], h.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", h, _cast(blk["wv"], h.dtype))
    return q, k, v


@jax.named_scope("scores_softmax")
def _scores_softmax(q, k, v, cfg: TransformerConfig):
    B, H, S, _ = q.shape
    if (lax.axis_size("sp") == 1 and _flash_enabled()
            and 4 * B * H * S * S > _SCORES_ON_CHIP_BYTES):
        # the whole sequence on one device, and a layer's scores too many
        # to stay on it: the pallas kernel (ops/) never sends them to HBM —
        # whole-row for short S, looped with the ring's online carry for
        # long S (longctx_tpu)
        from ..ops import flash_attention

        return flash_attention(q, k, v, causal=cfg.causal)
    return _ring_attention(q, k, v, cfg)


@jax.named_scope("out_proj")
def _out_proj(blk, o):
    if "wo_scale" in blk:
        # contraction is (h, k): quantize per (b, s) over the local heads —
        # each tp rank rescales its own partial product BEFORE the psum
        oq, osc = _int8_quant(o, (1, 3))        # [B,H,S,K] i8, [B,1,S,1]
        out = jnp.einsum("bhsk,hkd->bsd", oq, blk["wo"],
                         preferred_element_type=jnp.int32)
        out = (out.astype(jnp.float32)
               * osc[:, 0, :, :] * blk["wo_scale"]).astype(o.dtype)
    else:
        out = jnp.einsum("bhsk,hkd->bsd", o, _cast(blk["wo"], o.dtype))
    return lax.psum(out, "tp")


@jax.named_scope("attention")
def _attn_apply(blk, x, cfg: TransformerConfig):
    h = rmsnorm(x, blk["ln1"], cfg.norm_eps)
    q, k, v = _qkv_proj(blk, h)
    Sc = x.shape[1]
    positions = lax.axis_index("sp") * Sc + jnp.arange(Sc)
    q, k = _rope(q, k, positions, cfg.rope_theta)
    return x + _out_proj(blk, _scores_softmax(q, k, v, cfg))


@jax.named_scope("ffn")
def _ffn_apply(blk, x, cfg: TransformerConfig):
    h = rmsnorm(x, blk["ln2"], cfg.norm_eps)
    if cfg.moe:
        gate = jnp.einsum("bsd,de->bse", h.astype(jnp.float32),
                          blk["router"].astype(jnp.float32))
        top, _ = lax.top_k(gate, cfg.moe_top_k)
        thresh = top[..., -1:]
        probs = jax.nn.softmax(jnp.where(gate >= thresh, gate, -1e30), axis=-1)
        El = blk["we1"].shape[0]
        start = lax.axis_index("ep") * El
        local_probs = lax.dynamic_slice_in_dim(probs, start, El, axis=-1)

        def _mw(name):
            # expert weights dequantized on the fly when int8 (weight-only
            # for MoE: routing keeps the dense int8-MXU path out of reach)
            w = _cast(blk[name], h.dtype)
            s = blk.get(name + "_scale")
            return w * s.astype(h.dtype) if s is not None else w

        he = jnp.einsum("bsd,edf->ebsf", h, _mw("we1"))
        he = jax.nn.silu(he)
        oe = jnp.einsum("ebsf,efd->ebsd", he, _mw("we2"))
        oe = lax.psum(oe, "tp")
        out = jnp.einsum("ebsd,bse->bsd", oe, local_probs.astype(oe.dtype))
        out = lax.psum(out, "ep")
    elif "w1_scale" in blk:
        # dense FFN on the int8 MXU path (see _attn_apply); both matmuls
        # are 2D row-quantized GEMMs with no layout change around them,
        # so they take the fused quantize+matmul pallas kernel — the
        # int8 activation copy never round-trips HBM — wherever its
        # full-K-resident design takes the shape.  The choice is made
        # here, from the shape: the kernel raises on a shape it cannot
        # take rather than quietly running something else
        from ..ops import int8_matmul, int8_matmul_fits

        fused = frozenset(name for name in _int8_fused_mode()
                          if int8_matmul_fits(*blk[name].shape))
        if "w1" in fused:
            he = int8_matmul(h, blk["w1"], blk["w1_scale"])
        else:
            hq, hs = _int8_quant(h, (-1,))
            he = jnp.einsum("bsd,df->bsf", hq, blk["w1"],
                            preferred_element_type=jnp.int32)
            he = (he.astype(jnp.float32) * hs
                  * blk["w1_scale"]).astype(h.dtype)
        he = jax.nn.silu(he)
        if "w2" in fused:
            out = int8_matmul(he, blk["w2"], blk["w2_scale"])
        else:
            gq, gs = _int8_quant(he, (-1,))
            out = jnp.einsum("bsf,fd->bsd", gq, blk["w2"],
                             preferred_element_type=jnp.int32)
            out = (out.astype(jnp.float32) * gs
                   * blk["w2_scale"]).astype(h.dtype)
        out = lax.psum(out, "tp")
    else:
        he = jnp.einsum("bsd,df->bsf", h, _cast(blk["w1"], h.dtype))
        he = jax.nn.silu(he)
        out = jnp.einsum("bsf,fd->bsd", he, _cast(blk["w2"], h.dtype))
        out = lax.psum(out, "tp")
    return x + out


_LAYER_KEYS_DENSE = ("wq", "wk", "wv", "wo", "ln1", "ln2", "w1", "w2")
_LAYER_KEYS_MOE = ("wq", "wk", "wv", "wo", "ln1", "ln2", "router", "we1", "we2")


def _layer_keys(cfg):
    return _LAYER_KEYS_MOE if cfg.moe else _LAYER_KEYS_DENSE


def _stage_apply(params, x, cfg: TransformerConfig):
    """Run this pipeline stage's local stack of layers (lax.scan)."""
    blocks = {}
    for k in _layer_keys(cfg):
        blocks[k] = params[k]
        if k + "_scale" in params:
            blocks[k + "_scale"] = params[k + "_scale"]

    def step(carry, blk):
        y = _attn_apply(blk, carry, cfg)
        y = _ffn_apply(blk, y, cfg)
        return y, None

    out, _ = lax.scan(step, x, blocks)
    return out


def _pipeline_apply(params, x_mbs, cfg: TransformerConfig):
    """GPipe schedule over the ``pp`` ring.

    x_mbs: [n_micro, mb, Sc, D] embedded microbatches (identical on every pp
    rank).  Returns [n_micro, mb, Sc, D] — valid only on the LAST stage;
    other stages hold garbage that callers must mask."""
    pp = lax.axis_size("pp")
    stage = lax.axis_index("pp")
    n_micro = x_mbs.shape[0]
    steps = n_micro + pp - 1
    state0 = jnp.zeros_like(x_mbs[0])
    out0 = jnp.zeros_like(x_mbs)

    def body(t, carry):
        state, outs = carry
        inp = lax.dynamic_index_in_dim(
            x_mbs, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
        state = jnp.where(stage == 0, inp, state)
        state = _stage_apply(params, state, cfg)
        out_idx = t - (pp - 1)
        idx = jnp.clip(out_idx, 0, n_micro - 1)
        cur = lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
        valid = jnp.logical_and(out_idx >= 0, stage == pp - 1)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, state, cur), idx, 0)
        perm = [(j, (j + 1) % pp) for j in range(pp)]
        state = lax.ppermute(state, "pp", perm)
        return state, outs

    _, outs = lax.fori_loop(0, steps, body, (state0, out0))
    return outs


def _local_loss(params, tokens, labels, cfg: TransformerConfig,
                n_micro: int):
    """Per-rank masked loss sum + local token count.

    tokens/labels: [Bl, Sc] local (dp, sp) shards, replicated over pp/ep/tp.
    Loss is nonzero only on the last pp stage; callers psum over
    (dp, sp, pp) and divide by the global count."""
    Bl, Sc = tokens.shape
    mb = Bl // n_micro
    x = jnp.take(params["embed"].astype(cfg.dtype), tokens, axis=0)
    x_mbs = x.reshape(n_micro, mb, Sc, cfg.d_model)
    outs = _pipeline_apply(params, x_mbs, cfg)
    h = rmsnorm(outs, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("nbsd,dv->nbsv", h.astype(jnp.float32),
                        params["head"].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    lab = labels.reshape(n_micro, mb, Sc)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    is_last = (lax.axis_index("pp") == lax.axis_size("pp") - 1)
    local_sum = jnp.where(is_last, jnp.sum(nll), 0.0)
    return local_sum


def _replicated_axes(spec: P) -> Tuple[str, ...]:
    return parallel.replicated_axes(spec, MESH_AXES)


def _sync_grads(grads, specs):
    return parallel.sync_replicated_grads(grads, specs, MESH_AXES)


# ---------------------------------------------------------------------------
# Manual AdamW (elementwise => shards independently; no optax state-spec glue)
# ---------------------------------------------------------------------------

def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def _adam_update(params, grads, opt, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.0):
    count = opt["count"] + 1
    t = count.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def upd(p, g, mu, nu):
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * (g * g)
        step = lr * (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
        return p - step - lr * weight_decay * p, mu, nu

    new = {k: upd(params[k], grads[k], opt["mu"][k], opt["nu"][k])
           for k in params}
    params2 = {k: v[0] for k, v in new.items()}
    mu2 = {k: v[1] for k, v in new.items()}
    nu2 = {k: v[2] for k, v in new.items()}
    return params2, {"mu": mu2, "nu": nu2, "count": count}


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def opt_specs(cfg: TransformerConfig):
    ps = param_specs(cfg)
    return {"mu": ps, "nu": dict(ps), "count": P()}


def make_grad_fn(mesh: Mesh, cfg: TransformerConfig, n_micro: int = 2):
    """jit(shard_map): (params, tokens, labels) -> (synced mean grads, loss).

    Exposed separately so tests can check raw gradients (Adam hides constant
    per-leaf scale errors) and so external training loops can compose."""
    specs = param_specs(cfg)
    # tp/ep ranks each compute the *same* loss from their own param copies,
    # and autodiff (collective transposes) already hands every copy the full
    # tied gradient — so the psum over compute-replicated axes over-counts by
    # the axis size.  dp/sp shard *data* and pp's loss is masked to the last
    # stage, so those psums are true summation.  Static rescale corrects it
    # (verified against single-device grads in test_transformer.py).
    compute_scale = float(mesh.shape["tp"] * mesh.shape["ep"])

    def local_grads(params, tokens, labels):
        def loss_fn(p):
            return _local_loss(p, tokens, labels, cfg, n_micro)

        loss_local, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(loss_local, ("dp", "sp", "pp"))
        count = lax.psum(jnp.float32(tokens.size), ("dp", "sp"))
        grads = _sync_grads(grads, specs)
        grads = {k: g / (count * compute_scale) for k, g in grads.items()}
        return grads, loss / count

    return jax.jit(jax.shard_map(
        local_grads, mesh=mesh,
        in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(specs, P()),
        check_vma=False,
    ))


def make_train_step(mesh: Mesh, cfg: TransformerConfig, n_micro: int = 2,
                    lr: float = 1e-3):
    """jit(shard_map(train step)): (params, opt, tokens, labels) ->
    (params, opt, loss).  tokens/labels are global [B, S] int32."""
    specs = param_specs(cfg)
    ospecs = opt_specs(cfg)
    compute_scale = float(mesh.shape["tp"] * mesh.shape["ep"])

    def local_step(params, opt, tokens, labels):
        def loss_fn(p):
            return _local_loss(p, tokens, labels, cfg, n_micro)

        loss_local, grads = jax.value_and_grad(loss_fn)(params)
        loss_sum = lax.psum(loss_local, ("dp", "sp", "pp"))
        count = lax.psum(jnp.float32(tokens.size), ("dp", "sp"))
        loss = loss_sum / count
        grads = _sync_grads(grads, specs)
        grads = {k: g / (count * compute_scale) for k, g in grads.items()}
        params, opt = _adam_update(params, grads, opt, lr=lr)
        return params, opt, loss

    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, ospecs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(specs, ospecs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


def make_forward(mesh: Mesh, cfg: TransformerConfig, n_micro: int = 1,
                 quantized: bool = False, head_cols: Optional[int] = None):
    """jit(shard_map(forward)): (params, tokens[B,S]) -> logits [B,S,V]
    (replicated over pp via psum broadcast of the last stage's output).
    ``quantized=True`` expects quantize_layer_weights params and runs the
    layer matmuls on the int8 MXU path.  ``head_cols=N`` projects only the
    first N head columns (e.g. a BERT-SQuAD span head needs 2, not the
    vocab_size the shared param carries) — the FLOPs accounting in
    language.forward_flops_per_token takes the same value so MFU stays
    honest about what actually executed."""
    specs = param_specs(cfg, quantized=quantized)

    def local_fwd(params, tokens):
        Bl, Sc = tokens.shape
        mb = Bl // n_micro
        with jax.named_scope("embed"):
            x = jnp.take(_cast(params["embed"], cfg.dtype), tokens, axis=0)
        x_mbs = x.reshape(n_micro, mb, Sc, cfg.d_model)
        outs = _pipeline_apply(params, x_mbs, cfg)
        is_last = (lax.axis_index("pp") == lax.axis_size("pp") - 1)
        outs = jnp.where(is_last, outs, 0.0).astype(jnp.float32)
        outs = lax.psum(outs, "pp").astype(cfg.dtype)
        with jax.named_scope("head"):
            h = rmsnorm(outs, params["final_ln"], cfg.norm_eps)
            head = params["head"]
            if head_cols is not None:
                head = head[:, :head_cols]
            logits = jnp.einsum("nbsd,dv->nbsv", h.astype(jnp.float32),
                                head.astype(jnp.float32))
        return logits.reshape(Bl, Sc, head.shape[-1])

    sharded = jax.shard_map(
        local_fwd, mesh=mesh,
        in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp", None),
        check_vma=False,
    )
    return jax.jit(sharded)


def place_params(params, mesh: Mesh, cfg: TransformerConfig):
    specs = param_specs(
        cfg, quantized=any(k.endswith("_scale") for k in params))
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def place_opt(opt, mesh: Mesh, cfg: TransformerConfig):
    """Commit optimizer state to its mesh shardings (opt_specs). Needed when
    state round-trips through storage: a restored array is committed to
    whatever sharding it was saved with, so checkpoint templates must carry
    the mesh placement (utils/checkpoint.py)."""
    return {
        "mu": place_params(opt["mu"], mesh, cfg),
        "nu": place_params(opt["nu"], mesh, cfg),
        "count": jax.device_put(opt["count"], NamedSharding(mesh, P())),
    }
