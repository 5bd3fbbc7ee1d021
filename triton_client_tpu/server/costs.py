"""Cost observability: XLA cost-analysis extraction, roofline
classification, and the per-(model, tenant) cost ledger.

The stack could already say *where* time went (spans, tick profiles,
Perfetto timelines) but not *what it cost* or *who spent it*.  This
module is the missing layer, and its numbers come from the compiler,
not hand math — the TPU-native premise:

* :func:`executable_cost` pulls ``cost_analysis()`` (FLOPs, bytes
  accessed) and ``memory_analysis()`` (argument/output/temp/generated
  bytes) off a compiled XLA executable into a :class:`SignatureCost`.
  The DeviceStatsCollector caches one per (model, input-shape
  signature) at first compile, making auto-derived FLOPs the MFU
  source of truth: moe_tpu, which deliberately declares no
  ``flops_per_inference`` (the dense formula overcounts non-executed
  experts), gets a live MFU from the FLOPs XLA actually scheduled.

* :func:`classify_roofline` places a (FLOPs, bytes) pair against the
  chip ridge point — bf16 peak FLOP/s over peak HBM bytes/s, both from
  :data:`DEVICE_PEAKS` — into a ``compute_bound`` / ``memory_bound``
  verdict with arithmetic intensity and, when a measured compute window
  is supplied, the achieved fraction of the *bound* resource's peak.
  A device the table does not list gets no verdict.

* :class:`CostLedger` accumulates per-(model, tenant) device-time,
  FLOPs, generated tokens, and KV byte-seconds.  Attribution sites
  (the dynamic batcher, the direct-execution path, the decode worker)
  charge each request its *slot-share* of the batch's compute window,
  so per-tenant device-time sums back to the profiler's duty-cycle
  compute window by construction — conservation is the correctness
  contract, pinned by tests.

Every extractor is backend-tolerant: ``cost_analysis()`` returns a
list of dicts on current jax, a plain dict on older versions, and may
be missing entirely on some backends.  Unavailable means *absent* —
never 0, never fabricated — the same rule device_stats follows for
undeclared-FLOPs models.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Dict, List, Mapping, Optional, Tuple

__all__ = [
    "CostLedger",
    "DEVICE_PEAKS",
    "SignatureCost",
    "analysis_enabled",
    "analyze_jax_callable",
    "classify_roofline",
    "device_peaks",
    "executable_cost",
    "merge_cost_snapshots",
]

#: Published single-chip peaks keyed by ``jax.Device.device_kind`` — the
#: ONE source of every MFU and roofline denominator in the repo.
#: ``TPU v5 lite`` is how JAX names a v5e chip; figures from Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s
#: HBM.  A device that is not listed gets NO figure (the gauge is absent,
#: as ``hbm_stats`` does for backends without memory stats) — never a
#: default borrowed from another chip.
DEVICE_PEAKS: Dict[str, Mapping[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


@functools.lru_cache(maxsize=1)
def _local_device_kind() -> str:
    # cached: the batcher asks per executed batch, and a process's device
    # does not change
    import jax

    return jax.local_devices()[0].device_kind


def device_peaks(device_kind: Optional[str] = None
                 ) -> Optional[Mapping[str, float]]:
    """:data:`DEVICE_PEAKS` row for ``device_kind`` (default: this
    process's first local device); None for a device the table does not
    list — callers then report nothing rather than a wrong ratio."""
    if device_kind is None:
        device_kind = _local_device_kind()
    return DEVICE_PEAKS.get(device_kind)


def analysis_enabled() -> bool:
    """Whether compile-time cost analysis runs at all
    (``TRITON_TPU_COST_ANALYSIS=0`` disables — the bench A/B lever for
    the acquisition side; the ledger has its own ``enabled`` flag for
    the attribution side)."""
    return os.environ.get("TRITON_TPU_COST_ANALYSIS", "1") != "0"


class SignatureCost:
    """XLA-derived cost of one compiled (model, input-shape) signature:
    scheduled FLOPs and bytes accessed from ``cost_analysis()``, plus
    the ``memory_analysis()`` byte breakdown.  Zero fields mean the
    backend reported nothing for that leg — consumers must treat 0 as
    *unknown*, not free."""

    __slots__ = ("flops", "bytes_accessed", "argument_bytes",
                 "output_bytes", "temp_bytes", "generated_code_bytes")

    def __init__(self, flops: float = 0.0, bytes_accessed: float = 0.0,
                 argument_bytes: int = 0, output_bytes: int = 0,
                 temp_bytes: int = 0, generated_code_bytes: int = 0) -> None:
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.generated_code_bytes = int(generated_code_bytes)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
        }


def _merged_analysis(analysis: Any) -> Dict[str, float]:
    """Flatten ``cost_analysis()`` output to one {key: sum} dict.  jax
    returns a list of per-partition dicts on current versions and a
    plain dict on older ones; anything else contributes nothing."""
    entries = analysis if isinstance(analysis, (list, tuple)) else [analysis]
    out: Dict[str, float] = {}
    for entry in entries:
        if not isinstance(entry, dict):
            continue
        for key, value in entry.items():
            try:
                out[key] = out.get(key, 0.0) + float(value)
            except (TypeError, ValueError):
                continue
    return out


def executable_cost(compiled: Any) -> Optional[SignatureCost]:
    """Extract a :class:`SignatureCost` from a compiled XLA executable
    (``jitted.lower(...).compile()``).  Returns None when the backend
    exposes no usable analysis; never raises — this runs on the serving
    hot path's first-compile edge and must not take a request down."""
    flops = bytes_accessed = 0.0
    try:
        merged = _merged_analysis(compiled.cost_analysis())
        flops = max(0.0, merged.get("flops", 0.0))
        # XLA's key really does contain a space
        bytes_accessed = max(0.0, merged.get("bytes accessed", 0.0))
    except Exception:  # noqa: BLE001 — observability must never raise
        pass
    arg_b = out_b = temp_b = gen_b = 0
    try:
        ma = compiled.memory_analysis()
        arg_b = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out_b = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        temp_b = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        gen_b = int(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
    except Exception:  # noqa: BLE001
        pass
    if flops <= 0.0 and bytes_accessed <= 0.0:
        return None
    return SignatureCost(flops=flops, bytes_accessed=bytes_accessed,
                         argument_bytes=arg_b, output_bytes=out_b,
                         temp_bytes=temp_b,
                         generated_code_bytes=gen_b)


def analyze_jax_callable(fn: Any, *args: Any,
                         **kwargs: Any) -> Optional[SignatureCost]:
    """AOT-lower ``fn`` on concrete example arguments and extract its
    cost.  ``fn`` may be an already-jitted function or a raw callable
    (lowered through a fresh ``jax.jit`` — nothing executes).  A raw
    serving callable closes over its weights; jitting it as it stands
    would bake them into the executable as constants — on the chip that
    was a 2.4 GB program per ``bert_large`` signature, minutes to compile
    and gigabytes in the compile cache, for a pass that only reads two
    numbers off it.  So the callable is traced once and its jaxpr compiled
    with the closed-over arrays passed as ARGUMENTS: same FLOPs and bytes
    accessed, the weights counted where they live.
    None when jax/the backend can't oblige; never raises."""
    if not analysis_enabled():
        return None
    try:
        import jax

        if hasattr(fn, "lower"):
            compiled = fn.lower(*args, **kwargs).compile()
        else:
            closed = jax.make_jaxpr(fn)(*args, **kwargs)
            compiled = jax.jit(
                lambda consts, *flat: jax.core.eval_jaxpr(
                    closed.jaxpr, consts, *flat)
            ).lower(closed.consts,
                    *jax.tree_util.tree_leaves((args, kwargs))).compile()
    except Exception:  # noqa: BLE001
        return None
    return executable_cost(compiled)


def classify_roofline(flops: float, bytes_accessed: float,
                      compute_s: Optional[float] = None,
                      pf: Optional[float] = None,
                      pb: Optional[float] = None) -> Optional[Dict[str, Any]]:
    """Roofline verdict for a (FLOPs, bytes) workload point.

    ``arithmetic_intensity`` (FLOPs/byte) against the ridge point
    ``pf / pb`` (default: the local device's bf16 peak FLOP/s over its
    peak HBM bytes/s, :func:`device_peaks`): at or above the ridge the
    chip's compute ceiling binds (``compute_bound``), below it the
    memory ceiling does (``memory_bound``).  With a measured
    ``compute_s`` window, ``pct_of_peak`` reports the achieved fraction
    (in percent) of the *bound* resource's peak — how close the workload
    runs to the roof it actually sits under.  None when either axis is
    unknown, or the device has no :data:`DEVICE_PEAKS` row."""
    if flops <= 0.0 or bytes_accessed <= 0.0:
        return None
    if pf is None or pb is None:
        peaks = device_peaks()
        if peaks is None:
            return None
        pf = peaks["bf16_flops"] if pf is None else pf
        pb = peaks["hbm_bytes_per_s"] if pb is None else pb
    if pf <= 0.0 or pb <= 0.0:
        return None
    ai = flops / bytes_accessed
    ridge = pf / pb
    verdict = "compute_bound" if ai >= ridge else "memory_bound"
    out: Dict[str, Any] = {
        "arithmetic_intensity": round(ai, 4),
        "ridge_point": round(ridge, 4),
        "verdict": verdict,
    }
    if compute_s is not None and compute_s > 0.0:
        achieved = (flops / compute_s / pf if verdict == "compute_bound"
                    else bytes_accessed / compute_s / pb)
        out["pct_of_peak"] = round(achieved * 100.0, 4)
    return out


class _CostCell:
    """Cumulative per-(model, tenant) cost counters."""

    __slots__ = ("device_us", "flops", "tokens", "kv_byte_seconds")

    def __init__(self) -> None:
        self.device_us = 0.0
        self.flops = 0.0
        self.tokens = 0
        self.kv_byte_seconds = 0.0


class CostLedger:
    """Per-(model, tenant) cost attribution: device-time (each
    request's slot-share of its batch's compute window), FLOPs
    (slot-share of the signature's measured FLOPs), generated tokens,
    and KV byte-seconds (slot admit..release lifetime × the governor's
    per-token KV bytes).

    Tenant cardinality is bounded the same way the QoS and memory
    ledgers bound theirs: beyond :data:`MAX_TRACKED_TENANTS` distinct
    tenants, new ones fold into :data:`OVERFLOW_TENANT` so the
    ``nv_cost_*`` label sets can't be grown without bound by a client
    minting tenant ids.

    ``enabled=False`` turns every ``charge`` into a no-op — the bench
    ``cost_attribution_overhead`` A/B lever."""

    MAX_TRACKED_TENANTS = 1024
    OVERFLOW_TENANT = "~overflow"

    def __init__(self, enabled: Optional[bool] = None) -> None:
        if enabled is None:
            enabled = os.environ.get("TRITON_TPU_COST_LEDGER", "1") != "0"
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._cells: Dict[Tuple[str, str], _CostCell] = {}
        self._known_tenants: set = set()

    def _tenant_locked(self, tenant: str) -> str:
        if tenant in self._known_tenants:
            return tenant
        if len(self._known_tenants) < self.MAX_TRACKED_TENANTS:
            self._known_tenants.add(tenant)
            return tenant
        return self.OVERFLOW_TENANT

    def charge(self, model: str, tenant: str, device_us: float = 0.0,
               flops: float = 0.0, tokens: int = 0,
               kv_byte_seconds: float = 0.0) -> None:
        """Accumulate one attribution.  Tenant "" (anonymous traffic)
        is a first-class row, not dropped — unattributed device-time
        would break the conservation contract.

        ``kv_byte_seconds`` arrives from two integrators that share one
        reconciliation surface: per-slot generation pins (decode worker,
        admit..release) and prefix-cache block pins (server/kvcache.py,
        commit..evict — charged to the tenant whose cold prefill PINNED
        the block, not to its later hitters; a hit reads the resident
        block for free, so reuse is never double-charged)."""
        if not self.enabled:
            return
        with self._lock:
            key = (model, self._tenant_locked(tenant))
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells.setdefault(key, _CostCell())
            cell.device_us += device_us
            cell.flops += flops
            cell.tokens += int(tokens)
            cell.kv_byte_seconds += kv_byte_seconds

    def totals(self, model: Optional[str] = None) -> Dict[str, float]:
        """Summed counters across tenants (one model, or all)."""
        out = {"device_us": 0.0, "flops": 0.0, "tokens": 0,
               "kv_byte_seconds": 0.0}
        with self._lock:
            for (m, _t), cell in self._cells.items():
                if model is not None and m != model:
                    continue
                out["device_us"] += cell.device_us
                out["flops"] += cell.flops
                out["tokens"] += cell.tokens
                out["kv_byte_seconds"] += cell.kv_byte_seconds
        return out

    # -- export ------------------------------------------------------------
    def metric_rows(self) -> Dict[str, list]:
        """``nv_cost_*`` sample rows keyed by short family name — the
        one source for both the Prometheus renderer and the JSON
        snapshot."""
        rows: Dict[str, list] = {"device_us": [], "flops": [],
                                 "tokens": [], "kv_byte_seconds": []}
        with self._lock:
            items = sorted(self._cells.items())
        for (m, t), cell in items:
            labels = {"model": m, "tenant": t}
            rows["device_us"].append((labels, round(cell.device_us, 3)))
            rows["flops"].append((labels, cell.flops))
            rows["tokens"].append((labels, cell.tokens))
            rows["kv_byte_seconds"].append(
                (labels, round(cell.kv_byte_seconds, 6)))
        return rows

    def snapshot(self, model: Optional[str] = None) -> Dict[str, Any]:
        """The ``/v2/debug/costs`` JSON: per-model, per-tenant cost
        totals.  ``model`` filters; the shape is merge-friendly (see
        the cluster client's aggregation)."""
        with self._lock:
            items = sorted(self._cells.items())
        models: Dict[str, Any] = {}
        for (m, t), cell in items:
            if model is not None and m != model:
                continue
            models.setdefault(m, {})[t] = {
                "device_us": round(cell.device_us, 3),
                "flops": cell.flops,
                "tokens": cell.tokens,
                "kv_byte_seconds": round(cell.kv_byte_seconds, 6),
            }
        return {"enabled": self.enabled, "models": models}

    def reset(self) -> None:
        """Drop everything (tests / bench isolation)."""
        with self._lock:
            self._cells = {}
            self._known_tenants = set()


def merge_cost_snapshots(
        snapshots: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Sum a list of :meth:`CostLedger.snapshot` dicts into one — the
    cluster-level aggregation ``get_costs()`` performs across
    endpoints.  Tolerates malformed entries (a replica mid-restart
    returns {}) by skipping them."""
    merged: Dict[str, Dict[str, Dict[str, Any]]] = {}
    enabled = False
    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        enabled = enabled or bool(snap.get("enabled"))
        for m, tenants in (snap.get("models") or {}).items():
            if not isinstance(tenants, dict):
                continue
            dst_m = merged.setdefault(m, {})
            for t, cell in tenants.items():
                if not isinstance(cell, dict):
                    continue
                dst = dst_m.setdefault(t, {"device_us": 0.0, "flops": 0.0,
                                           "tokens": 0,
                                           "kv_byte_seconds": 0.0})
                for key in ("device_us", "flops", "kv_byte_seconds"):
                    try:
                        dst[key] = round(dst[key] + float(
                            cell.get(key, 0.0)), 6)
                    except (TypeError, ValueError):
                        pass
                try:
                    dst["tokens"] += int(cell.get("tokens", 0))
                except (TypeError, ValueError):
                    pass
    return {"enabled": enabled, "models": merged}
