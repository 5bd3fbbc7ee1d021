"""The hybrid generation's share of the chip's memory bandwidth: the bytes
the traced window's executions had to move (``flop_counts/lfm2_8b_a1b``: a
prefill reads every matrix once and writes both kinds of state; each decode
step reads the experts its batch touched, from Δ``experts_touched`` as the
device counted them, every other matrix, the keys and values up to its
position and the conv layers' rows of state) over what the chip could have
moved while it was busy.  A decode step is bound by the weights it reads, so
this is the cell's roofline; the prefill's compute-bound seconds are in the
denominator too (``PERF.md`` §5 gives a decode step alone beside it).

The window's mean batch stands for every execution's (exact where its
executions are alike)."""

from chipbench.files import load_module

_NEEDS = ("decode_steps.count", "experts_touched.count", "inference_count",
          "execution_count")


def read(ctx: dict):
    trace, delta = ctx.get("trace"), ctx.get("stats_delta")
    if not trace or not delta or not all(delta.get(k) for k in _NEEDS):
        return None
    cfg = ctx["config"]
    work = load_module("flop_counts", cfg["flops"])
    executions = delta["execution_count"]
    batch = delta["inference_count"] / executions
    moved = executions * work.generation_bytes(
        cfg, batch, delta["experts_touched.count"] / executions)
    peak = ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"]
    return 100.0 * moved / (trace["busy_s"] * peak)
