"""The generation's share of the chip's memory bandwidth: the bytes the
traced window's executions had to move (``flop_counts/sdar_30b_a3b``: a
prefill's weights and cache an execution; a pass's touched experts, from
Δ``experts_touched`` as the device counted them, its attention, router and
head weights and the cache it reads) over what the chip could have moved
while it was busy.  A pass is bound by the weights it reads, so this is the
cell's roofline; the prefill's compute-bound seconds are in the denominator
too (``PERF.md`` §5 gives a pass alone beside it).

The counters count passes x sequences; the passes themselves follow from
the window's mean batch (exact where its executions are alike, as the
static rule makes them)."""

from chipbench.files import load_module

_NEEDS = ("denoise_passes.count", "denoise_tokens.count",
          "experts_touched.count", "inference_count", "execution_count")


def read(ctx: dict):
    trace, delta = ctx.get("trace"), ctx.get("stats_delta")
    if not trace or not delta or not all(delta.get(k) for k in _NEEDS):
        return None
    cfg = ctx["config"]
    work = load_module("flop_counts", cfg["flops"])
    block = cfg["assumed"]["generation"]["block_length"]
    batch = delta["inference_count"] / delta["execution_count"]
    passes = delta["denoise_passes.count"] / batch
    commits = delta["denoise_tokens.count"] / block / batch
    context = cfg["served"]["seq_len"] + cfg["served"]["new_tokens"] / 2
    touched = delta["experts_touched.count"] / passes   # a pass, all layers
    moved = (delta["execution_count"] * work.prefill_bytes(cfg, batch)
             + (passes - commits) * work.pass_bytes(
                 cfg, batch, touched, context)
             + commits * work.pass_bytes(
                 cfg, batch, touched, context, head=False))
    peak = ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"]
    return 100.0 * moved / (trace["busy_s"] * peak)
