"""The looped generation's share of the chip's memory bandwidth: the bytes
the traced window's executions had to move (``flop_counts/ouro_2_6b``: a
prefill reads every layer's weights once a loop step and writes the prompt's
keys and values at every loop step; each decode step reads the weights once
a loop step, the head, and the cache of every loop step up to its position)
over what the chip could have moved while it was busy.  A decode step is
bound by the weights it reads four times, so this is the cell's roofline;
the prefill's compute-bound seconds are in the denominator too (``PERF.md``
§5 gives a decode step alone beside it).

The window's mean batch stands for every execution's (exact where its
executions are alike)."""

from chipbench.files import load_module

_NEEDS = ("loop_tokens.count", "inference_count", "execution_count")


def read(ctx: dict):
    trace, delta = ctx.get("trace"), ctx.get("stats_delta")
    if not trace or not delta or not all(delta.get(k) for k in _NEEDS):
        return None
    cfg = ctx["config"]
    work = load_module("flop_counts", cfg["flops"])
    batch = delta["inference_count"] / delta["execution_count"]
    moved = delta["execution_count"] * work.generation_bytes(cfg, batch)
    peak = ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"]
    return 100.0 * moved / (trace["busy_s"] * peak)
