"""The windowed attention kernel's share of the chip's peak: the operations
its calls in the traced window needed (``flop_counts/<config>.kernel_work(cfg,
"window_attention")``: QK^T and P·v over the pairs a sliding layer attends;
one call a sliding layer a prompt) over what the chip could have done in the
kernel's own seconds.  The kernel walks whole 512 x 512 blocks from the
window's left edge to the diagonal (81,264,640 pairs a head at 32,768 and
2,048, for 65,012,736 attended), so it reads at most 80% of an MXU-bound
walk.

Its seconds are the ``_window_call`` ops of the line's ``breakdown``; an
op's output ``[heads x batch rows, S, Dv]`` names its bucket, and the
counters say how many steps ran at each (``mla_attention_roofline.
steps_by_bucket``).  The program runs every layer in one scan, so the kernel
is one op a bucket, called by each sliding layer: a bucket counts where its
op is on the line.  Otherwise ``None``."""

import re

from chipbench.files import load_module

_OP = re.compile(r"^_window_call[.\w]* [a-z0-9]+\[(\d+),")


def read(ctx: dict):
    trace, delta = ctx.get("trace"), ctx.get("stats_delta")
    if not trace or not delta or not delta.get("execution_count"):
        return None
    calls = [(int(m.group(1)), s) for m, s in (
        (_OP.match(name), s)
        for name, s in trace.get("breakdown", {}).get("device_ops", []))
        if m and s > 0]
    if not calls:  # a program without the kernel, as the parent is
        return None
    cfg = ctx["config"]
    heads = cfg["num_attention_heads"]
    sliding = load_module("flop_counts", cfg["flops"]).kinds(cfg).count(
        "sliding_attention")
    seconds = {}  # bucket -> the seconds of its ops on the line
    for rows, s in calls:
        if rows % heads == 0:
            seconds[rows // heads] = seconds.get(rows // heads, 0.0) + s
    steps = load_module("layer_metrics", "mla_attention_roofline"
                        ).steps_by_bucket(
        cfg["served"]["batch_buckets"], delta["execution_count"],
        delta.get("bucket_rows.count", delta["inference_count"]))
    if not seconds or not steps:
        return None
    work = load_module("flop_counts", cfg["flops"]).kernel_work(
        cfg, "window_attention")
    flops = kernel_s = 0.0
    for bucket, s in seconds.items():
        if steps.get(bucket):
            flops += steps[bucket] * sliding * bucket * work["flops"]
            kernel_s += s
    if not kernel_s:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (kernel_s * peak)
