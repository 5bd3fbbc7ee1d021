"""The sparse-attention kernel's share of the chip's peak, counted on the
**chosen** pairs: the operations its calls in the traced window needed
(``flop_counts/<config>.kernel_work(cfg, "dsa_attention")``: QK^T and P·v
over sum_t min(t + 1, index_topk) pairs a head; one call a block a prompt)
over what the chip could have done in the kernel's own seconds.  The kernel
walks the causal half whatever was chosen (a masked form), so it reads at
most the chosen share of the causal pairs (44% at 8,192 and 2,048).

Its seconds are the ``_dsa_call`` ops of the line's ``breakdown``, counted
bucket by bucket and only where the line accounts for every call, as
``mla_attention_roofline.py`` counts the latent-attention kernel: an op's
output ``[heads x batch rows, S, Dv]`` names its bucket, the counters say
how many steps ran at each bucket, and a bucket counts where its ops'
seconds, in units of the shortest (a block outside a scan: one call a
step), come to more than all but one of the blocks.  Otherwise ``None``."""

import re

from chipbench.files import load_module

_OP = re.compile(r"^_dsa_call[.\w]* [a-z0-9]+\[(\d+),")


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
    blocks = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    seconds = {}  # bucket -> the seconds of each of its ops on the line
    for rows, s in calls:
        if rows % heads == 0:
            seconds.setdefault(rows // heads, []).append(s)
    steps = load_module("layer_metrics", "mla_attention_roofline"
                        ).steps_by_bucket(
        cfg["served"]["batch_buckets"], delta["execution_count"],
        delta.get("bucket_rows.count", delta["inference_count"]))
    if not seconds or not steps:
        return None
    work = load_module("flop_counts", cfg["flops"]).kernel_work(
        cfg, "dsa_attention")
    flops = kernel_s = 0.0
    for bucket, ops in seconds.items():
        if steps.get(bucket) and sum(ops) / min(ops) > blocks - 1:
            flops += steps[bucket] * blocks * bucket * work["flops"]
            kernel_s += sum(ops)
    if not kernel_s:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (kernel_s * peak)
