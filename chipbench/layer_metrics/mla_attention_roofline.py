"""The latent-attention kernel's share of the chip's peak: the operations
its calls in the traced window needed (``flop_counts/kimi_k2.kernel_work``,
the causal half; one call a layer a prompt) over what the chip could have
done in the kernel's own seconds.  The kernel is bound by the MXU (2,048
FLOP a byte), so the roofline is the FLOP peak.

Its seconds are the ``_flash_call`` ops of the line's ``breakdown``, which
holds the ten longest ops only, so the calls are counted bucket by bucket
and only where the line accounts for them:

* an op's output is ``[heads x batch rows, S, Dv]``: that names the bucket
  its step ran at;
* how many steps ran at each bucket follows from the counters
  (``execution_count`` steps ran ``bucket_rows`` rows between them: with two
  buckets that decides it);
* every call of the kernel at one bucket takes the same time, and an op the
  ten left out is shorter than each they kept; so a bucket's ops are all on
  the line when their seconds, in units of the shortest (a layer outside
  the scan: one call a step), count more than all but one of the layers.  A
  bucket with an op missing is left out of both sides of the ratio.

Where no bucket's calls can be accounted for, the reader returns ``None``
and does not guess."""

import re

from chipbench.files import load_module

_OP = re.compile(r"^_flash_call[.\w]* [a-z0-9]+\[(\d+),")


def steps_by_bucket(buckets, executions: int, rows: int):
    """``{bucket: steps}`` where ``executions`` steps of the given buckets
    ran ``rows`` rows (padding included) between them; ``None`` where the
    two counts do not decide it."""
    for bucket in buckets:
        if rows == bucket * executions:
            return {bucket: executions}
    if len(buckets) == 2:
        low, high = sorted(buckets)
        n_high, rest = divmod(rows - low * executions, high - low)
        if rest == 0 and 0 <= n_high <= executions:
            return {low: executions - n_high, high: n_high}
    return None


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
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    seconds = {}  # bucket -> the seconds of each of its ops on the line
    for rows, s in calls:
        if rows % heads == 0:
            seconds.setdefault(rows // heads, []).append(s)
    steps = steps_by_bucket(
        cfg["served"]["batch_buckets"], delta["execution_count"],
        delta.get("bucket_rows.count", delta["inference_count"]))
    if not seconds or not steps:
        return None
    work = load_module("flop_counts", cfg["flops"]).kernel_work(
        cfg, "mla_attention")
    flops = kernel_s = 0.0
    for bucket, ops in seconds.items():
        if steps.get(bucket) and sum(ops) / min(ops) > layers - 1:
            flops += steps[bucket] * layers * bucket * work["flops"]
            kernel_s += sum(ops)
    if not kernel_s:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (kernel_s * peak)
