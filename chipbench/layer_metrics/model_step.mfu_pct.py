"""The whole step's share of the chip's peak: the operations the sequences
answered in the traced window needed (the benchmark's own count, padding
rows not counted) over what the chip could have done while it was busy."""


def read(ctx: dict):
    trace, delta = ctx.get("trace"), ctx.get("stats_delta")
    if not trace or not delta or not delta["inference_count"]:
        return None
    flops = delta["inference_count"] * ctx["flops_per_inference"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (trace["busy_s"] * peak)
