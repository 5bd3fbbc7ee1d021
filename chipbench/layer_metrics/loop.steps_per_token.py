"""Loop steps a token took before it left the stack: Δ``loop_steps`` (summed
over the tokens of every sequence, counted on the device) / Δ``loop_tokens``.
The published model reads ``total_ut_steps`` = 4.0 (``early_exit_threshold``
1: every token takes every step); a threshold under 1 that lets tokens leave
early reads less."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("loop_tokens.count") \
            or "loop_steps.count" not in delta:
        return None
    return delta["loop_steps.count"] / delta["loop_tokens.count"]
