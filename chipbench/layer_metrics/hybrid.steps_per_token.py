"""Decode steps a generated token cost: Δ``decode_steps`` (steps x the
sequences they ran for, counted on the device) / Δ``decode_tokens`` (tokens
those steps yielded).  The published greedy loop reads 1.0: one pass of the
whole stack, through both kinds of state, a token; a step that yielded
several (drafted and verified) would read less."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("decode_tokens.count") \
            or "decode_steps.count" not in delta:
        return None
    return delta["decode_steps.count"] / delta["decode_tokens.count"]
