"""Of every second of the traced window, the milliseconds in which the
collector or a late event loop held requests of this model (``pause``: every
collection and every loop probe that fired 10 ms late or more, charged while
the model had a request pending; no millisecond twice)."""


def read(ctx: dict):
    delta, trace = ctx.get("stats_delta"), ctx.get("trace")
    if not delta or not trace or "pause.ns" not in delta:
        return None
    return delta["pause.ns"] / 1e6 / trace["window_s"]
