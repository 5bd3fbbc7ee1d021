"""Share of the traced window in which no operation ran on the device."""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
