"""Mean time a sequence waited in the batcher's queue before its batch
formed, on the server's clock."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta["queue.count"]:
        return None
    return delta["queue.ns"] / delta["queue.count"] / 1e6
