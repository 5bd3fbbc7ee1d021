"""Mean time a sequence waited in the batcher's queue, each from its own
enqueue until its batch formed, on the server's clock (``queue_member``;
``scheduler.queue_ms`` charges the first member's wait to every member)."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("queue_member.count"):
        return None
    return delta["queue_member.ns"] / delta["queue_member.count"] / 1e6
