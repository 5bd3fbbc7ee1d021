"""The step as the host lives it, a sequence: the hop to the executor
thread, the call into the model (the enqueue of its programs) and the wait
until every output is on the host (``executor_wait`` + ``dispatch`` +
``device_wait``), on the server's clock."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("device_wait.count"):
        return None
    ns = (delta["executor_wait.ns"] + delta["dispatch.ns"]
          + delta["device_wait.ns"])
    return ns / delta["device_wait.count"] / 1e6
