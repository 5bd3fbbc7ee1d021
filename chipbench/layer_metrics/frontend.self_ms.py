"""The frontend's self time, a sequence: its handler's span (``request``:
entry, before the message is decoded, until the response is built and
handed to the transport) less what its children cover — the member's queue
wait, batch assembly and the host's step.  What is left is decode,
admission, waits for the event loop, split, serialise and hand-off."""

from chipbench.files import load_module


def _mean_ms(delta: dict, entry: str):
    count = delta.get(entry + ".count")
    return delta[entry + ".ns"] / count / 1e6 if count else None


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta:
        return None
    request, *children = (
        _mean_ms(delta, "request"), _mean_ms(delta, "queue_member"),
        _mean_ms(delta, "batch_assembly"),
        load_module("layer_metrics", "model_step.host_ms").read(ctx))
    if request is None or None in children:
        return None
    return request - sum(children)
