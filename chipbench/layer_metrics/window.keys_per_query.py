"""Keys a query of a sliding-window layer attended: Δ``window_pairs`` ((query,
key) pairs the windowed layers attended, counted on the device from the
window's mask) / Δ``window_queries`` (query rows x windowed layers).  At a
prompt of 32,768 and a window of 2,048 a layer attends sum_t min(t + 1,
2048) = 65,012,736 pairs, 1,984.03 a query; dense causal attention would
read 16,384.5."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("window_queries.count") \
            or "window_pairs.count" not in delta:
        return None
    return delta["window_pairs.count"] / delta["window_queries.count"]
