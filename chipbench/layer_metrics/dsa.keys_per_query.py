"""Keys an attention query read: Δ``dsa_pairs`` ((query, key) pairs the
sparse attention attended, counted on the device from the indexers' bit
planes) / Δ``dsa_queries`` (query rows x attention blocks).  At a prompt of
8,192 and ``index_topk`` 2,048 every block reads sum_t min(t + 1, 2048) /
8192 = 1,792.125 keys a query; dense causal attention would read 4,096.5."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("dsa_queries.count") \
            or "dsa_pairs.count" not in delta:
        return None
    return delta["dsa_pairs.count"] / delta["dsa_queries.count"]
