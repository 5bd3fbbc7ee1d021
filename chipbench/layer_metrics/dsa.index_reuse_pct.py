"""Share of attention blocks that ran no indexer of their own and attended
over the keys the full block before them chose: 100 x Δ``index_reused``
(query rows x shared blocks) / Δ``dsa_queries`` (query rows x attention
blocks).  ``hy4_preview`` reads 50.0: layers 2-4 of six blocks."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("dsa_queries.count") \
            or "index_reused.count" not in delta:
        return None
    return 100.0 * delta["index_reused.count"] / delta["dsa_queries.count"]
