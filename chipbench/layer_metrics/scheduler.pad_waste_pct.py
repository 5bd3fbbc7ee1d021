"""Share of the rows the model ran that the batcher padded on to reach a
bucket: 100 x (1 - sequences answered / rows executed, ``bucket_rows``)."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("bucket_rows.count"):
        return None
    return 100.0 * (1.0 - delta["inference_count"]
                    / delta["bucket_rows.count"])
