"""Mean sequences per model execution, as the dynamic batcher formed them."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta["execution_count"]:
        return None
    return delta["inference_count"] / delta["execution_count"]
