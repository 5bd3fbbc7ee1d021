"""Passes the generation ran for each token it committed: Δ``denoise_passes``
(passes x sequences, the commit passes among them, counted on the device) /
Δ``denoise_tokens``.  The published loop reads 1.25 (4 denoising passes and
a commit pass for a block of 4); a commit pass folded into the next block's
first pass would read 1.0, a threshold that ends blocks early less."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("denoise_tokens.count") \
            or "denoise_passes.count" not in delta:
        return None
    return delta["denoise_passes.count"] / delta["denoise_tokens.count"]
