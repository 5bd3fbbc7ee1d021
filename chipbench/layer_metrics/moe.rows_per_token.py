"""(token, expert) pairs the expert layers computed for each token they ran:
the held experts' share of the routing.  Even routing gives
experts-per-token x held / all (8 x 12/384 = 0.25 in ``kimi_k2``); the dense
form the zoo had would read the number of held experts."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("expert_tokens.count"):
        return None
    return delta["expert_rows.count"] / delta["expert_tokens.count"]
