"""How unevenly the router fills the held experts: the fullest held expert's
rows over the mean held expert's, per expert layer and execution (1.0 is
even; the grouped matmul's time follows the sum, a deployment's exchange
the busiest)."""


def read(ctx: dict):
    delta = ctx.get("stats_delta")
    if not delta or not delta.get("expert_rows.count"):
        return None
    held = ctx["config"]["n_routed_experts"]
    return (held * delta["expert_rows_busiest.count"]
            / delta["expert_rows.count"])
