"""Numbers for a prefill whose answer is the next token, its row of logits
and every token's experts: each sampled request's row against the
reference's, the reference routing every token as the program routed it.

On random weights the router's scores of the k-th and the next expert lie
within bfloat16's rounding of each other; a program that rounds takes
another expert than float32 would, and what a token carries reaches the
last row through every later key, by more than the rounding compared here.
So ``reference.replay(ids, routes)`` runs the prompts in float32 **routing
every token of every expert layer to the program's experts** (``ROUTES``),
weighting them by its own scores, and gives the last position's row.  Then

* ``logit_rel_l2_median``: the median over the rows (one a request) of the
  L2 distance over the reference's L2 norm.  Rounding moves every row a
  little, and this is what a lower precision moves;
* ``logit_rel_l2_worst``: the largest; an answer that went to the wrong
  caller reads 1.4;
* ``route_shortfall_worst``: over every token and expert layer, the share
  by which the least of the program's experts lies under the reference's
  own k-th in score + bias (0 where they agree; rounding leaves hundredths;
  an expert chosen at random reads 0.3 and more);
* ``token_inconsistent``: requests whose ``TOKENS`` is not the arg-max of
  the ``LOGITS`` row returned with it, or whose ``ROUTES`` name an expert
  twice or none that exists.  Exact: the limit is 0.  This ties what was
  compared to what was served.

Limits: ``limits`` of the configuration, under the four names (``PERF.md``
§2 has the readings they were set from)."""

import numpy as np

MEDIAN, WORST = "logit_rel_l2_median", "logit_rel_l2_worst"
SHORTFALL, INCONSISTENT = "route_shortfall_worst", "token_inconsistent"


def inconsistent(tokens, logits, routes, cfg: dict) -> bool:
    chosen = np.sort(np.asarray(routes), axis=-1)
    if chosen.min() < 0 or chosen.max() >= cfg["num_experts"] \
            or (np.diff(chosen, axis=-1) == 0).any():
        return True
    return any(int(t) != int(np.argmax(row)) for t, row in zip(tokens, logits))


def compare(cfg: dict, inputs: list, answers: list, reference) -> dict:
    """``inputs`` and ``answers`` hold one ``{tensor name: array}`` for
    each sampled request; ``reference`` has ``replay(ids, routes)``."""
    median = worst = short = bad = None
    if inputs:
        ids = np.concatenate([x[cfg["served"]["inputs"][0]["name"]]
                              for x in inputs])
        tokens, got, routes = (
            np.concatenate([np.asarray(a[name]) for a in answers])
            for name in ("TOKENS", "LOGITS", "ROUTES"))
        bad = sum(inconsistent(t, rows, r, cfg)
                  for t, rows, r in zip(tokens, got, routes))
        replayed = reference.replay(
            ids, np.clip(routes, 0, cfg["num_experts"] - 1))
        want = np.asarray(replayed["logits"], np.float64)
        got = got.astype(np.float64).reshape(want.shape)
        values = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        values = np.where(np.isfinite(values), values, np.inf)
        median, worst = float(np.median(values)), float(np.max(values))
        short = float(np.max(replayed["route_shortfall"]))
    return {name: {"value": value, "limit": cfg["limits"][name]}
            for name, value in ((MEDIAN, median), (WORST, worst),
                                (SHORTFALL, short), (INCONSISTENT, bad))}
