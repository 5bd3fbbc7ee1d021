"""Numbers for a model whose attention reads the keys an indexer chose and
whose answer carries a draft token: each sampled request's two logit rows
against the reference's, told the program's choices, and how far those
choices lie from the reference's own.

On random weights the index scores near a row's ``index_topk``-th, and the
router scores near the k-th expert's, lie within bfloat16's rounding of each
other; a program that rounds takes other keys and experts than float32
would, and each exchange moves a row by more than the rounding compared
here.  So ``reference.replay(ids, first_tokens, chosen, routes)`` runs the
prompts in float32 **attending over the keys the program's full indexers
chose** (``CHOSEN``, the bit planes its attention read) **and routing every
token to the program's experts** (``ROUTES``), and gives the main head's
last-position row and the multi-token-prediction module's, the latter
**teacher-forced** on the program's own ``TOKENS[0]``.  Then

* ``logit_rel_l2_median``: the median over every row (two a request) of the
  L2 distance over the reference's L2 norm.  Rounding moves every row a
  little, and this is what a lower precision moves;
* ``logit_rel_l2_worst``: the largest; an answer that went to the wrong
  caller reads 1.4;
* ``index_shortfall_worst``: over every query of every full indexer and
  every request, how far the least-scored of the program's keys lies under
  the reference's own ``min(t + 1, index_topk)``-th index score, in
  standard deviations of the row's causal scores (0 where they agree;
  rounding leaves a few hundredths; keys chosen by another position's
  scores, or at random, read one and more);
* ``route_shortfall_worst``: over every token and expert block, the share
  by which the least of the program's experts lies under the reference's
  own k-th in score + bias;
* ``token_inconsistent``: requests whose ``TOKENS`` are not the arg-max of
  the ``LOGITS`` rows returned with them, whose ``CHOSEN`` row of a query
  ``t`` holds other than ``min(t + 1, index_topk)`` keys or a key past
  ``t``, or whose ``ROUTES`` name an expert twice or none that exists.
  Exact: the limit is 0.  This ties what was compared to what was served.

Limits: ``limits`` of the configuration, under the five names (``PERF.md``
§2 has the readings they were set from)."""

import numpy as np

MEDIAN, WORST = "logit_rel_l2_median", "logit_rel_l2_worst"
INDEX, ROUTE = "index_shortfall_worst", "route_shortfall_worst"
INCONSISTENT = "token_inconsistent"


def _bad_choice(planes, k: int) -> bool:
    """``planes [S,W]``: a query's row of bit planes, key ``s`` at bit ``s
    // W`` of word ``s % W``."""
    bits = np.asarray(planes).view(np.uint32)
    S, W = bits.shape
    t = np.arange(S)[:, None]
    count = np.zeros(S, np.int64)
    for p in range(32):
        on = ((bits >> np.uint32(p)) & 1) == 1
        if (on & (p * W + np.arange(W)[None, :] > t)).any():
            return True
        count += on.sum(-1)
    return bool((count != np.minimum(np.arange(S) + 1, k)).any())


def inconsistent(tokens, logits, chosen, routes, cfg: dict) -> bool:
    if any(int(t) != int(np.argmax(row)) for t, row in zip(tokens, logits)):
        return True
    experts = np.sort(np.asarray(routes), axis=-1)
    total = cfg["deployment"]["published"]["n_routed_experts"]
    if experts.min() < 0 or experts.max() >= total \
            or (np.diff(experts, axis=-1) == 0).any():
        return True
    return any(_bad_choice(planes, cfg["index_topk"]) for planes in chosen)


def compare(cfg: dict, inputs: list, answers: list, reference) -> dict:
    """``inputs`` and ``answers`` hold one ``{tensor name: array}`` for
    each sampled request; ``reference`` has ``replay(ids, first_tokens,
    chosen, routes)``."""
    median = worst = index = route = bad = None
    if inputs:
        ids = np.concatenate([x[cfg["served"]["inputs"][0]["name"]]
                              for x in inputs])
        tokens, got, chosen, routes = (
            np.concatenate([np.asarray(a[name]) for a in answers])
            for name in ("TOKENS", "LOGITS", "CHOSEN", "ROUTES"))
        bad = sum(inconsistent(t, rows, c, r, cfg)
                  for t, rows, c, r in zip(tokens, got, chosen, routes))
        total = cfg["deployment"]["published"]["n_routed_experts"]
        replayed = reference.replay(
            ids, np.clip(tokens[:, 0], 0, cfg["vocab_size"] - 1), chosen,
            np.clip(routes, 0, total - 1))
        want = np.asarray(replayed["logits"], np.float64)
        got = got.astype(np.float64).reshape(want.shape)
        values = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        values = np.where(np.isfinite(values), values, np.inf)
        median, worst = float(np.median(values)), float(np.max(values))
        index = float(np.max(replayed["index_shortfall"]))
        route = float(np.max(replayed["route_shortfall"]))
    return {name: {"value": value, "limit": cfg["limits"][name]}
            for name, value in ((MEDIAN, median), (WORST, worst),
                                (INDEX, index), (ROUTE, route),
                                (INCONSISTENT, bad))}
