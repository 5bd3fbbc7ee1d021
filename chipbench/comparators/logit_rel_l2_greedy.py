"""Numbers for a model that generates greedily, one token a step, through
state that its prefill hands to its decode steps: its logits are compared
at the program's own tokens.

On random weights the largest of 65,536 logits and the 4th of 32 experts
change on bfloat16's rounding, so a sentence the reference generated by
itself would part from the program's within a few tokens.  The reference is
therefore **teacher-forced**: ``reference.replay(ids, tokens, routes)`` runs
one full causal forward over the prompt and the program's own ``TOKENS`` but
the last, routes every position as the program routed it (``ROUTES [P + G -
1, expert layers, k]``) and gives the logits at the three positions whose
rows the program returns (``LOGITS [3, V]``): the prompt's
last, which chose the first new token; the next, the first decode step,
which reads what the prefill handed over (the prompt's keys and every conv
layer's last rows) and nothing else, so that a state handed over wrongly
shows here at full size; and the last but one of the whole, which chose the
last token and has read every cached key.  Each sampled request gives three
relative L2 distances, and then

* ``logit_rel_l2_median``: the median over all rows.  Rounding moves every
  row a little; this is what a lower precision moves;
* ``logit_rel_l2_worst``: the largest; an answer that went to the wrong
  caller reads 1.4;
* ``route_shortfall_worst``: what keeps the routing that was taken over
  honest: the largest, over every token and layer, share by which the
  least of the experts the program chose lies under the reference's own
  k-th in score + bias (0 where they agree; rounding leaves hundredths; an expert
  chosen at random reads 0.3 and more);
* ``token_inconsistent``: requests whose first, second or last ``TOKENS`` is
  not the arg-max of the ``LOGITS`` row returned with it, or whose
  ``ROUTES`` name an expert twice or none that exists.  Exact: the limit is
  0.  This is what ties the tokens to the logits compared;
* ``logit_rel_l2``: one L2 over all rows together, reported under a limit
  only a gross fault passes.

Limits: ``limits`` of the configuration, under the five names
(``PERF.md`` §2 has the readings they were set from)."""

import numpy as np

MEDIAN, WORST = "logit_rel_l2_median", "logit_rel_l2_worst"
SHORTFALL, INCONSISTENT, JOINT = ("route_shortfall_worst",
                                  "token_inconsistent", "logit_rel_l2")


def inconsistent(tokens, logits, routes, cfg: dict) -> bool:
    chosen = np.sort(np.asarray(routes), axis=-1)
    if chosen.min() < 0 or chosen.max() >= cfg["num_experts"] \
            or (np.diff(chosen, axis=-1) == 0).any():
        return True
    at = (0, min(1, len(tokens) - 1), len(tokens) - 1)
    return any(int(tokens[p]) != int(np.argmax(row))
               for p, row in zip(at, logits))


def compare(cfg: dict, inputs: list, answers: list, reference) -> dict:
    """``inputs`` and ``answers`` hold one ``{tensor name: array}`` for
    each sampled request; ``reference`` has ``replay(ids, tokens,
    routes)``."""
    median = worst = joint = bad = short = None
    if inputs:
        ids = np.concatenate([x[cfg["served"]["inputs"][0]["name"]]
                              for x in inputs])
        tokens, got, routes = (
            np.concatenate([np.asarray(a[name]) for a in answers])
            for name in ("TOKENS", "LOGITS", "ROUTES"))
        bad = sum(inconsistent(t, rows, r, cfg)
                  for t, rows, r in zip(tokens, got, routes))
        replayed = reference.replay(
            ids, np.clip(tokens, 0, cfg["vocab_size"] - 1),
            np.clip(routes, 0, cfg["num_experts"] - 1))
        want = np.asarray(replayed["logits"], np.float64)
        got = got.astype(np.float64).reshape(want.shape)
        values = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        values = np.where(np.isfinite(values), values, np.inf)
        median, worst = float(np.median(values)), float(np.max(values))
        joint = float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))
        joint = joint if np.isfinite(joint) else float("inf")
        short = float(np.max(replayed["route_shortfall"]))
    return {name: {"value": value, "limit": cfg["limits"][name]}
            for name, value in ((MEDIAN, median), (WORST, worst),
                                (SHORTFALL, short), (INCONSISTENT, bad),
                                (JOINT, joint))}
