"""Numbers for a model that generates: its logits are compared at the
program's own state, since the trajectory hangs on discrete choices.

On random weights the largest of 151,936 logits, the most confident of four
positions and the 8th of 128 experts change on bfloat16's rounding, so a
trajectory the reference generated by itself would part from the program's
within a block and every later number would compare two different
sentences.  The reference is therefore **teacher-forced**:
``reference.replay(ids, tokens, commit_pass, routes)`` rebuilds, from the
tokens and the order the program returned, the state the program was in at
two moments (block 0's pass 0: the prompt and a block of MASK; the last
block's last pass: everything generated but the last position), routes the
answering position as the program routed it (``ROUTES``) and gives the
logits there, which the program returns too (``LOGITS [2, V]``).  Each
sampled request gives two relative L2 distances, and then

* ``logit_rel_l2_median``: the median over all rows.  Rounding moves every
  row a little; this is what a lower precision moves;
* ``logit_rel_l2_worst``: the largest; an answer that went to the wrong
  caller reads 1.4;
* ``route_shortfall_worst``: what keeps the routing that was taken over
  honest: the largest, over rows and layers, share by which the least
  probable expert the program chose lies under the reference's own 8th
  probability (0 where they agree; rounding leaves hundredths; an expert
  chosen at random reads 0.5 and more);
* ``commit_inconsistent``: requests whose ``TOKENS`` at the two returned
  positions are not the arg-max of the ``LOGITS`` returned, whose
  ``COMMIT_PASS`` is not, in every block, a permutation of the passes (under
  the static rule; with a threshold: a pass of the block's range), or whose
  ``ROUTES`` name an expert twice or none that exists.  Exact: the limit is
  0.  This is what ties the tokens to the logits compared;
* ``logit_rel_l2``: one L2 over all rows together, reported under a limit
  only a gross fault passes (the benchmark's tests ask every configuration
  for it).

Limits: ``limits`` of the configuration, under the five names
(``PERF.md`` §2 has the readings they were set from)."""

import numpy as np

MEDIAN, WORST = "logit_rel_l2_median", "logit_rel_l2_worst"
INCONSISTENT, JOINT = "commit_inconsistent", "logit_rel_l2"
SHORTFALL = "route_shortfall_worst"


def returned_positions(commit_pass, block: int):
    """The two positions (within the answer) whose logits a request
    returns: the first committed at block 0's pass 0, the first committed
    at the last block's last pass."""
    first, last = commit_pass[:block], commit_pass[-block:]
    return (int(np.argmax(first == 0)),
            len(commit_pass) - block + int(np.argmax(last == last.max())))


def inconsistent(tokens, commit_pass, logits, routes, cfg: dict) -> bool:
    gen = cfg["assumed"]["generation"]
    B, T = gen["block_length"], gen["denoising_steps"]
    chosen = np.sort(np.asarray(routes), axis=-1)
    if chosen.min() < 0 or chosen.max() >= cfg["num_experts"] \
            or (np.diff(chosen, axis=-1) == 0).any():
        return True
    blocks = np.asarray(commit_pass).reshape(-1, B)
    if gen["confidence_threshold"] is None:
        steps = np.repeat(np.arange(T), B // T)
        if not (np.sort(blocks, axis=-1) == steps).all():
            return True
    elif blocks.min() < 0 or blocks.max() >= T:
        return True
    at = returned_positions(np.asarray(commit_pass), B)
    return any(int(tokens[p]) != int(np.argmax(row))
               for p, row in zip(at, logits))


def compare(cfg: dict, inputs: list, answers: list, reference) -> dict:
    """``inputs`` and ``answers`` hold one ``{tensor name: array}`` for
    each sampled request; ``reference`` has ``replay(ids, tokens,
    commit_pass, routes)``."""
    median = worst = joint = bad = short = None
    if inputs:
        ids = np.concatenate([x[cfg["served"]["inputs"][0]["name"]]
                              for x in inputs])
        tokens, when, got, routes = (
            np.concatenate([np.asarray(a[name]) for a in answers])
            for name in ("TOKENS", "COMMIT_PASS", "LOGITS", "ROUTES"))
        bad = sum(inconsistent(t, w, rows, r, cfg)
                  for t, w, rows, r in zip(tokens, when, got, routes))
        replayed = reference.replay(
            ids, tokens, when, np.clip(routes, 0, cfg["num_experts"] - 1))
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
