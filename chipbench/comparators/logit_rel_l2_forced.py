"""Numbers for a model that generates greedily, one token a step: its logits
are compared at the program's own tokens.

On random weights the largest of 49,152 logits changes on bfloat16's
rounding, so a sentence the reference generated by itself would part from
the program's within a few tokens and every later number would compare two
different sentences.  The reference is therefore **teacher-forced**:
``reference.replay(ids, tokens)`` runs one full causal forward over the
prompt and the program's own ``TOKENS`` but the last, and gives the logits
and the exit probabilities at the two positions whose rows the program
returns (``LOGITS [2, V]``, ``EXIT_PDF [2, T]``): the prompt's last, which
chose the first new token, and the last but one of the whole, which chose
the last token and has read every cached position of every loop step.  Each
sampled request gives two relative L2 distances, and then

* ``logit_rel_l2_median``: the median over all rows.  Rounding moves every
  row a little; this is what a lower precision moves;
* ``logit_rel_l2_worst``: the largest; an answer that went to the wrong
  caller reads 1.4;
* ``exit_pdf_abs_worst``: the largest absolute difference of an exit
  probability.  At a threshold of 1 the gate moves no logit, so this alone
  holds it to the reference;
* ``token_inconsistent``: requests whose first or last ``TOKENS`` is not the
  arg-max of the ``LOGITS`` row returned with it, or whose ``EXIT_PDF`` rows
  do not sum to 1.  Exact: the limit is 0.  This is what ties the tokens to
  the logits compared;
* ``logit_rel_l2``: one L2 over all rows together, reported under a limit
  only a gross fault passes (the benchmark's tests ask every configuration
  for it).

Limits: ``limits`` of the configuration, under the five names
(``PERF.md`` §2 has the readings they were set from)."""

import numpy as np

MEDIAN, WORST = "logit_rel_l2_median", "logit_rel_l2_worst"
PDF, INCONSISTENT, JOINT = ("exit_pdf_abs_worst", "token_inconsistent",
                            "logit_rel_l2")


def inconsistent(tokens, logits, pdf) -> bool:
    return (int(tokens[0]) != int(np.argmax(logits[0]))
            or int(tokens[-1]) != int(np.argmax(logits[1]))
            or not np.allclose(np.sum(pdf, axis=-1), 1.0, atol=1e-4))


def compare(cfg: dict, inputs: list, answers: list, reference) -> dict:
    """``inputs`` and ``answers`` hold one ``{tensor name: array}`` for
    each sampled request; ``reference`` has ``replay(ids, tokens)``."""
    median = worst = joint = bad = pdf_off = None
    if inputs:
        ids = np.concatenate([x[cfg["served"]["inputs"][0]["name"]]
                              for x in inputs])
        tokens, got, pdf = (
            np.concatenate([np.asarray(a[name]) for a in answers])
            for name in ("TOKENS", "LOGITS", "EXIT_PDF"))
        bad = sum(inconsistent(t, rows, p)
                  for t, rows, p in zip(tokens, got, pdf))
        replayed = reference.replay(
            ids, np.clip(tokens, 0, cfg["vocab_size"] - 1))
        want = np.asarray(replayed["logits"], np.float64)
        got = got.astype(np.float64).reshape(want.shape)
        values = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        values = np.where(np.isfinite(values), values, np.inf)
        median, worst = float(np.median(values)), float(np.max(values))
        joint = float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))
        joint = joint if np.isfinite(joint) else float("inf")
        off = np.abs(pdf.astype(np.float64).reshape(
            replayed["exit_pdf"].shape) - replayed["exit_pdf"])
        pdf_off = float(np.max(np.where(np.isfinite(off), off, np.inf)))
    return {name: {"value": value, "limit": cfg["limits"][name]}
            for name, value in ((MEDIAN, median), (WORST, worst),
                                (PDF, pdf_off), (INCONSISTENT, bad),
                                (JOINT, joint))}
