"""One number for a model that answers with logits: the L2 distance of all
the sampled requests' logits from the reference's, over the L2 norm of the
reference's.  Its limit is the configuration's ``limits.logit_rel_l2``."""

import numpy as np

NUMBER = "logit_rel_l2"


def compare(cfg: dict, inputs: list, answers: list, reference) -> dict:
    """``inputs`` and ``answers`` hold one ``{tensor name: array}`` for
    each sampled request; ``reference`` has ``outputs(inputs)``."""
    value = None
    if inputs:
        name = cfg["served"]["outputs"][0]["name"]
        joined = {k: np.concatenate([x[k] for x in inputs]) for k in inputs[0]}
        want = np.asarray(reference.outputs(joined)[name], np.float32)
        got = np.concatenate([a[name] for a in answers]).astype(np.float32)
        value = float(np.sqrt(((got.reshape(want.shape) - want) ** 2).sum()
                              / (want ** 2).sum()))
        if not np.isfinite(value):
            value = float("inf")
    return {NUMBER: {"value": value, "limit": cfg["limits"][NUMBER]}}
