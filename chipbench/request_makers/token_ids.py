"""Requests of a token model: ``[batch, seq_len]`` ids drawn uniformly from
the vocabulary.  Request ``index`` under ``seed`` is the same wherever it is
made: in the client, and as the reference's input."""

import numpy as np


def make(cfg: dict, seed: int, index: int, batch: int) -> dict:
    served = cfg["served"]
    rng = np.random.default_rng([seed, index])
    ids = rng.integers(0, cfg["vocab_size"],
                       size=(batch, served["seq_len"]), dtype=np.int32)
    return {served["inputs"][0]["name"]: ids}
