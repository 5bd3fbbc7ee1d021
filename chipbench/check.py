"""The comparison that decides ``correct``: dispatch only.

What the timed path answered, for a sample of the window's requests drawn
from the seed, against the plain reference run over the same inputs.  The
configuration names its request-maker (``served.requests``, which makes the
sampled requests' inputs again), its reference and its comparator
(``compare``), each a file of its own; the comparator returns every number
compared beside its limit (``PERF.md`` gives the readings the limits were
set from).  The run is correct when every number is at or under its limit,
every request that was sent came back, and none failed.
"""

from __future__ import annotations

from .files import load_module


def compare(cfg: dict, traffic: dict, seed: int, sample, answers: list,
            reference) -> dict:
    """``{name: {"value", "limit"}}`` over the sampled requests."""
    make = load_module("request_makers", cfg["served"]["requests"]).make
    batch = int(traffic["request_batch"])
    inputs = [make(cfg, seed, int(i), batch) for i in sample]
    return load_module("comparators", cfg["compare"]).compare(
        cfg, inputs, answers, reference)


def verdict(compared: dict, attempted: int, failed: int, never: int) -> bool:
    if failed or never or not attempted or not compared:
        return False
    for entry in compared.values():
        value, limit = entry["value"], entry["limit"]
        if value is None or limit is None or not value <= limit:
            return False
    return True
