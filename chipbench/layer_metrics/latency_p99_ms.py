"""The 99th percentile of the latency of the requests that the traced run's
window sent and got back wholly outside the profiler's time (from the call
that started it until it had written its file), on the client's clock.

It is not held to a bound: on this program the tail is made by one or two
stalls of some 130 ms a window in which nothing completes, and swings with
their number (``PERF.md``, sections 2 and 5)."""

import numpy as np


def read(ctx: dict):
    rec, profiled = ctx.get("records"), ctx.get("profiled")
    if rec is None or profiled is None:
        return None
    began, ended = profiled
    keep = (rec[:, 3] > 0) & ((rec[:, 2] < began) | (rec[:, 1] > ended))
    if not keep.any():
        return None
    return float(np.percentile((rec[keep, 2] - rec[keep, 1]) * 1e3, 99))
