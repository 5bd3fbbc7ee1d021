"""Sequences whose request came back inside the window, over the window's
seconds: all the work over all the time.  Failed requests count in
``failed``, not here."""


def read(run: dict) -> float:
    rec, (start, close) = run["records"], run["window"]
    inside = (rec[:, 3] > 0) & (rec[:, 2] <= close)
    return float(inside.sum() * run["request_batch"] / (close - start))
