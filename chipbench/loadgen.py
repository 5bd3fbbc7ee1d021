"""The load generator's process: a client that never imports JAX.

A user's client does not share the server's interpreter, so the load runs
here, in a child of ``run.py``.  This file is dispatch only: the traffic
file names its generator (``chipbench/generators/<generator>.py``, a class
``Generator(url, cfg, traffic, seed, make_request)`` with ``run(seconds)``)
and the configuration its request-maker
(``chipbench/request_makers/<served.requests>.py``, ``make(cfg, seed, index,
batch)`` giving ``{input name: array}``).  The child says ``ready``, waits
for ``go`` on standard input, lets the generator drive the window and wait
for every answer (up to a minute past the close), and writes one ``.npz`` to
standard output: a record (index, sent, done, ok, ...) of every request and
the answers of a sample of the finished ones, drawn from the seed, for the
comparison that decides ``correct``.
"""

from __future__ import annotations

import argparse
import gc
import io
import json
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench.files import load_module  # noqa: E402


def sample_indices(seed: int, finished: np.ndarray, count: int) -> np.ndarray:
    """``count`` of the finished requests' indices, drawn from the seed."""
    finished = np.sort(np.asarray(finished))
    if len(finished) <= count:
        return finished
    rng = np.random.default_rng([seed, 0x5A3B1E])
    return np.sort(rng.choice(finished, size=count, replace=False))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    make = load_module("request_makers", cfg["served"]["requests"]).make
    generator = load_module("generators", traffic["generator"]).Generator(
        args.url, cfg, traffic, args.seed, make)
    out = sys.stdout.buffer
    out.write(b"ready\n")
    out.flush()
    if sys.stdin.readline().strip() != "go":
        return 1
    # the client's collector would stop all callers at once for tens of
    # milliseconds, at moments of its own choosing: not in the window
    gc.freeze()
    gc.disable()
    ran = generator.run(args.seconds)
    gc.enable()
    if "jax" in sys.modules:
        raise RuntimeError("the load generator imported jax")
    rec = np.array(sorted(ran["records"]), dtype=np.float64)
    rec = rec.reshape(len(ran["records"]), -1) if len(rec) else np.zeros((0, 4))
    finished = rec[rec[:, 3] > 0, 0].astype(np.int64)
    picked = sample_indices(args.seed, finished,
                            int(traffic["check_requests"]))
    arrays = {f"answer.{k}.{name}": array
              for k, i in enumerate(picked)
              for name, array in ran["answers"][int(i)].items()}
    buf = io.BytesIO()
    np.savez(buf, records=rec, window=np.array([ran["start"], ran["close"]]),
             never=np.array([ran["never"]]), sample=picked,
             first_error=np.array([ran["first_error"] or ""]), **arrays)
    out.write(buf.getvalue())
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
