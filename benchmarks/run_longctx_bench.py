#!/usr/bin/env python3
"""BASELINE row 6/11: long-context document scorer latency over HTTP.

Measures ``longctx_tpu`` p50 at the active preset's sequence length with the
pallas flash kernel, and (optionally) with XLA fused attention for the same
request.  The kernel choice binds at trace time and a chip belongs to one
process at a time, so with ``--compare-xla`` this process stays off JAX and
runs the two variants as children, one after the other.

    TRITON_TPU_LONGCTX_PRESET=xl python benchmarks/run_longctx_bench.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(n: int = 8) -> dict:
    import triton_client_tpu.http as httpclient
    from triton_client_tpu.models import language, zoo
    from triton_client_tpu.server import ModelRegistry
    from triton_client_tpu.server.testing import ServerHarness

    registry = ModelRegistry()
    zoo.register_all(registry)
    S = language.longctx_seq_len()
    with ServerHarness(registry) as h:
        with httpclient.InferenceServerClient(h.http_url) as c:
            toks = np.random.default_rng(0).integers(
                0, 255, (1, S), dtype=np.int32)
            inp = httpclient.InferInput("TOKENS", [1, S], "INT32")
            inp.set_data_from_numpy(toks)
            c.infer("longctx_tpu", [inp])  # compile outside the clock
            lats = []
            for _ in range(n):
                t0 = time.perf_counter()
                c.infer("longctx_tpu", [inp])
                lats.append(time.perf_counter() - t0)
    return {
        "seq_len": S,
        "flash": os.environ.get("TRITON_TPU_FLASH", "1") != "0",
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 1),
        "min_ms": round(float(np.min(lats)) * 1e3, 1),
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("-n", type=int, default=8)
    parser.add_argument("--compare-xla", action="store_true",
                        help="measure with the flash kernel and then with "
                        "TRITON_TPU_FLASH=0, each in its own subprocess")
    args = parser.parse_args()

    if not args.compare_xla:
        print(json.dumps(measure(args.n)))
        return
    import subprocess

    for flash in ("1", "0"):
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "-n", str(args.n)],
            env=dict(os.environ, TRITON_TPU_FLASH=flash), check=True)


if __name__ == "__main__":
    main()
