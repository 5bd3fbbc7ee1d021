#!/usr/bin/env python3
"""Measure the genai-perf metric set against the live ``llama_decode`` model.

Run on the chip (defaults) or the CPU (JAX_PLATFORMS=cpu).  Prints the
full report per concurrency level; the aggregate numbers extend BASELINE.md
row 7 with TTFT/ITL percentiles.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from triton_client_tpu import genai_perf
from triton_client_tpu.models import zoo
from triton_client_tpu.server import ModelRegistry
from triton_client_tpu.server.testing import ServerHarness


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--concurrency", default="1,8",
                        help="comma-separated levels")
    parser.add_argument("--output-tokens", type=int, default=16)
    parser.add_argument("--num-requests", type=int, default=8)
    parser.add_argument("--model", default="llama_decode")
    parser.add_argument("--generate-model", default="llama_generate",
                        help="model for the generate_stream (SSE) sweep")
    args = parser.parse_args()

    registry = ModelRegistry()
    zoo.register_all(registry)
    with ServerHarness(registry) as h:
        # warm: one generation outside the clock (XLA prefill+step compile)
        genai_perf.profile(h.grpc_url, args.model, concurrency=1,
                           output_tokens=1, num_requests=1)
        for level in [int(c) for c in args.concurrency.split(",")]:
            report = genai_perf.profile(
                h.grpc_url, args.model, concurrency=level,
                output_tokens=args.output_tokens,
                num_requests=max(args.num_requests, level))
            print(json.dumps(report))
        # server-side loop over the generate extension (SSE): ITL here is
        # on-device step time, not a client round trip per token.  Its own
        # warm pass: the generate path compiles the independent prefill/step
        # pair, which the decode warm-up above only covers in independent
        # decode mode.
        # output_tokens=2 so the decode `step` compiles too (a 1-token
        # generation is prefill-only)
        genai_perf.profile_generate(
            h.http_url, args.generate_model, concurrency=1,
            output_tokens=2, num_requests=1)
        for level in [int(c) for c in args.concurrency.split(",")]:
            report = genai_perf.profile_generate(
                h.http_url, args.generate_model, concurrency=level,
                output_tokens=args.output_tokens,
                num_requests=max(args.num_requests, level))
            print(json.dumps(report))


if __name__ == "__main__":
    main()
