#!/usr/bin/env python3
"""BASELINE row 7: KV-cache incremental decode (`llama_decode`).

Measures closed-loop generation over a gRPC sequence stream — serial
(tok/s, ms/token) and N concurrent streams (aggregate tok/s) — against the
in-process harness, same methodology as rows 1-5 (benchmarks/run_baseline.py).

    python benchmarks/run_decode_bench.py            # full (TPU: 1b preset)
    python benchmarks/run_decode_bench.py --smoke    # CPU CI smoke
"""

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def gen_loop(grpc_url, grpcclient, S, seq_id, prompt, steps):
    """Prefill once, then feed each produced token back as a [1] step."""
    done: "queue.Queue" = queue.Queue()
    lats = []
    with grpcclient.InferenceServerClient(grpc_url) as c:
        c.start_stream(callback=lambda result, error: done.put((result, error)))
        win = np.zeros(S, np.int32)
        b = np.frombuffer(prompt[-S:], np.uint8)
        win[S - len(b):] = b
        inp = grpcclient.InferInput("TOKENS", [S], "INT32")
        inp.set_data_from_numpy(win)
        c.async_stream_infer("llama_decode", [inp], sequence_id=seq_id,
                             sequence_start=True)
        res, err = done.get(timeout=2400)
        if err is not None:
            raise RuntimeError(err)
        for i in range(steps):
            tok = np.asarray(res.as_numpy("NEXT_TOKEN")).astype(
                np.int32).reshape(1)
            ninp = grpcclient.InferInput("TOKENS", [1], "INT32")
            ninp.set_data_from_numpy(tok)
            t0 = time.time()
            c.async_stream_infer("llama_decode", [ninp], sequence_id=seq_id,
                                 sequence_end=(i == steps - 1))
            res, err = done.get(timeout=1200)
            if err is not None:
                raise RuntimeError(err)
            lats.append(time.time() - t0)
        c.stop_stream()
    return lats


def measure_mode(mode, args, slots, chunk):
    """One harness per decode mode (DecodeModel reads the env at init)."""
    os.environ["TRITON_TPU_DECODE_MODE"] = mode
    os.environ["TRITON_TPU_DECODE_SLOTS"] = str(slots)
    os.environ["TRITON_TPU_PREFILL_CHUNK"] = str(chunk if mode == "batched"
                                                 else 0)
    from triton_client_tpu.models import language, zoo
    from triton_client_tpu.server.registry import ModelRegistry
    from triton_client_tpu.server.testing import ServerHarness
    import triton_client_tpu.grpc as grpcclient

    registry = ModelRegistry()
    zoo.register_all(registry)
    h = ServerHarness(registry)
    h.start()
    S = language.LLAMA_SEQ_LEN
    out = {"mode": mode, "slots": slots,
           "prefill_chunk": chunk if mode == "batched" else 0}
    try:
        # serial (first sequence pays prefill+step compiles; timing uses
        # per-step latencies, not the compile)
        steps = 4 if args.smoke else 24
        gen_loop(h.grpc_url, grpcclient, S, 700,
                 b"In a hole in the ground there lived", steps)
        lats = gen_loop(h.grpc_url, grpcclient, S, 701,
                        b"It was the best of times", steps)  # warm pass
        out["serial"] = {
            "tokens_per_sec": 1.0 / float(np.mean(lats)),
            "ms_per_token_p50": float(np.percentile(lats, 50) * 1e3),
        }
        print(f"[{mode}] serial: "
              f"{out['serial']['tokens_per_sec']:.2f} tok/s, p50 "
              f"{out['serial']['ms_per_token_p50']:.0f} ms/token",
              flush=True)

        conc_steps = 4 if args.smoke else 16
        out["concurrent"] = []
        for n_streams in args.streams:
            if n_streams > slots and mode == "batched":
                # starts beyond the slot pool are rejected; skip
                continue
            errors = []

            def worker(w):
                try:
                    gen_loop(h.grpc_url, grpcclient, S, 800 + w,
                             f"stream {w}: in the beginning".encode(),
                             conc_steps)
                except Exception as exc:  # noqa: BLE001 — after join
                    errors.append((w, exc))

            t0 = time.time()
            threads = [threading.Thread(target=worker, args=(w,),
                                        daemon=True)
                       for w in range(n_streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=2400)
            if errors:
                raise RuntimeError(f"decode workers failed: {errors}")
            if any(t.is_alive() for t in threads):
                raise RuntimeError("decode worker hung")
            wall = time.time() - t0
            total = n_streams * (conc_steps + 1)  # +1 = prefill's token
            out["concurrent"].append(
                {"streams": n_streams, "tokens_per_sec": total / wall})
            print(f"[{mode}] x{n_streams} streams: {total / wall:.1f} "
                  f"tok/s aggregate", flush=True)
    finally:
        h.stop()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny preset + short loops (CPU CI)")
    ap.add_argument("--modes", nargs="+",
                    default=["independent"],
                    choices=["independent", "batched"],
                    help="decode modes to sweep. Default sweeps only "
                    "'independent' (the server default); in this "
                    "client-side closed loop a batched tick is a "
                    "per-cohort sync point.  Which mode wins on the chip "
                    "is not measured on today's code (ROADMAP S1); pass "
                    "--modes independent batched to compare")
    ap.add_argument("--streams", nargs="+", type=int, default=None,
                    help="concurrency sweep (default 8 16 32; smoke: 2)")
    ap.add_argument("--slots", type=int, default=32,
                    help="decode slots for batched mode")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk tokens for batched mode (0=off)")
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("TRITON_TPU_LLAMA_PRESET", "tiny")
        if args.streams is None:
            args.streams = [2]
        args.slots = min(args.slots, 4)
    elif args.streams is None:
        args.streams = [8, 16, 32]

    results = {"sweep": [measure_mode(m, args, args.slots, args.chunk)
                         for m in args.modes]}
    # smoke output must never clobber a real TPU measurement
    name = "DECODE_RESULTS_SMOKE.json" if args.smoke else "DECODE_RESULTS.json"
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
