#!/usr/bin/env python3
"""Run the BASELINE.md measurement matrix (rows 1-5) on the current host.

Starts the in-process serving harness (all zoo models, including the
BASELINE models: resnet50, bert_large, ensemble_llama) and measures each
configured row with the perf_analyzer-equivalent or a purpose-built driver.
Writes ``benchmarks/BASELINE_RESULTS.json`` (git-ignored: a run-time
output, stamped with the device JAX reports) and prints the markdown rows.
The harness is in-process, so this script owns the chip while it runs.

Run on the chip:     python benchmarks/run_baseline.py
Quick CPU smoke:     python benchmarks/run_baseline.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _warm(client, httpclient, model, name, shape, dtype, buckets):
    """One blocking infer per preferred batch bucket so XLA compiles outside
    any measurement window (bench.py learned this the hard way in round 1)."""
    for b in buckets:
        arr = np.zeros((b, *shape), dtype)
        inp = httpclient.InferInput(name, [b, *shape],
                                    {"int32": "INT32", "float32": "FP32"}[arr.dtype.name])
        inp.set_data_from_numpy(arr)
        t0 = time.time()
        client.infer(model, [inp])
        print(f"  warm {model} b={b}: {time.time() - t0:.1f}s", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny windows + tiny llama preset (CPU CI smoke)")
    ap.add_argument("--measure-ms", type=int, default=5000)
    ap.add_argument("--rows", nargs="+", type=int, default=None,
                    help="run only these BASELINE row numbers (default all)")
    args = ap.parse_args()

    def row_on(n):
        return args.rows is None or n in args.rows

    if args.smoke:
        os.environ.setdefault("TRITON_TPU_LLAMA_PRESET", "tiny")
        args.measure_ms = min(args.measure_ms, 1500)

    import triton_client_tpu.http as httpclient
    from triton_client_tpu.models import language, zoo
    from triton_client_tpu.server.registry import ModelRegistry
    from triton_client_tpu.server.testing import ServerHarness

    registry = ModelRegistry()
    zoo.register_all(registry)
    harness = ServerHarness(registry)
    harness.start()
    grpc_url = f"127.0.0.1:{harness.grpc_port}"
    results = {}
    t_start = time.time()

    def solo_probe(model, arrays, n=3):
        """Median solo-request latency on an (assumed) idle link."""
        import triton_client_tpu.grpc as pm
        from triton_client_tpu.utils import np_to_triton_dtype

        samples = []
        with pm.InferenceServerClient(grpc_url) as probe:
            for _ in range(n):
                req_inputs = []
                for name, arr in arrays.items():
                    dt = ("BYTES" if arr.dtype == np.object_
                          else np_to_triton_dtype(arr.dtype))
                    inp = pm.InferInput(name, list(arr.shape), dt)
                    inp.set_data_from_numpy(arr)
                    req_inputs.append(inp)
                t0 = time.time()
                probe.infer(model, req_inputs)
                samples.append(time.time() - t0)
        return float(np.median(samples))

    def drain(model, arrays, floor):
        """Block until the abandoned tail of the previous closed-loop level
        has cleared the device link: two consecutive solo probes near the
        PRE-congestion floor (captured before the first level — a floor
        taken from post-congestion samples mistakes "uniformly congested"
        for "drained"; r3 lesson, same fix as bench.py's quiesce)."""
        import triton_client_tpu.grpc as pm
        from triton_client_tpu.utils import np_to_triton_dtype

        with pm.InferenceServerClient(grpc_url) as probe:
            deadline = time.time() + 120.0
            last_two = []
            while time.time() < deadline:
                req_inputs = []
                for name, arr in arrays.items():
                    dt = ("BYTES" if arr.dtype == np.object_
                          else np_to_triton_dtype(arr.dtype))
                    inp = pm.InferInput(name, list(arr.shape), dt)
                    inp.set_data_from_numpy(arr)
                    req_inputs.append(inp)
                t0 = time.time()
                probe.infer(model, req_inputs)
                last_two = (last_two + [time.time() - t0])[-2:]
                if len(last_two) == 2 and max(last_two) < 2.0 * floor:
                    return
                time.sleep(0.3)

    def sweep(model, levels, shm="none", streaming=False, batch=1):
        rows = []
        from triton_client_tpu.perf_analyzer import (_make_data,
                                                     _resolve_model,
                                                     run_level)
        import triton_client_tpu.grpc as pm

        meta = pm.InferenceServerClient(grpc_url)
        inputs, outputs, max_batch = _resolve_model(meta, "grpc", model, "")
        meta.close()
        arrays = _make_data(inputs, {}, batch, max_batch,
                            np.random.default_rng(0))
        floor = solo_probe(model, arrays)
        for level in levels:
            res = run_level("grpc", grpc_url, model, "", level, arrays,
                            outputs, shm, 1 << 22, args.measure_ms / 1000.0,
                            streaming=streaming)
            if res["errors"]:
                print(f"  !! {model} c={level}: {res['errors']} errors: "
                      f"{res['first_error']}", flush=True)
            rows.append(res)
            print(f"  {model} c={level} shm={shm}{' stream' if streaming else ''}: "
                  f"{res['throughput']:.1f} infer/s p50={res['p50_us']/1e3:.1f}ms "
                  f"p99={res['p99_us']/1e3:.1f}ms", flush=True)
            # backlog from this level must not starve the next
            drain(model, arrays, floor)
        best = max(rows, key=lambda r: r["throughput"])
        return {"levels": rows, "best": best}

    # cold XLA compiles of the big models can take minutes — warm-up
    # infers must not trip the client's 60s default read timeout.
    warm_client = httpclient.InferenceServerClient(
        harness.http_url, network_timeout=600.0)

    # ---- row 1: simple + system shm --------------------------------------
    if row_on(1):
        print("row 1: simple (system shm)", flush=True)
        results["row1_simple_sysshm"] = sweep("simple", [1, 8], shm="system")

    # ---- row 2: resnet50 over gRPC ---------------------------------------
    if row_on(2):
        print("row 2: resnet50 (async gRPC)", flush=True)
        # concurrency c coalesces into batches the batcher pads to the next
        # preferred bucket — warm every bucket a sweep level can hit, or the
        # measurement window sits behind a fresh XLA compile.
        buckets = [1, 4, 8, 16, 32] if not args.smoke else [1]
        if args.smoke:
            import triton_client_tpu.models.vision as vision
            vision._STAGES = ((1, 8), (1, 8), (1, 8), (1, 8))
        _warm(warm_client, httpclient, "resnet50", "INPUT", (3, 224, 224),
              np.float32, buckets)
        results["row2_resnet50_grpc"] = sweep(
            "resnet50", [1, 4, 8] if not args.smoke else [1])

    # ---- row 3: xla shm on dense_tpu -------------------------------------
    if row_on(3):
        print("row 3: dense_tpu (xla shm)", flush=True)
        _warm(warm_client, httpclient, "dense_tpu", "INPUT", (512,), np.float32,
              [1, 8] if args.smoke else [1, 8, 16, 32, 64])
        results["row3_dense_xlashm"] = sweep("dense_tpu", [1, 8], shm="xla")

    # ---- row 4: bert_large, streaming gRPC + xla shm ---------------------
    if row_on(4):
        print("row 4: bert_large (streaming gRPC)", flush=True)
        if not args.smoke:
            _warm(warm_client, httpclient, "bert_large", "INPUT_IDS",
                  (language.BERT_SEQ_LEN,), np.int32, [1, 2, 4, 8, 16, 32])
            # concurrency must reach max_batch_size (32) for the dynamic
            # batcher to build MFU-deep batches.  WIRE outputs: the MFU
            # number must count device-synchronous completions — xla-shm
            # responses return at dispatch time, so that sweep (kept below
            # as a dispatch/latency metric) overcounts compute ~2x
            # (benchmarks/BERT_PROFILE.md).
            # deep levels keep the device queue non-empty and let the
            # batcher build max_batch=32 executions
            results["row4_bert_stream"] = sweep(
                "bert_large", [32, 64, 128], shm="none", streaming=True)
            best = results["row4_bert_stream"]["best"]
            results["row4_bert_stream"]["mfu"] = language.serving_mfu(
                best["throughput"], language.BERT_LARGE,
                language.BERT_SEQ_LEN, head_cols=language.BERT_HEAD_COLS)
            results["row4_bert_stream"]["tokens_per_sec"] = (
                best["throughput"] * language.BERT_SEQ_LEN)
            # zero-copy response path: NOT an MFU number — demonstrates the
            # xla-shm serving property (responses decoupled from device
            # completion; the shm consumer synchronizes when it reads)
            results["row4_bert_xlashm_dispatch"] = sweep(
                "bert_large", [16], shm="xla", streaming=True)

    # ---- row 5: llama ensemble generation over the stream ----------------
    if row_on(5):
        print("row 5: ensemble_llama sequence/stream generation", flush=True)
        import triton_client_tpu.grpc as grpcclient

        # warm (first token pays compile)
        inp = httpclient.InferInput("TEXT", [1, 1], "BYTES")
        inp.set_data_from_numpy(np.array([[b"warmup"]], dtype=object))
        t0 = time.time()
        warm_client.infer("ensemble_llama", [inp])
        print(f"  warm ensemble_llama: {time.time() - t0:.1f}s", flush=True)

        def gen_loop(seq_id, steps, prompt):
            """Closed-loop stream generation: one request per token, 128-byte
            window, OUT_TEXT appended — the single definition of the protocol
            shared by the serial and concurrent row-5 measurements.  Returns
            (generation wall seconds, per-token latencies); the timed window
            spans first request → last response, excluding client/stream
            setup and teardown (the historical measurement methodology)."""
            done_q: "queue.Queue" = queue.Queue()
            text = prompt
            lats = []
            with grpcclient.InferenceServerClient(grpc_url) as c:
                c.start_stream(
                    callback=lambda result, error: done_q.put((result, error)))
                t_gen = time.time()
                for step in range(steps):
                    ginp = grpcclient.InferInput("TEXT", [1, 1], "BYTES")
                    ginp.set_data_from_numpy(np.array([[text[-128:]]], dtype=object))
                    t0 = time.time()
                    c.async_stream_infer("ensemble_llama", [ginp],
                                         sequence_id=seq_id,
                                         sequence_start=(step == 0),
                                         sequence_end=(step == steps - 1))
                    res, err = done_q.get(timeout=300)
                    if err is not None:
                        raise RuntimeError(err)
                    lats.append(time.time() - t0)
                    text += bytes(
                        np.asarray(res.as_numpy("OUT_TEXT")).reshape(-1)[0])
                wall_s = time.time() - t_gen
                c.stop_stream()
            return wall_s, lats

        gen_steps = 8 if args.smoke else 64
        wall, lat = gen_loop(1, gen_steps, b"In a hole in the ground there lived")
        cfg = language._llama_cfg()
        flops_tok = language.forward_flops_per_token(cfg, language.LLAMA_SEQ_LEN)
        # each generated token re-runs the full 128-token window forward
        window_flops = flops_tok * language.LLAMA_SEQ_LEN
        results["row5_llama_ensemble"] = {
            "preset_params": language.n_params(cfg),
            "gen_tokens": gen_steps,
            "tokens_per_sec": gen_steps / wall,
            "stream_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "stream_p99_ms": float(np.percentile(lat, 99) * 1e3),
            "mfu": (gen_steps / wall) * window_flops / language.V5E_PEAK_FLOPS,
        }
        r5 = results["row5_llama_ensemble"]
        print(f"  llama({r5['preset_params']/1e9:.2f}B params): "
              f"{r5['tokens_per_sec']:.2f} tok/s p50={r5['stream_p50_ms']:.0f}ms "
              f"MFU={r5['mfu']*100:.1f}%", flush=True)

        # concurrent generation: N independent streams; the ensemble's member
        # executions coalesce through llama_tpu's dynamic batcher, so aggregate
        # tokens/sec scales past the serial one-request-per-token rate
        _warm(warm_client, httpclient, "llama_tpu", "TOKENS",
              (language.LLAMA_SEQ_LEN,), np.int32,
              [1, 2, 4, 8] if not args.smoke else [1, 2])
        import threading

        n_streams = 2 if args.smoke else 8
        conc_steps = 4 if args.smoke else 32
        worker_errors = []
        t_conc = time.time()

        def guarded_worker(widx):
            try:
                gen_loop(2000 + widx, conc_steps,
                         f"stream {widx}: in the beginning".encode())
            except Exception as exc:  # noqa: BLE001 — surfaced after join
                worker_errors.append((widx, exc))

        threads = [threading.Thread(target=guarded_worker, args=(w,), daemon=True)
                   for w in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        if worker_errors:
            raise RuntimeError(f"concurrent-stream workers failed: {worker_errors}")
        if any(t.is_alive() for t in threads):
            raise RuntimeError("concurrent-stream worker hung past 600s join")
        conc_wall = time.time() - t_conc
        # every worker completed exactly conc_steps tokens (guards above raise
        # on any failure or hang)
        total_toks = n_streams * conc_steps
        results["row5_llama_concurrent"] = {
            "streams": n_streams,
            "gen_tokens": total_toks,
            "tokens_per_sec": total_toks / conc_wall,
            "mfu": (total_toks / conc_wall) * window_flops / language.V5E_PEAK_FLOPS,
        }
        r5c = results["row5_llama_concurrent"]
        print(f"  llama concurrent x{n_streams}: {r5c['tokens_per_sec']:.2f} "
              f"tok/s aggregate MFU={r5c['mfu']*100:.1f}%", flush=True)

    warm_client.close()
    harness.stop()
    # per-row provenance: every row records which session and which
    # device measured it (partial --rows runs merge into the file without
    # masquerading as one session)
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    session = {
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "device": device,
        "session_wall_s": round(time.time() - t_start, 1),
    }
    for key, val in results.items():
        if isinstance(val, dict):
            val["session"] = session
    results["wall_s"] = time.time() - t_start
    results["device"] = device

    # smoke output must never clobber a real TPU measurement (same
    # convention as run_decode_bench.py)
    name = ("BASELINE_RESULTS_SMOKE.json" if args.smoke
            else "BASELINE_RESULTS.json")
    out = os.path.join(REPO, "benchmarks", name)
    if args.rows is not None and os.path.exists(out):
        # partial run: merge over the existing matrix, don't clobber rows
        # that weren't measured
        with open(out) as f:
            merged = json.load(f)
        merged.update(results)
        results = merged
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nwrote {out}")

    # markdown rows for BASELINE.md
    def fmt(r):
        b = r["best"]
        return (f"{b['throughput']:.1f} infer/s, p50 {b['p50_us']/1e3:.1f} ms, "
                f"p99 {b['p99_us']/1e3:.1f} ms (c={b['concurrency']})")

    print("\n--- BASELINE.md rows ---")
    if "row1_simple_sysshm" in results:
        print(f"| 1 | simple, system shm | "
              f"{fmt(results['row1_simple_sysshm'])} |")
    if "row2_resnet50_grpc" in results:
        print(f"| 2 | resnet50, async gRPC | "
              f"{fmt(results['row2_resnet50_grpc'])} |")
    if "row3_dense_xlashm" in results:
        print(f"| 3 | dense_tpu, xla shm | "
              f"{fmt(results['row3_dense_xlashm'])} |")
    if "row4_bert_stream" in results:
        r4 = results["row4_bert_stream"]
        print(f"| 4 | bert_large, streaming gRPC (wire) | {fmt(r4)}, "
              f"{r4['tokens_per_sec']:.0f} tok/s, MFU {r4['mfu']*100:.1f}% |")
    if "row4_bert_xlashm_dispatch" in results:
        r4d = results["row4_bert_xlashm_dispatch"]["best"]
        print(f"| 4b | bert_large xla-shm zero-copy response rate "
              f"(dispatch, NOT MFU) | {r4d['throughput']:.1f} resp/s, "
              f"p50 {r4d['p50_us']/1e3:.1f} ms |")
    if ("row5_llama_ensemble" in results
            and "row5_llama_concurrent" in results):
        r5 = results["row5_llama_ensemble"]
        r5c = results["row5_llama_concurrent"]
        print(f"| 5 | ensemble_llama stream gen | "
              f"{r5['tokens_per_sec']:.2f} tok/s, "
              f"stream p50 {r5['stream_p50_ms']:.0f} ms, "
              f"MFU {r5['mfu']*100:.1f}%; "
              f"x{r5c['streams']} streams: {r5c['tokens_per_sec']:.2f} "
              f"tok/s, MFU {r5c['mfu']*100:.1f}% |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
