#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on
the chip.

Drives the main path once, through the entry points a user would call, on
ONE TPU chip at the full width of the zoo's models (``llama`` preset ``1b``,
``bert_large`` at S=384, ``longctx_tpu`` ``base`` at S=4096), with random
weights made from seeds:

* **Child A** — ``python -m triton_client_tpu.server --zoo``, default
  environment.  Over the wire only: readiness and the device the server
  owns, ``simple`` over gRPC and HTTP, ``dense_tpu`` through
  ``perf_analyzer.run_level`` with system and then xla shared memory (the
  two-process xla-shm path), ``bert_large``, ``longctx_tpu`` (the request
  that compiles ``ops/flash_attention.py`` with Mosaic), ``llama_generate``
  over HTTP ``generate_stream`` and ``llama_decode`` over the gRPC sequence
  stream; then every model used is checked ready by name, SIGTERM, drain,
  exit code 0.
* **Child B** — the same server with ``TRITON_TPU_DECODE_MODE=batched`` and
  ``TRITON_TPU_QUANT=int8``: the batched decode engine (donated slot slabs,
  fused ticks) and ``bert_large`` int8 (the request that compiles
  ``ops/int8_matmul.py``), whose logits must stay close to Child A's bf16.
* **Child C** — co-located zero copy: one process running ``ServerHarness``
  plus the cudashm example sequence and a ``dense_tpu`` xla-shm infer on
  device-resident regions, and each pallas kernel, compiled, against its
  jnp reference at the served shape.

One process holds the chip at a time: this parent never imports JAX (it is
asserted at the end), children run one after another, and each is started
with ``JAX_PLATFORMS=tpu,cpu`` so a TPU that cannot be opened is an error,
never JAX's CPU fallback (a bare ``tpu`` drops ``jax.devices("cpu")``, which
the zoo's ``KIND_CPU`` models place on).  No ``TRITON_TPU_*_PRESET`` is set:
the models must pick their full width by themselves.

Every phase failure is fatal.  The last line of stdout is one JSON object
with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
— printed only when every phase passed on a TPU; the line before it,
``[summary] {...}``, carries versions, presets, the compile cache directory,
totals and the phases passed.  Without an accelerator (or outside the repo)
the script exits non-zero and prints no result.

``--debug-cpu`` runs the same flow on the CPU at the tiny presets, for
working on this script in a sandbox without a chip.  It never prints the
result line and always exits non-zero: it is not a chip result.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import queue
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
#: The platform list children start with.  ``tpu,cpu``: a failed TPU init
#: raises, and ``jax.devices("cpu")`` stays available for KIND_CPU models.
CHIP_PLATFORMS = "tpu,cpu"
#: What ``_env_preset`` must resolve by itself on the chip.
FULL_WIDTH = {"platform": "tpu", "llama": "1b", "longctx": "base"}
LLAMA_1B_VOCAB = 128256
LONGCTX_BASE_SEQ = 4096
GEN_TOKENS = 16
PROMPT = "In a hole in the ground there lived"


class SmokeError(Exception):
    """A phase failed; the run exits non-zero."""


def free_port() -> int:
    # server.testing.free_port would import the server (and JAX) here
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platforms: str, extra=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not (k.startswith("TRITON_TPU_") and k.endswith("_PRESET"))}
    env["JAX_PLATFORMS"] = platforms
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"  # the parent reads startup lines from the log
    env.update(extra or {})
    return env


def log_tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def timed(fn, steady: int):
    """(last result, first-request seconds — compile included —, steady
    seconds per request over ``steady`` further calls)."""
    t0 = time.perf_counter()
    out = fn()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steady):
        out = fn()
    steady_s = (time.perf_counter() - t0) / max(1, steady)
    return out, {"first_s": round(first_s, 3),
                 "steady_ms": round(steady_s * 1e3, 2), "steady_n": steady}


class Smoke:
    def __init__(self, debug_cpu: bool):
        self.debug_cpu = debug_cpu
        self.platforms = "cpu" if debug_cpu else CHIP_PLATFORMS
        self.phases = []
        self.device = None
        self.presets = None
        self.cache_dirs = set()
        self.bert_bf16_logits = None

    def phase(self, name: str, fn) -> None:
        t0 = time.perf_counter()
        info = fn() or {}
        self.passed(name, time.perf_counter() - t0, info)

    def passed(self, name: str, wall_s: float, info=None) -> None:
        wall = round(wall_s, 2)
        self.phases.append({"phase": name, "wall_s": wall, **(info or {})})
        print(f"[phase] {name}: ok {wall}s {json.dumps(info or {})}",
              flush=True)


# -- the server children (A and B) -------------------------------------------

class ServerChild:
    """``python -m triton_client_tpu.server --zoo`` on free ports, sole owner
    of the chip while it lives.  Leaving the block cleanly sends SIGTERM and
    requires a drained exit code 0; leaving it on an error kills the child."""

    def __init__(self, tag: str, platforms: str, extra_env=None):
        self.tag = tag
        self.http_port, self.grpc_port, self.metrics_port = (
            free_port(), free_port(), free_port())
        self.http_url = f"127.0.0.1:{self.http_port}"
        self.grpc_url = f"127.0.0.1:{self.grpc_port}"
        self.log_path = os.path.join(OUT_DIR, f"server_{tag}.log")
        self._env = child_env(platforms, extra_env)
        self.proc = None
        self.start_s = None
        self.drain_s = None

    def __enter__(self) -> "ServerChild":
        os.makedirs(OUT_DIR, exist_ok=True)
        t0 = time.perf_counter()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "triton_client_tpu.server", "--zoo",
                 "--host", "127.0.0.1",
                 "--http-port", str(self.http_port),
                 "--grpc-port", str(self.grpc_port),
                 "--metrics-port", str(self.metrics_port)],
                env=self._env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
        try:
            self._wait_ready(deadline_s=300.0)
        except BaseException:
            self._kill()
            raise
        self.start_s = round(time.perf_counter() - t0, 2)
        return self

    def _wait_ready(self, deadline_s: float) -> None:
        deadline = time.monotonic() + deadline_s
        url = f"http://{self.http_url}/v2/health/ready"
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                tail = log_tail(self.log_path)
                hint = ""
                if "Unable to initialize backend" in tail:
                    hint = (" — JAX found no TPU device to open "
                            f"(JAX_PLATFORMS={self._env['JAX_PLATFORMS']})")
                raise SmokeError(
                    f"server {self.tag} exited rc={rc} before it was ready"
                    f"{hint}:\n{tail}")
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    if r.status == 200:
                        return
            except OSError:
                pass
            time.sleep(0.25)
        raise SmokeError(f"server {self.tag} not ready after {deadline_s}s:\n"
                         f"{log_tail(self.log_path)}")

    def startup_line(self, prefix: str) -> str:
        with open(self.log_path, errors="replace") as f:
            for line in f:
                if line.startswith(prefix):
                    return line[len(prefix):].strip()
        raise SmokeError(f"server {self.tag} log has no '{prefix}' line:\n"
                         f"{log_tail(self.log_path)}")

    def get_json(self, path: str):
        with urllib.request.urlopen(
                f"http://{self.http_url}{path}", timeout=60) as r:
            return json.loads(r.read())

    def get_text(self, path: str) -> str:
        with urllib.request.urlopen(
                f"http://{self.http_url}{path}", timeout=60) as r:
            return r.read().decode()

    def _kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self._kill()
            return
        t0 = time.perf_counter()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            self._kill()
            raise SmokeError(f"server {self.tag} did not drain and exit "
                             f"within 90s of SIGTERM:\n"
                             f"{log_tail(self.log_path)}")
        if rc != 0:
            raise SmokeError(f"server {self.tag} exited rc={rc} after "
                             f"SIGTERM:\n{log_tail(self.log_path)}")
        self.drain_s = time.perf_counter() - t0


def check_device_and_presets(smoke: Smoke, srv: ServerChild) -> dict:
    """What the server owns, read from the server: HBM rows of
    /v2/debug/device_stats, the HBM-headroom gauge, and its startup lines."""
    m = re.match(r"platform=(\S+) kind='([^']*)' count=(\d+)",
                 srv.startup_line("device: "))
    if m is None:
        raise SmokeError("unparseable 'device:' startup line")
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    presets = dict(kv.split("=", 1) for kv in
                   srv.startup_line("transformer presets: ").split())
    cache_dir = srv.startup_line("compile cache: ")
    smoke.cache_dirs.add(cache_dir)
    info = {"device": device, "presets": presets, "compile_cache": cache_dir,
            "server_start_s": srv.start_s}
    if not smoke.debug_cpu:
        if device["platform"] != "tpu":
            raise SmokeError(f"server runs on {device}, not a TPU")
        for key, want in FULL_WIDTH.items():
            if presets.get(key) != want:
                raise SmokeError(
                    f"preset {key} resolved to {presets.get(key)!r}, the "
                    f"chip run needs {want!r} (all: {presets})")
        hbm = srv.get_json("/v2/debug/device_stats").get("hbm", {})
        if len(hbm) != device["count"] or not all(
                k.startswith("tpu:") and "bytes_limit" in v
                for k, v in hbm.items()):
            raise SmokeError(f"device_stats HBM rows do not show the TPU: "
                             f"{hbm}")
        rows = [ln for ln in srv.get_text("/metrics").splitlines()
                if ln.startswith("nv_mem_hbm_headroom_bytes")]
        if not rows or float(rows[0].split()[-1]) <= 0:
            raise SmokeError("nv_mem_hbm_headroom_bytes absent or zero: "
                             "HBM admission is not live")
        info["hbm_limit_bytes"] = hbm["tpu:0"]["bytes_limit"]
        info["hbm_headroom_bytes"] = int(float(rows[0].split()[-1]))
    if smoke.device is None:
        smoke.device, smoke.presets = device, presets
    elif (smoke.device, smoke.presets) != (device, presets):
        raise SmokeError(f"server {srv.tag} sees {device} {presets}, an "
                         f"earlier child saw {smoke.device} {smoke.presets}")
    return info


def check_simple(srv: ServerChild) -> dict:
    """examples/simple_{grpc,http}_infer_client.py, values and all."""
    import numpy as np

    import triton_client_tpu.grpc as grpcclient
    import triton_client_tpu.http as httpclient

    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.ones((1, 16), dtype=np.int32)
    info = {}
    for proto, mod, url in (("grpc", grpcclient, srv.grpc_url),
                            ("http", httpclient, srv.http_url)):
        with mod.InferenceServerClient(url) as client:
            inputs = [mod.InferInput("INPUT0", [1, 16], "INT32"),
                      mod.InferInput("INPUT1", [1, 16], "INT32")]
            inputs[0].set_data_from_numpy(a)
            inputs[1].set_data_from_numpy(b)
            outputs = [mod.InferRequestedOutput("OUTPUT0"),
                       mod.InferRequestedOutput("OUTPUT1")]
            result, t = timed(lambda: client.infer(
                "simple", inputs, outputs=outputs), steady=20)
            if not (np.array_equal(result.as_numpy("OUTPUT0"), a + b)
                    and np.array_equal(result.as_numpy("OUTPUT1"), a - b)):
                raise SmokeError(f"simple over {proto}: sum/diff mismatch")
            info[proto] = t
    return info


def check_dense_shm(srv: ServerChild) -> dict:
    """dense_tpu through the perf_analyzer load loop with system and then
    xla shared memory — this process is the out-of-process client of the
    README quickstart, so the xla leg must leave no JAX behind."""
    import numpy as np

    import triton_client_tpu.http as httpclient
    from triton_client_tpu.perf_analyzer import (_make_data, _resolve_model,
                                                 run_level)

    with httpclient.InferenceServerClient(srv.http_url) as meta:
        inputs, outputs, max_batch = _resolve_model(
            meta, "http", "dense_tpu", "")
        arrays = _make_data(inputs, {}, 1, max_batch,
                            np.random.default_rng(0))
        x = next(iter(arrays.values()))
        inp = httpclient.InferInput("INPUT", list(x.shape), "FP32")
        inp.set_data_from_numpy(x)
        _, first = timed(lambda: meta.infer("dense_tpu", [inp]), steady=0)
    info = {"first_s": first["first_s"]}
    measure_s = 2.0
    for mode in ("system", "xla"):
        res = run_level("grpc", srv.grpc_url, "dense_tpu", "", 4, arrays,
                        outputs, mode, 1 << 16, measure_s, warmup_s=1.0)
        n = int(res["throughput"] * measure_s)
        if res["errors"] or n < 100:
            raise SmokeError(
                f"dense_tpu shared-memory={mode}: {res['errors']} errors, "
                f"{n} requests in {measure_s}s "
                f"(first error: {res.get('first_error')})")
        info[mode] = {"requests": n, "errors": 0,
                      "p50_us": round(res["p50_us"], 1)}
    if "jax" in sys.modules:
        raise SmokeError("the xla shared-memory CLIENT imported jax: an "
                         "out-of-process client must never open a backend")
    return info


def _grpc_infer(client, grpcclient, model, name, arr, dtype):
    inp = grpcclient.InferInput(name, list(arr.shape), dtype)
    inp.set_data_from_numpy(arr)
    return client.infer(model, [inp])


def check_bert(smoke: Smoke, srv: ServerChild, int8: bool) -> dict:
    """bert_large at S=384: batch 1 and one batched shape, LOGITS [384,2]
    finite; batch row 0 agrees with the batch-1 answer; the int8 server's
    logits stay close to the bf16 server's for the same ids."""
    import numpy as np

    import triton_client_tpu.grpc as grpcclient

    S = 384
    ids = np.random.default_rng(7).integers(
        0, 30000, (4, S), dtype=np.int32)
    info = {}
    with grpcclient.InferenceServerClient(srv.grpc_url) as client:
        outs = {}
        for b in (1, 4):
            res, t = timed(lambda: _grpc_infer(
                client, grpcclient, "bert_large", "INPUT_IDS", ids[:b],
                "INT32"), steady=3)
            logits = res.as_numpy("LOGITS")
            if logits.shape != (b, S, 2) or not np.isfinite(logits).all():
                raise SmokeError(f"bert_large batch {b}: LOGITS shape "
                                 f"{logits.shape}, finite="
                                 f"{bool(np.isfinite(logits).all())}")
            outs[b] = logits
            info[f"batch{b}"] = t
    scale = float(np.abs(outs[1]).max())
    row_err = float(np.abs(outs[4][0] - outs[1][0]).max())
    info["batch_row_max_err"] = round(row_err, 5)
    if row_err > 0.05 * max(scale, 1.0):
        raise SmokeError(f"bert_large: batch-4 row 0 differs from the "
                         f"batch-1 answer by {row_err} (scale {scale})")
    if not int8:
        smoke.bert_bf16_logits = outs[1]
    else:
        ref = smoke.bert_bf16_logits.ravel()
        got = outs[1].ravel()
        cos = float(np.dot(ref, got)
                    / (np.linalg.norm(ref) * np.linalg.norm(got)))
        info["int8_vs_bf16_cosine"] = round(cos, 5)
        if cos < 0.95:
            raise SmokeError(f"bert_large int8 logits drifted from bf16: "
                             f"cosine {cos}")
    return info


def check_longctx(smoke: Smoke, srv: ServerChild) -> dict:
    """longctx_tpu at the preset's window (4096 on the chip): LOGPROBS
    finite, non-positive, and identical for an identical second request."""
    import numpy as np

    import triton_client_tpu.grpc as grpcclient

    with grpcclient.InferenceServerClient(srv.grpc_url) as client:
        md = client.get_model_metadata("longctx_tpu", as_json=True)
        S = int(md["inputs"][0]["shape"][-1])
        if not smoke.debug_cpu and S != LONGCTX_BASE_SEQ:
            raise SmokeError(f"longctx_tpu serves S={S}, the chip run needs "
                             f"{LONGCTX_BASE_SEQ}")
        toks = np.random.default_rng(11).integers(
            0, 255, (1, S), dtype=np.int32)
        first, t = timed(lambda: _grpc_infer(
            client, grpcclient, "longctx_tpu", "TOKENS", toks,
            "INT32").as_numpy("LOGPROBS"), steady=0)
        second, t2 = timed(lambda: _grpc_infer(
            client, grpcclient, "longctx_tpu", "TOKENS", toks,
            "INT32").as_numpy("LOGPROBS"), steady=2)
    if first.shape != (1, S) or not np.isfinite(first).all() \
            or (first > 1e-6).any():
        raise SmokeError(f"longctx_tpu: LOGPROBS shape {first.shape}, "
                         f"finite={bool(np.isfinite(first).all())}, "
                         f"max={float(first.max())}")
    if not np.allclose(first, second, rtol=0, atol=1e-5):
        raise SmokeError("longctx_tpu: two identical requests disagree by "
                         f"{float(np.abs(first - second).max())}")
    return {"seq_len": S, "first_s": t["first_s"],
            "steady_ms": t2["steady_ms"], "steady_n": t2["steady_n"],
            "mean_logprob": round(float(first[0, :-1].mean()), 4)}


def _generate_stream(http_url: str, prompt: str, n_tokens: int):
    """One llama_generate SSE stream -> (token ids, seconds to first frame)."""
    body = json.dumps({"text_input": prompt, "max_tokens": n_tokens}).encode()
    req = urllib.request.Request(
        f"http://{http_url}/v2/models/llama_generate/generate_stream",
        data=body, headers={"Content-Type": "application/json"})
    ids, t0, ttft = [], time.perf_counter(), None
    with urllib.request.urlopen(req, timeout=600) as resp:
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            frame = json.loads(line[len(b"data: "):])
            if "error" in frame:
                raise SmokeError(f"generate_stream: {frame['error']}")
            if ttft is None:
                ttft = time.perf_counter() - t0
            tok = frame["token_id"]
            ids.append(int(tok[0] if isinstance(tok, list) else tok))
    return ids, ttft, time.perf_counter() - t0


def check_generate(smoke: Smoke, srv: ServerChild) -> dict:
    """llama_generate over HTTP generate_stream: a first stream (compiles),
    then two concurrent streams of the same prompt — ids in the vocab's
    range, and the same greedy ids all three times."""
    first_ids, first_ttft, first_s = _generate_stream(
        srv.http_url, PROMPT, GEN_TOKENS)
    results = [None, None]

    def run(i):
        results[i] = _generate_stream(srv.http_url, PROMPT, GEN_TOKENS)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    pair_s = time.perf_counter() - t0
    if any(r is None for r in results):
        raise SmokeError("llama_generate: a concurrent stream failed (see "
                         "the traceback above)")
    vocab = 256 if smoke.debug_cpu else LLAMA_1B_VOCAB
    for ids in (first_ids, results[0][0], results[1][0]):
        if len(ids) != GEN_TOKENS or not all(0 <= t < vocab for t in ids):
            raise SmokeError(f"llama_generate: expected {GEN_TOKENS} ids in "
                             f"[0, {vocab}), got {ids}")
        if ids != first_ids:
            raise SmokeError(f"llama_generate: greedy ids differ for one "
                             f"prompt: {first_ids} vs {ids}")
    return {"first_stream_s": round(first_s, 2),
            "first_ttft_s": round(first_ttft, 2),
            "two_streams_s": round(pair_s, 3),
            "steady_ms_per_token": round(
                pair_s / GEN_TOKENS * 1e3, 2),
            "ids": first_ids}


def check_decode(srv: ServerChild) -> dict:
    """llama_decode over the gRPC sequence stream, the
    examples/simple_grpc_decode_client.py flow: the prompt window once,
    then one token per step fed back."""
    import numpy as np

    import triton_client_tpu.grpc as grpcclient

    n_tokens = 8
    results: "queue.Queue" = queue.Queue()
    produced, step_s = [], []
    with grpcclient.InferenceServerClient(srv.grpc_url) as client:
        cfg = client.get_model_config("llama_decode", as_json=True)["config"]
        S = int(cfg["parameters"]["prompt_tokens"]["string_value"])
        window = np.zeros(S, np.int32)
        raw = PROMPT.encode()[-S:]
        window[S - len(raw):] = np.frombuffer(raw, np.uint8)
        client.start_stream(
            callback=lambda result, error: results.put((result, error)))
        inp = grpcclient.InferInput("TOKENS", [S], "INT32")
        inp.set_data_from_numpy(window)
        t0 = time.perf_counter()
        client.async_stream_infer("llama_decode", [inp], sequence_id=4001,
                                  sequence_start=True)
        for step in range(n_tokens):
            res, err = results.get(timeout=600)
            step_s.append(time.perf_counter() - t0)
            if err is not None:
                raise SmokeError(f"llama_decode stream: {err}")
            tok = np.asarray(res.as_numpy("NEXT_TOKEN")).astype(
                np.int32).reshape(1)
            produced.append(int(tok[0]))
            nxt = grpcclient.InferInput("TOKENS", [1], "INT32")
            nxt.set_data_from_numpy(tok)
            t0 = time.perf_counter()
            client.async_stream_infer(
                "llama_decode", [nxt], sequence_id=4001,
                sequence_end=(step == n_tokens - 1))
        res, err = results.get(timeout=600)
        if err is not None:
            raise SmokeError(f"llama_decode stream: {err}")
        client.stop_stream()
    if len(produced) != n_tokens:
        raise SmokeError(f"llama_decode: {len(produced)} tokens, expected "
                         f"{n_tokens}")
    return {"prefill_first_s": round(step_s[0], 2),
            "first_step_s": round(step_s[1], 2),
            "steady_ms_per_step": round(
                sum(step_s[2:]) / len(step_s[2:]) * 1e3, 2),
            "ids": produced}


def check_models_ready(srv: ServerChild, names) -> dict:
    """A failed load or warmup only prints and unloads — so ask, by name."""
    import triton_client_tpu.http as httpclient

    with httpclient.InferenceServerClient(srv.http_url) as client:
        not_ready = [n for n in names if not client.is_model_ready(n)]
    if not_ready:
        raise SmokeError(f"models not ready after use: {not_ready}\n"
                         f"{log_tail(srv.log_path)}")
    return {"ready": list(names)}


def run_child_a(smoke: Smoke) -> None:
    with ServerChild("A", smoke.platforms) as srv:
        smoke.phase("A.ready_device",
                    lambda: check_device_and_presets(smoke, srv))
        smoke.phase("A.simple_grpc_http", lambda: check_simple(srv))
        smoke.phase("A.dense_tpu_shm_system_xla",
                    lambda: check_dense_shm(srv))
        smoke.phase("A.bert_large_bf16",
                    lambda: check_bert(smoke, srv, int8=False))
        smoke.phase("A.longctx_flash", lambda: check_longctx(smoke, srv))
        smoke.phase("A.llama_generate_stream",
                    lambda: check_generate(smoke, srv))
        smoke.phase("A.llama_decode_grpc_stream", lambda: check_decode(srv))
        smoke.phase("A.models_ready", lambda: check_models_ready(
            srv, ("simple", "dense_tpu", "bert_large", "longctx_tpu",
                  "llama_generate", "llama_decode")))
    smoke.passed("A.sigterm_drain_exit0", srv.drain_s)


def run_child_b(smoke: Smoke) -> None:
    extra = {"TRITON_TPU_DECODE_MODE": "batched", "TRITON_TPU_QUANT": "int8"}
    with ServerChild("B", smoke.platforms, extra) as srv:
        smoke.phase("B.ready_device",
                    lambda: check_device_and_presets(smoke, srv))
        smoke.phase("B.llama_generate_batched_int8",
                    lambda: check_generate(smoke, srv))
        smoke.phase("B.bert_large_int8",
                    lambda: check_bert(smoke, srv, int8=True))
        smoke.phase("B.models_ready", lambda: check_models_ready(
            srv, ("bert_large", "llama_generate", "llama_decode")))
    smoke.passed("B.sigterm_drain_exit0", srv.drain_s)


# -- child C: co-located zero copy, and the kernels against their references -

def run_child_c(smoke: Smoke) -> None:
    def go():
        cmd = [sys.executable, os.path.abspath(__file__), "--colocated-child"]
        if smoke.debug_cpu:
            cmd.append("--debug-cpu")
        log_path = os.path.join(OUT_DIR, "child_C.log")
        with open(log_path, "w") as log:
            proc = subprocess.run(
                cmd, env=child_env(smoke.platforms), cwd=REPO,
                stdout=subprocess.PIPE, stderr=log, text=True, timeout=600)
        if proc.returncode != 0:
            raise SmokeError(f"co-located child exited rc={proc.returncode}:"
                             f"\n{proc.stdout[-2000:]}\n{log_tail(log_path)}")
        info = json.loads(proc.stdout.strip().splitlines()[-1])
        if info.pop("device") != smoke.device:
            raise SmokeError("co-located child saw another device than the "
                             f"servers: {info}")
        smoke.cache_dirs.add(info["compile_cache"])
        return info

    smoke.phase("C.colocated_zero_copy_and_kernels", go)


def colocated_child(debug_cpu: bool) -> None:
    """Runs in its own process (it owns the chip): ServerHarness + the
    examples/simple_grpc_cudashm_client.py sequence + a dense_tpu xla-shm
    infer, all on device-resident regions; then each pallas kernel,
    compiled, against its reference.  Prints one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import triton_client_tpu.grpc as grpcclient
    import triton_client_tpu.utils.cuda_shared_memory as cudashm
    from triton_client_tpu._xla_broker import broker
    from triton_client_tpu.models import zoo
    from triton_client_tpu.server.compile_cache import enable_compile_cache
    from triton_client_tpu.server.registry import ModelRegistry
    from triton_client_tpu.server.testing import ServerHarness
    from triton_client_tpu.utils import shared_memory as sysshm

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    want_platform = "cpu" if debug_cpu else "tpu"
    if dev.platform != want_platform:
        raise SmokeError(f"co-located child runs on {dev.platform}")
    shm_before = set(os.listdir("/dev/shm"))
    registry = ModelRegistry()
    zoo.register_all(registry)
    info = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "compile_cache": cache_dir}

    def on_device(region) -> None:
        arr = region.array
        if arr is None or {d.platform for d in arr.devices()} != {
                want_platform}:
            raise SmokeError(f"region {region.triton_shm_name} is not a "
                             f"{want_platform}-resident jax.Array: {arr!r}")
        seq = int(sysshm.get_contents_as_numpy(
            region._seq, np.uint64, [1])[0])
        if seq != 0:
            raise SmokeError(f"region {region.triton_shm_name}: the staging "
                             "region was written in the co-located topology")

    with ServerHarness(registry) as h:
        if not broker().server_present:
            raise SmokeError("ServerHarness did not mark the broker "
                             "server_present")
        with grpcclient.InferenceServerClient(h.grpc_url) as client:
            # examples/simple_grpc_cudashm_client.py, step for step
            client.unregister_cuda_shared_memory()
            a = np.arange(16, dtype=np.int32).reshape(1, 16)
            b = np.ones((1, 16), dtype=np.int32)
            handles = {}
            for name in ("input0_data", "input1_data",
                         "output0_data", "output1_data"):
                handles[name] = cudashm.create_shared_memory_region(
                    name, a.nbytes, 0)
                client.register_cuda_shared_memory(
                    name, cudashm.get_raw_handle(handles[name]), 0, a.nbytes)
            cudashm.set_shared_memory_region(handles["input0_data"], [a])
            cudashm.set_shared_memory_region(handles["input1_data"], [b])
            inputs = [grpcclient.InferInput("INPUT0", [1, 16], "INT32"),
                      grpcclient.InferInput("INPUT1", [1, 16], "INT32")]
            inputs[0].set_shared_memory("input0_data", a.nbytes)
            inputs[1].set_shared_memory("input1_data", a.nbytes)
            outputs = [grpcclient.InferRequestedOutput("OUTPUT0"),
                       grpcclient.InferRequestedOutput("OUTPUT1")]
            outputs[0].set_shared_memory("output0_data", a.nbytes)
            outputs[1].set_shared_memory("output1_data", a.nbytes)
            t0 = time.perf_counter()
            client.infer("simple", inputs, outputs=outputs)
            info["simple_first_s"] = round(time.perf_counter() - t0, 3)
            got_sum = cudashm.get_contents_as_numpy(
                handles["output0_data"], np.int32, [1, 16])
            got_diff = cudashm.get_contents_as_numpy(
                handles["output1_data"], np.int32, [1, 16])
            if not (np.array_equal(got_sum, a + b)
                    and np.array_equal(got_diff, a - b)):
                raise SmokeError("co-located cudashm flow: sum/diff mismatch")
            for region in handles.values():
                on_device(region)
            # dense_tpu: input and output both device-resident regions,
            # checked against the same request over the wire
            x = np.random.default_rng(0).random((1, 512), np.float32)
            wire_in = grpcclient.InferInput("INPUT", [1, 512], "FP32")
            wire_in.set_data_from_numpy(x)
            want = client.infer("dense_tpu", [wire_in]).as_numpy("OUTPUT")
            for name in ("dense_in", "dense_out"):
                handles[name] = cudashm.create_shared_memory_region(
                    name, x.nbytes, 0)
                client.register_cuda_shared_memory(
                    name, cudashm.get_raw_handle(handles[name]), 0, x.nbytes)
            cudashm.set_shared_memory_region(handles["dense_in"], [x])
            shm_in = grpcclient.InferInput("INPUT", [1, 512], "FP32")
            shm_in.set_shared_memory("dense_in", x.nbytes)
            shm_out = grpcclient.InferRequestedOutput("OUTPUT")
            shm_out.set_shared_memory("dense_out", x.nbytes)
            t0 = time.perf_counter()
            for _ in range(20):
                client.infer("dense_tpu", [shm_in], outputs=[shm_out])
            info["dense_xla_shm_steady_ms"] = round(
                (time.perf_counter() - t0) / 20 * 1e3, 2)
            got = cudashm.get_contents_as_numpy(
                handles["dense_out"], np.float32, [1, 512])
            # bf16 matmuls: the two paths may compile to different
            # programs, so agree to bf16 precision, not to the bit
            err = float(np.abs(got - want).max())
            info["dense_xla_shm_vs_wire_max_err"] = round(err, 6)
            if not np.allclose(got, want, rtol=2e-2, atol=2e-2):
                raise SmokeError("dense_tpu over xla shm disagrees with the "
                                 f"wire answer by {err}")
            on_device(handles["dense_in"])
            on_device(handles["dense_out"])
            client.unregister_cuda_shared_memory()
            for region in handles.values():
                cudashm.destroy_shared_memory_region(region)
    if cudashm.allocated_shared_memory_regions():
        raise SmokeError("leaked xla shared-memory regions: "
                         f"{cudashm.allocated_shared_memory_regions()}")
    leaked = set(os.listdir("/dev/shm")) - shm_before
    if leaked:
        raise SmokeError(f"leaked /dev/shm objects: {sorted(leaked)}")
    info["regions_on_device"] = 6
    if not debug_cpu:
        info["kernels"] = kernels_vs_references(jax, jnp)
    print(json.dumps(info), flush=True)


def kernels_vs_references(jax, jnp) -> dict:
    """Each pallas kernel compiled by Mosaic at the shape the zoo serves it
    at, against its jnp reference (only the chip can run this)."""
    from triton_client_tpu.ops import (flash_attention,
                                       flash_attention_reference, int8_matmul,
                                       int8_matmul_reference)

    out = {}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    for name, shape, causal in (
            # longctx_tpu "base": 16 heads x S=4096 x D=64: the looped form
            ("flash_S4096", (1, 16, LONGCTX_BASE_SEQ, 64), True),
            # bert_large at a bucket whose scores outgrow the chip: the
            # whole-row form, bidirectional
            ("flash_S384", (4, 16, 384, 64), False)):
        q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
                   for key in keys)
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            force=True))(q, k, v))
        compile_s = time.perf_counter() - t0
        # the reference materialises [S, S] f32 scores: two heads are enough
        want = flash_attention_reference(q[:, :2], k[:, :2], v[:, :2],
                                         causal=causal)
        err = float(jnp.max(jnp.abs(got[:, :2].astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        if not bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))) \
                or err > 0.05:
            raise SmokeError(f"{name}: kernel vs reference, max abs err {err}")
        out[name] = {"first_s": round(compile_s, 2), "max_abs_err": err}
    # bert_large int8 FFN-down (w2): [384, 4096] @ [4096, 1024]
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (384, 4096), jnp.bfloat16)
    w = jax.random.randint(kw, (4096, 1024), -127, 128, jnp.int8)
    ws = (jnp.abs(jax.random.normal(ks, (1024,), jnp.float32)) + 0.01) * 0.02
    t0 = time.perf_counter()
    got = jax.block_until_ready(jax.jit(
        lambda x, w, ws: int8_matmul(x, w, ws, force=True))(x, w, ws))
    compile_s = time.perf_counter() - t0
    want = jax.jit(int8_matmul_reference)(x, w, ws)
    g32, w32 = got.astype(jnp.float32), want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(g32 - w32)))
    bound = float(jnp.max(jnp.abs(w32))) * 2.0 ** -7  # one bf16 ulp at the top
    if err > bound:
        raise SmokeError(f"int8 kernel vs reference: max abs err {err} "
                         f"(bound {bound})")
    out["int8_w2_M384"] = {"first_s": round(compile_s, 2),
                           "max_abs_err": err}
    return out


# -- main --------------------------------------------------------------------

def package_version(name: str) -> str:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--debug-cpu", action="store_true",
                        help="run the flow on the CPU at the tiny presets "
                        "to debug this script; never a pass")
    parser.add_argument("--colocated-child", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.colocated_child:
        colocated_child(args.debug_cpu)
        return 0

    t_start = time.perf_counter()
    # fail here, before any child, outside the repo
    try:
        import triton_client_tpu.genai_perf  # noqa: F401
        import triton_client_tpu.grpc  # noqa: F401
        import triton_client_tpu.http  # noqa: F401
        import triton_client_tpu.perf_analyzer  # noqa: F401
        import triton_client_tpu.utils.xla_shared_memory  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: FAILED: the program is not here to drive "
              f"(run from the root of a checkout of triton_client_tpu): {e}",
              file=sys.stderr)
        return 1

    smoke = Smoke(args.debug_cpu)
    try:
        run_child_a(smoke)
        run_child_b(smoke)
        run_child_c(smoke)
    except SmokeError as e:
        print(f"chip_smoke: FAILED after "
              f"{[p['phase'] for p in smoke.phases]}: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("chip_smoke: FAILED: the parent imported jax", file=sys.stderr)
        return 1
    total_s = round(time.perf_counter() - t_start, 1)
    first_s = round(sum(
        v for p in smoke.phases for k, v in _walk(p)
        if k in ("first_s", "first_stream_s", "prefill_first_s",
                 "server_start_s")), 1)
    print(f"[total] {total_s}s wall, of which {first_s}s in server starts "
          f"and first (compiling) requests; compile cache: "
          f"{sorted(smoke.cache_dirs)}", flush=True)
    if args.debug_cpu:
        print("chip_smoke: --debug-cpu: every phase ran on the CPU at the "
              "tiny presets; this is not a chip result", file=sys.stderr)
        return 2
    print("[summary] " + json.dumps({
        "versions": {p: package_version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "presets": smoke.presets,
        "compile_cache": sorted(smoke.cache_dirs),
        "total_s": total_s,
        "first_request_and_start_s": first_s,
        "phases_passed": [p["phase"] for p in smoke.phases],
        "claim": None,
    }), flush=True)
    print(result_line(smoke.device), flush=True)
    return 0


def result_line(device: dict) -> str:
    """The last line of stdout: exactly these keys and no other, the device
    as the server's JAX reported it.  Everything else the run learned is on
    the ``[summary]`` line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _walk(d: dict):
    for k, v in d.items():
        if isinstance(v, dict):
            yield from _walk(v)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            yield k, v


if __name__ == "__main__":
    sys.exit(main())
