"""HTTP/REST v2 frontend (aiohttp).

Endpoint surface mirrors what the reference HTTP client targets (URI builders
surveyed at http/_client.py:364-1474), including the binary-tensor-data
extension: request/response bodies are ``<json header><concatenated raw
buffers>`` with the JSON length in the ``Inference-Header-Content-Length``
header (reference framing: http/_utils.py:137-150).
"""

from __future__ import annotations

import asyncio
import base64
import binascii
import gzip
import json
import math
import time
import zlib

import numpy as np
from aiohttp import web

from ..utils import deserialize_bytes_tensor, triton_to_np_dtype
from .core import InferenceCore
from .log import log_off_loop
from .memory import DEFAULT_MAX_REQUEST_BYTES
from .qos import tenant_from_headers
from .types import (InferError, InferRequest, InputTensor,
                    RequestedOutput, ShmRef, apply_request_deadline,
                    apply_request_priority, reshape_input)
from .wire import encode_http_response, sse_frame

_HEADER_LEN = "Inference-Header-Content-Length"
_REQUEST_ID_HDR = "triton-request-id"
_TRACEPARENT_HDR = "traceparent"
# remaining client deadline budget in microseconds (the HTTP wire form of
# the v2 `timeout` parameter; restamped per retry attempt by the client
# resilience layer)
_TIMEOUT_HDR = "triton-timeout-us"
# QoS tenant id (falls back to the basic-auth username, then "anonymous")
_TENANT_HDR = "triton-tenant"


def _stamp_qos(req: InferRequest, request: web.Request) -> None:
    """Resolve the request's QoS identity: tenant from the triton-tenant
    header / basic-auth username, priority consumed out of the v2
    ``priority`` parameter (0 = highest)."""
    req.tenant = tenant_from_headers(
        request.headers.get(_TENANT_HDR),
        request.headers.get("Authorization"))
    apply_request_priority(req)


def _oversize_response(size, cap: int) -> web.Response:
    """The typed wire-cap rejection: 413 with the limit in the body and
    the machine-readable headers, BEFORE any body materialization.  The
    pushback headers ride along for symmetry with every other shed, but
    the client resilience layer classifies 413 as non-retryable — the
    same payload can only bounce again; the fix is client-side."""
    size_s = f"request of {size} bytes" if size else "request"
    return web.json_response(
        {"error": f"{size_s} exceeds the server's max request size of "
                  f"{cap} bytes (--max-request-bytes)"},
        status=413,
        headers={
            "Retry-After": "1",
            "triton-retry-after-ms": "1000",
            "triton-max-request-bytes": str(cap),
        })


def _ingress_cap(cap: int):
    """Wire ingress cap middleware (server/memory.py layer 1): reject
    oversize requests from their DECLARED sizes — ``Content-Length``, or
    the ``Inference-Header-Content-Length`` a chunked upload still
    announces — before reading a byte of body; bodies that only reveal
    their size while streaming in are cut off by aiohttp's
    ``client_max_size`` (HTTPRequestEntityTooLarge), converted here to
    the same typed 413 instead of the stock HTML error page."""

    @web.middleware
    async def middleware(request: web.Request, handler):
        declared = request.content_length
        if declared is not None and declared > cap:
            return _oversize_response(declared, cap)
        hlen = request.headers.get(_HEADER_LEN)
        if hlen is not None:
            try:
                if int(hlen) > cap:
                    return _oversize_response(int(hlen), cap)
            except ValueError:
                pass  # junk header: the handler 400s it with context
        try:
            return await handler(request)
        except web.HTTPRequestEntityTooLarge as e:
            return _oversize_response(getattr(e, "actual_size", None), cap)

    return middleware


def build_app(core: InferenceCore,
              max_request_bytes: int = DEFAULT_MAX_REQUEST_BYTES
              ) -> web.Application:
    cap = max(0, int(max_request_bytes or 0))
    # client_max_size enforces the cap on bodies whose size is only
    # discovered while streaming; 0 (explicit opt-out) restores the old
    # 1 GiB aiohttp ceiling
    app = web.Application(client_max_size=cap or 1 << 30,
                          middlewares=[_ingress_cap(cap)] if cap else [])
    r = app.router
    r.add_get("/v2/health/live", _h(core, _health_live))
    r.add_get("/v2/health/ready", _h(core, _health_ready))
    r.add_get("/v2/models/{model}/ready", _h(core, _model_ready))
    r.add_get("/v2/models/{model}/versions/{version}/ready", _h(core, _model_ready))
    r.add_get("/v2", _h(core, _server_metadata))
    r.add_get("/v2/models/{model}", _h(core, _model_metadata))
    r.add_get("/v2/models/{model}/versions/{version}", _h(core, _model_metadata))
    r.add_get("/v2/models/{model}/config", _h(core, _model_config))
    r.add_get("/v2/models/{model}/versions/{version}/config", _h(core, _model_config))
    r.add_get("/v2/models/stats", _h(core, _model_stats))
    r.add_get("/v2/models/{model}/stats", _h(core, _model_stats))
    r.add_get("/v2/models/{model}/versions/{version}/stats", _h(core, _model_stats))
    r.add_post("/v2/repository/index", _h(core, _repo_index))
    r.add_post("/v2/repository/models/{model}/load", _h(core, _repo_load))
    r.add_post("/v2/repository/models/{model}/unload", _h(core, _repo_unload))
    r.add_post("/v2/models/{model}/infer", _h(core, _infer))
    r.add_post("/v2/models/{model}/versions/{version}/infer", _h(core, _infer))
    r.add_post("/v2/models/{model}/generate", _h(core, _generate))
    r.add_post("/v2/models/{model}/versions/{version}/generate",
               _h(core, _generate))
    r.add_post("/v2/models/{model}/generate_stream", _h(core, _generate_stream))
    r.add_post("/v2/models/{model}/versions/{version}/generate_stream",
               _h(core, _generate_stream))
    r.add_get("/v2/trace/setting", _h(core, _get_trace))
    r.add_post("/v2/trace/setting", _h(core, _set_trace))
    r.add_get("/v2/models/{model}/trace/setting", _h(core, _get_trace))
    r.add_post("/v2/models/{model}/trace/setting", _h(core, _set_trace))
    r.add_get("/v2/logging", _h(core, _get_logging))
    r.add_post("/v2/logging", _h(core, _set_logging))
    r.add_get("/v2/debug/flight_recorder", _h(core, _flight_recorder))
    r.add_get("/v2/debug/device_stats", _h(core, _device_stats))
    r.add_get("/v2/debug/costs", _h(core, _costs))
    r.add_get("/v2/debug/profile", _h(core, _profile))
    r.add_get("/v2/debug/incident", _h(core, _incident_status))
    r.add_post("/v2/debug/incident", _h(core, _incident_trigger))
    r.add_get("/metrics", _h(core, _metrics))
    for kind in ("systemsharedmemory", "cudasharedmemory"):
        r.add_get(f"/v2/{kind}/status", _h(core, _shm_status))
        r.add_get(f"/v2/{kind}/region/{{name}}/status", _h(core, _shm_status))
        r.add_post(f"/v2/{kind}/region/{{name}}/register", _h(core, _shm_register))
        r.add_post(f"/v2/{kind}/unregister", _h(core, _shm_unregister))
        r.add_post(f"/v2/{kind}/region/{{name}}/unregister", _h(core, _shm_unregister))

    # OpenAI-compatible surface over the generation stack (/v1/models,
    # /v1/completions, /v1/chat/completions)
    from .openai_api import add_openai_routes

    add_openai_routes(app, core)

    # gRPC-Web bridge: the full v2 gRPC service over HTTP/1.1 framing (used
    # by the C++ gRPC client; interops with stock gRPC-Web stubs).
    from .grpc_server import InferenceServicer
    from .grpc_web import add_grpc_web_routes

    add_grpc_web_routes(app, InferenceServicer(core))
    return app


async def _read_json(request: web.Request, default=None, expect_object=True):
    """Parse a JSON body as a client error (400) — a malformed body (or a
    valid one of the wrong top-level type) must never surface as a 500."""
    if default is not None and not request.can_read_body:
        return default
    try:
        body = await request.json()
    except Exception:
        raise InferError("failed to parse request JSON")
    if expect_object and not isinstance(body, dict):
        raise InferError("request body must be a JSON object")
    return body


def build_metrics_app(core: InferenceCore) -> web.Application:
    """Minimal app for the dedicated Prometheus port (Triton convention:
    :8002): ``/metrics`` plus the two debug snapshots.  Under
    ``--frontends N`` each worker gets its own metrics port (base + worker
    index), so this app is the one per-PROCESS observability surface —
    pointing ``triton-top --url`` at each worker's metrics port gives the
    per-process view that the kernel-balanced main port can't (every poll
    there lands on a random worker)."""
    app = web.Application()
    app.router.add_get("/metrics", _h(core, _metrics))
    app.router.add_get("/v2/debug/flight_recorder", _h(core, _flight_recorder))
    app.router.add_get("/v2/debug/device_stats", _h(core, _device_stats))
    app.router.add_get("/v2/debug/costs", _h(core, _costs))
    app.router.add_get("/v2/debug/profile", _h(core, _profile))
    app.router.add_get("/v2/debug/incident", _h(core, _incident_status))
    app.router.add_post("/v2/debug/incident", _h(core, _incident_trigger))
    return app


def _h(core: InferenceCore, fn):
    async def handler(request: web.Request) -> web.Response:
        # propagated correlation id rides every log line for this request
        # (passed explicitly: the executor hop would lose a contextvar)
        rid = request.headers.get(_REQUEST_ID_HDR, "")
        try:
            resp = await fn(core, request)
            if core.log.verbose_enabled():
                log_off_loop(
                    core.log.verbose, 1,
                    f"{request.method} {request.path} -> {resp.status}",
                    rid)
            return resp
        except InferError as e:
            from .chaos import ChaosAbort

            if isinstance(e, ChaosAbort):
                # injected mid-response connection abort: kill the
                # transport so the client sees a protocol error, not a
                # well-formed 5xx — the connection-class failure the
                # retry layer must absorb
                if request.transport is not None:
                    request.transport.close()
                return web.Response(status=503)
            # 5xx are server-side failures (log_error); 4xx are client
            # mistakes — verbose only, or every fuzz/validation request
            # would spam the log
            if e.http_status >= 500:
                log_off_loop(
                    core.log.error,
                    f"{request.method} {request.path} failed: {e}", rid)
            elif core.log.verbose_enabled():
                log_off_loop(
                    core.log.verbose, 1,
                    f"{request.method} {request.path} -> "
                    f"{e.http_status}: {e}", rid)
            headers = None
            if e.retry_after_s is not None:
                # shed load carries the server's pushback horizon; the
                # client retry policy honors it over its own backoff.
                # Retry-After must be integer delta-seconds (RFC 7231) —
                # the precise sub-second horizon travels alongside in
                # triton-retry-after-ms (this framework's clients prefer
                # it; standards-only intermediaries still parse the RFC
                # form)
                headers = {
                    "Retry-After": str(max(1, math.ceil(e.retry_after_s))),
                    "triton-retry-after-ms":
                        str(int(e.retry_after_s * 1000)),
                }
            return web.json_response({"error": str(e)},
                                     status=e.http_status, headers=headers)
        except web.HTTPException:
            raise
        except Exception as e:  # pragma: no cover - defensive
            log_off_loop(
                core.log.error,
                f"{request.method} {request.path} crashed: {e}", rid)
            return web.json_response({"error": str(e)}, status=500)

    return handler


# -- health / metadata -----------------------------------------------------


async def _health_live(core, request):
    return web.Response(status=200 if core.live else 400)


async def _health_ready(core, request):
    # not-ready while startup warmup runs or any model is mid-load: a
    # load balancer must not route at a server that would compile on its
    # first request (Triton semantics: ready = "will serve now")
    return web.Response(status=200 if core.ready() else 400)


async def _model_ready(core, request):
    # registry-ready AND not quarantined after device faults — a load
    # balancer stops routing at a quarantined model while the server
    # itself stays healthy (see InferenceCore.model_ready)
    ok = core.model_ready(
        request.match_info["model"], request.match_info.get("version", "")
    )
    return web.Response(status=200 if ok else 400)


async def _server_metadata(core, request):
    return web.json_response(core.server_metadata())


async def _model_metadata(core, request):
    model = core.registry.get(
        request.match_info["model"], request.match_info.get("version", "")
    )
    return web.json_response(model.metadata())


async def _model_config(core, request):
    from google.protobuf import json_format

    model = core.registry.get(
        request.match_info["model"], request.match_info.get("version", "")
    )
    cfg = json_format.MessageToDict(model.config, preserving_proto_field_name=True)
    cfg.setdefault("name", model.name)
    return web.json_response(cfg)


async def _model_stats(core, request):
    stats = core.statistics(
        request.match_info.get("model"), request.match_info.get("version", "")
    )
    return web.json_response({"model_stats": stats})


# -- repository ------------------------------------------------------------


async def _repo_index(core, request):
    body = await _read_json(request, default={})
    ready = bool(body.get("ready", False))
    return web.json_response(core.registry.index(ready_only=ready))


async def _repo_load(core, request):
    name = request.match_info["model"]
    body = await _read_json(request, default={})
    params = body.get("parameters", {}) or {}
    config_override = params.get("config")
    files = {k: v for k, v in params.items() if k.startswith("file:")}
    await core.load_model(name, config_override=config_override,
                          files=files or None)
    return web.Response(status=200)


async def _repo_unload(core, request):
    name = request.match_info["model"]
    body = await _read_json(request, default={})
    params = body.get("parameters", {}) or {}
    core.registry.unload(name, unload_dependents=bool(params.get("unload_dependents")))
    core.retire_name_caches(name)
    log_off_loop(core.log.info, f"successfully unloaded model '{name}'")
    return web.Response(status=200)


# -- trace / logging -------------------------------------------------------


async def _get_trace(core, request):
    model = request.match_info.get("model")
    if model:
        core.registry.get(model)  # unknown model -> 400
        return web.json_response(core.tracer.effective_settings(model))
    return web.json_response(core.trace_settings)


async def _set_trace(core, request):
    from .trace import TRACE_DEFAULTS, validate_trace_update

    model = request.match_info.get("model")
    body = await _read_json(request, default={})
    if model:
        core.registry.get(model)  # unknown model -> 400
        update, cleared = {}, []
        for k, v in body.items():
            if v is None:
                # null in model scope clears the OVERRIDE — the model goes
                # back to inheriting the global value (reference contract)
                if k not in TRACE_DEFAULTS:
                    raise InferError(f"unknown trace setting '{k}'", 400)
                cleared.append(k)
            else:
                update[k] = v if isinstance(v, list) else [str(v)]
        validate_trace_update(update, model_scope=True)
        if update or cleared:
            core.tracer.update_model(model, update, cleared)
        return web.json_response(core.tracer.effective_settings(model))
    update = {}
    for k, v in body.items():
        if v is None:
            # null clears to default (reference update_trace_settings
            # contract); a typo'd clear flows into the shared validator,
            # which 400s unknown keys — same contract as model scope
            update[k] = list(TRACE_DEFAULTS.get(k, []))
        else:
            update[k] = v if isinstance(v, list) else [str(v)]
    validate_trace_update(update)  # 501 for TENSORS, 400 for junk — pre-apply
    if update:  # an empty body is a read, not an update — counters keep phase
        core.tracer.apply(update)
    return web.json_response(core.trace_settings)


async def _build_generate(core, request):
    """Shared generate prologue: (name, version, model, InferRequest)."""
    from .generate import build_generate_request

    name = request.match_info["model"]
    version = request.match_info.get("version", "")
    model = core.registry.get(name, version)
    # read raw first: the byte ledger needs the ACTUAL body size (a
    # chunked upload has no Content-Length to trust), and an oversize
    # read raises HTTPRequestEntityTooLarge for the ingress-cap
    # middleware — it must not be swallowed into the JSON 400 below
    raw = await request.read()
    try:
        body = json.loads(raw)
    except Exception:
        raise InferError("failed to parse generate request JSON", 400)
    req = build_generate_request(model, name, version, body)
    req.protocol = "http"
    req.wire_bytes = len(raw)
    # trace propagation on the generate surface too (join-key parity with
    # /infer): a traced generate_stream record joins client telemetry on
    # the same correlation id / traceparent unary requests use
    req.client_request_id = request.headers.get(_REQUEST_ID_HDR, "")
    req.traceparent = request.headers.get(_TRACEPARENT_HDR, "")
    _stamp_qos(req, request)
    return name, version, model, req


async def _generate(core, request):
    from .generate import response_to_json

    name, version, model, req = await _build_generate(core, request)
    if model.decoupled:
        raise InferError(
            f"model '{name}' is decoupled: use generate_stream", 400)
    response = await core.infer(req)
    return web.Response(
        text=response_to_json(name, version, response),
        content_type="application/json")


async def sse_stream(request, agen, write_frame, on_error, epilogue=None):
    """Shared SSE lifecycle for streaming endpoints (generate_stream, the
    OpenAI frontend).

    The first response is pulled BEFORE committing the 200/SSE headers so
    request/model errors surface as proper HTTP statuses (__anext__, not the
    anext() builtin: requires-python floor is 3.9).  ``write_frame(stream,
    resp)`` serializes each response; ``on_error(e) -> bytes`` formats a
    mid-stream InferError as an in-band frame; ``epilogue(stream)`` runs
    after a clean drain (e.g. OpenAI's [DONE] terminator).

    Every exit closes ``agen`` deterministically: a consumer disconnect
    must reach the core's stream envelope NOW (cancel accounting, the
    stream trace record, decode-slot reclaim) rather than at GC time."""
    try:
        try:
            first = await agen.__anext__()
        except StopAsyncIteration:
            first = None
        stream = web.StreamResponse()
        stream.headers["Content-Type"] = "text/event-stream"
        stream.headers["Cache-Control"] = "no-cache"
        await stream.prepare(request)
        try:
            if first is not None:
                await write_frame(stream, first)
            async for resp in agen:
                await write_frame(stream, resp)
            if epilogue is not None:
                await epilogue(stream)
        except InferError as e:
            # mid-stream failure: headers are committed, deliver in-band
            await stream.write(on_error(e))
        except (ConnectionError, OSError, asyncio.CancelledError):
            # client went away mid-stream — close quietly; re-raising would
            # make the handler wrapper answer a second response on a
            # transport the StreamResponse owns
            return stream
        await stream.write_eof()
        return stream
    finally:
        await agen.aclose()


async def _generate_stream(core, request):
    from .generate import response_to_json

    name, version, model, req = await _build_generate(core, request)

    async def write_frame(stream, resp):
        if not resp.outputs:
            return  # final-flagged empty frame ends decoupled streams
        tr = resp.trace
        if tr is None:
            # precompiled envelope affixes: only the payload is encoded per
            # event, not the whole "data: ...\n\n" frame re-formatted
            await stream.write(sse_frame(response_to_json(name, version, resp)))
            return
        # traced stream: each flushed chunk's serialize+write window lands
        # as a NETWORK_WRITE span, batched at the token stride inside
        # record_write (per-chunk spans would double the record size)
        t0 = time.monotonic_ns()
        await stream.write(sse_frame(response_to_json(name, version, resp)))
        tr.record_write(t0, time.monotonic_ns())

    return await sse_stream(
        request, core.infer_stream(req), write_frame,
        on_error=lambda e: sse_frame(json.dumps({"error": str(e)})))


async def _flight_recorder(core, request):
    from .flight_recorder import parse_snapshot_limit

    model = request.query.get("model") or None
    # shared validator (also used by the gRPC FlightRecorder RPC): junk or
    # negative ?limit= is a client mistake — 400 with a JSON error body,
    # never an unhandled 500
    limit = parse_snapshot_limit(request.query.get("limit", "0"))
    # snapshot + serialize off-loop: at operator-sized rings (10^4-10^5
    # records) this is a multi-MB json.dumps — done inline it would stall
    # every in-flight inference for the duration of a debug poll
    body = await asyncio.get_running_loop().run_in_executor(
        None, lambda: json.dumps(
            core.flight_recorder.snapshot(model=model, limit=limit)))
    return web.Response(text=body, content_type="application/json")


async def _device_stats(core, request):
    """Debug surface for the device/scheduler observability layer: the
    DeviceStatsCollector snapshot (compute/compile/tick/transfer/HBM)
    with the SLO engine's per-model state alongside under ``"slo"``.
    ``?model=`` filters the per-model sections."""
    model = request.query.get("model") or None

    def _snap():
        out = core.device_stats.snapshot(model=model)
        out["slo"] = core.slo.snapshot(model=model)
        # the byte-admission ledger rides the same debug surface: live
        # budget, in-flight bytes per model/tenant, shed counts
        out["memory"] = core.memory.snapshot()
        # prefix/KV cache block stores: hit/miss/evict counters and
        # pinned bytes per model (server/kvcache.py) — the counters the
        # gen_shared_prefix bench reads back
        from . import kvcache

        out["kv_cache"] = kvcache.snapshot()
        return json.dumps(out)

    body = await asyncio.get_running_loop().run_in_executor(None, _snap)
    return web.Response(text=body, content_type="application/json")


async def _costs(core, request):
    """Debug surface for the per-tenant cost-attribution ledger
    (server/costs.py): device-time, FLOPs, generated tokens, and KV
    byte-seconds per (model, tenant).  ``?model=`` filters to one
    model's tenants.  Off-loop like the other debug snapshots."""
    model = request.query.get("model") or None
    body = await asyncio.get_running_loop().run_in_executor(
        None, lambda: json.dumps(core.cost_ledger.snapshot(model=model)))
    return web.Response(text=body, content_type="application/json")


async def _profile(core, request):
    """Debug surface for the always-on host profiler (server/profiler.py).

    Default output is collapsed-stack text — pipe straight into
    ``flamegraph.pl`` or paste into speedscope.  ``?format=json`` returns
    the structured snapshot (loop-lag series, GC pauses, top stacks);
    ``?role=`` filters the folded stacks to one thread role."""
    role = request.query.get("role") or None
    if request.query.get("format") == "json":
        body = await asyncio.get_running_loop().run_in_executor(
            None, lambda: json.dumps(core.profiler.snapshot()))
        return web.Response(text=body, content_type="application/json")
    text = await asyncio.get_running_loop().run_in_executor(
        None, core.profiler.collapsed, role)
    return web.Response(text=text, content_type="text/plain")


async def _incident_status(core, request):
    body = await asyncio.get_running_loop().run_in_executor(
        None, lambda: json.dumps(core.incidents.snapshot()))
    return web.Response(text=body, content_type="application/json")


async def _incident_trigger(core, request):
    """Manual incident bundle: ``POST /v2/debug/incident`` (optional JSON
    body ``{"reason": ...}``).  Synchronous — the response carries the
    bundle path — but off-loop: the capture window must not stall the
    loop it is trying to observe.  202 with ``"rate_limited"`` when the
    manual class is inside its cool-down."""
    payload = await _read_json(request, default={})
    reason = str(payload.get("reason", "manual trigger"))
    path = await asyncio.get_running_loop().run_in_executor(
        None, lambda: core.incidents.trigger(
            "manual", reason=reason, sync=True))
    if path is None:
        return web.json_response(
            {"status": "rate_limited", "bundle": None}, status=202)
    return web.json_response({"status": "written", "bundle": path})


async def _metrics(core, request):
    from .metrics import render_prometheus

    # off-loop like /v2/debug/*: the device-stats rows sum O(window-events)
    # under the collector lock — a scrape must not stall in-flight requests
    text = await asyncio.get_running_loop().run_in_executor(
        None, render_prometheus, core)
    return web.Response(
        text=text,
        content_type="text/plain",
        charset="utf-8",
    )


async def _get_logging(core, request):
    return web.json_response(core.log_settings)


async def _set_logging(core, request):
    body = await _read_json(request, default={})
    core.log_settings.update(body)
    return web.json_response(core.log_settings)


# -- shared memory ---------------------------------------------------------


def _shm_registry(core: InferenceCore, request: web.Request):
    return core.system_shm if "systemsharedmemory" in request.path else core.xla_shm


async def _shm_status(core, request):
    reg = _shm_registry(core, request)
    status = reg.status(request.match_info.get("name"))
    return web.json_response(list(status.values()))


async def _shm_register(core, request):
    reg = _shm_registry(core, request)
    name = request.match_info["name"]
    body = await _read_json(request)
    needed = (("key", "byte_size") if reg is core.system_shm
              else ("raw_handle", "byte_size"))
    missing = [k for k in needed if k not in body]
    if missing:
        raise InferError(
            f"shared memory registration missing field(s): {missing}")
    try:
        if reg is core.system_shm:
            reg.register(
                name, body["key"], int(body.get("offset", 0)),
                int(body["byte_size"]))
        else:
            handle = body["raw_handle"]
            if not isinstance(handle, dict) or "b64" not in handle:
                raise InferError(
                    "raw_handle must be an object with a 'b64' field")
            raw = base64.b64decode(handle["b64"], validate=True)
            reg.register(name, raw, int(body.get("device_id", 0)),
                         int(body["byte_size"]))
    except InferError:
        raise
    except (TypeError, ValueError, binascii.Error) as e:
        raise InferError(f"invalid shared memory registration: {e}")
    return web.Response(status=200)


async def _shm_unregister(core, request):
    reg = _shm_registry(core, request)
    reg.unregister(request.match_info.get("name"))
    return web.Response(status=200)


# -- infer -----------------------------------------------------------------


async def _infer(core, request: web.Request) -> web.Response:
    t_recv = time.monotonic_ns()
    # aiohttp inflates gzip/deflate request bodies transparently.
    raw = await request.read()

    header_len = request.headers.get(_HEADER_LEN)
    if header_len is not None:
        try:
            hlen = int(header_len)
        except ValueError:
            raise InferError(f"invalid {_HEADER_LEN} header: {header_len!r}")
        json_bytes, binary = raw[:hlen], raw[hlen:]
    else:
        json_bytes, binary = raw, b""
    try:
        body = json.loads(json_bytes)
    except Exception:
        raise InferError("failed to parse inference request JSON")

    req = _decode_request(
        request.match_info["model"], request.match_info.get("version", ""), body, binary
    )
    # trace propagation: record the client's correlation id (headers are
    # case-insensitive in aiohttp) so the tracer can join client and server
    req.client_request_id = request.headers.get(_REQUEST_ID_HDR, "")
    req.traceparent = request.headers.get(_TRACEPARENT_HDR, "")
    # span tracing: the read+parse window becomes the DECODE child span
    # (arrival_ns is left at construction time — queue statistics must not
    # absorb a slow client's body upload), and this frontend finalizes the
    # trace so SERIALIZE/NETWORK_WRITE land in it
    req.decode_start_ns = t_recv
    req.decode_end_ns = time.monotonic_ns()
    req.trace_handoff = True
    req.protocol = "http"
    # the memory governor's ledger entry: what this request actually put
    # on the wire (body bytes as received, post-inflate)
    req.wire_bytes = len(raw)
    # deadline propagation: the triton-timeout-us header (the restamped
    # remaining budget) wins over the body's `timeout` parameter
    apply_request_deadline(req, header_us=request.headers.get(_TIMEOUT_HDR))
    _stamp_qos(req, request)
    resp = await core.infer(req)
    trace = resp.trace
    try:
        t_ser0 = time.monotonic_ns() if trace is not None else 0
        default_binary = bool(
            req.parameters.get("binary_data_output", header_len is not None)
        )
        # wire fast path: per-(model, output-set) response templates stamp
        # only id / batch dims / payload sizes; tensor bytes ride zero-copy
        # memoryview segments into one gather (see server/wire.py)
        payload, json_len = encode_http_response(
            resp, {o.name: o for o in req.outputs}, default_binary,
            cache=core.http_wire_templates,
            generation=core.registry.generation(resp.model_name))
        if trace is not None:
            t_ser1 = time.monotonic_ns()
            trace.add_span("SERIALIZE", t_ser0, t_ser1)
        headers = {_HEADER_LEN: str(json_len)}
        if req.client_request_id:
            headers[_REQUEST_ID_HDR] = req.client_request_id
        accept = request.headers.get("Accept-Encoding", "")
        if "gzip" in accept and len(payload) > 1024:
            payload = gzip.compress(payload)
            headers["Content-Encoding"] = "gzip"
        response = web.Response(
            body=payload, headers=headers,
            content_type="application/octet-stream"
        )
        if trace is not None:
            # compression + response assembly up to the transport handoff
            # (aiohttp writes the socket after the handler returns)
            trace.add_span("NETWORK_WRITE", t_ser1, time.monotonic_ns())
        resp.count_request(t_recv)
    except BaseException as e:
        # a serialize/compress failure happens after the core reported
        # success — the flight record must still land as a failure
        # ("failures are always captured"), not as outcome="ok"
        if trace is not None:
            trace.mark_failed(e)
        raise
    finally:
        if trace is not None:
            await trace.emit_async()
    return response


def _decode_request(
    model_name: str, version: str, body: dict, binary: bytes
) -> InferRequest:
    # structural validation first: every client-controlled field that the
    # loop below indexes must 400 (not 500) when it has the wrong type
    if not isinstance(body, dict):
        raise InferError("inference request body must be a JSON object")
    if not isinstance(body.get("inputs", []), list) \
            or not isinstance(body.get("outputs", []), list):
        raise InferError("'inputs'/'outputs' must be arrays")
    if not isinstance(body.get("parameters", {}) or {}, dict):
        raise InferError("'parameters' must be an object")
    req = InferRequest(
        model_name=model_name,
        model_version=version,
        id=body.get("id", ""),
        parameters=body.get("parameters", {}) or {},
    )
    offset = 0
    for t in body.get("inputs", []):
        try:
            name, datatype = t["name"], t["datatype"]
            shape = tuple(int(s) for s in t["shape"])
        except (TypeError, KeyError, ValueError, AttributeError) as e:
            raise InferError(f"malformed input specification: {e}")
        params = t.get("parameters", {}) or {}
        if not isinstance(params, dict):
            raise InferError(f"input '{name}' parameters must be an object")
        tensor = InputTensor(name=name, datatype=datatype, shape=shape, parameters=params)
        shm_name = params.get("shared_memory_region")
        bin_size = params.get("binary_data_size")
        try:
            if shm_name:
                tensor.shm = ShmRef(
                    region_name=shm_name,
                    byte_size=int(params["shared_memory_byte_size"]),
                    offset=int(params.get("shared_memory_offset", 0)),
                )
            elif bin_size is not None:
                chunk = binary[offset: offset + int(bin_size)]
                if len(chunk) != int(bin_size):
                    raise InferError(
                        f"unexpected end of binary data for input '{name}'"
                    )
                offset += int(bin_size)
                tensor.data = _bytes_to_array(chunk, datatype, shape, name)
            elif "data" in t:
                tensor.data = _json_to_array(t["data"], datatype, shape, name)
            else:
                raise InferError(f"input '{name}' has no data")
        except (TypeError, KeyError, ValueError, AttributeError) as e:
            raise InferError(f"malformed input '{name}': {e}")
        req.inputs.append(tensor)

    for o in body.get("outputs", []) or []:
        try:
            params = o.get("parameters", {}) or {}
            if not isinstance(params, dict):
                raise InferError("output parameters must be an object")
            out = RequestedOutput(
                name=o["name"],
                binary_data=bool(params.get("binary_data", False)),
                class_count=int(params.get("classification", 0)),
                parameters=params,
            )
            shm_name = params.get("shared_memory_region")
            if shm_name:
                out.shm = ShmRef(
                    region_name=shm_name,
                    byte_size=int(params["shared_memory_byte_size"]),
                    offset=int(params.get("shared_memory_offset", 0)),
                )
        except (TypeError, KeyError, ValueError, AttributeError) as e:
            raise InferError(f"malformed output specification: {e}")
        req.outputs.append(out)
    return req


def _bytes_to_array(chunk: bytes, datatype: str, shape, name: str) -> np.ndarray:
    if datatype == "BYTES":
        try:
            flat = deserialize_bytes_tensor(chunk)
        except Exception as e:
            # the codec raises the CLIENT exception class on a truncated
            # length-prefixed stream — uncaught it would 500 a malformed
            # body instead of 400ing it (same fix as the gRPC decoder)
            raise InferError(
                f"malformed BYTES payload for input '{name}': {e}")
        return reshape_input(flat, shape, name)
    dt = triton_to_np_dtype(datatype)
    if dt is None:
        raise InferError(f"unsupported datatype '{datatype}' for input '{name}'")
    # math.prod over python ints (empty shape -> 1): same hot-path fix as
    # the gRPC decoder (benchmarks/HOTPATH_PROFILE.md)
    count = math.prod(shape)
    expected = count * dt.itemsize
    if len(chunk) != expected:
        raise InferError(
            f"unexpected total byte size {len(chunk)} for input '{name}', expecting {expected}"
        )
    return reshape_input(np.frombuffer(chunk, dtype=dt), shape, name)


def _json_to_array(data, datatype: str, shape, name: str = "") -> np.ndarray:
    if datatype == "BYTES":
        def coerce(x):
            if isinstance(x, str):
                return x.encode("utf-8")
            if isinstance(x, (bytes, bytearray, list)):
                return bytes(x)
            # bytes(int) would ALLOCATE that many zero bytes — a client-
            # controlled memory bomb, not a serialization
            raise InferError(
                f"BYTES input '{name}' elements must be strings or byte "
                f"arrays, got {type(x).__name__}")
        flat = np.array(
            [coerce(x) for x in _flatten(data)], dtype=np.object_)
        return reshape_input(flat, shape, name)
    dt = triton_to_np_dtype(datatype)
    if dt is None:
        raise InferError(f"unsupported datatype '{datatype}' for input '{name}'")
    try:
        arr = np.array(data, dtype=dt)
    except (ValueError, TypeError) as e:
        raise InferError(f"invalid data for input '{name}': {e}")
    return reshape_input(arr, shape, name)


def _flatten(x):
    if isinstance(x, list):
        for item in x:
            yield from _flatten(item)
    else:
        yield x


# Response encoding lives in server/wire.py (shared header builder +
# per-(model, output-set) templates + zero-copy readback segments).
