"""gRPC v2 frontend (grpc.aio).

Implements ``inference.GRPCInferenceService`` (this framework's own IDL,
``protocol/inference.proto``) — the RPC surface the reference gRPC client
drives (surveyed at grpc/_client.py).  Tensor data travels positionally in
``raw_input_contents``/``raw_output_contents`` (reference
grpc/_infer_input.py:160-174, _infer_result.py:63-97); typed
``InferTensorContents`` decoding is also supported for third-party stubs that
use it (e.g. the Go generated example).
"""

from __future__ import annotations

import asyncio
import math
import time
from typing import Any, Dict, Optional

import grpc
import numpy as np

from ..protocol import inference_pb2 as pb
from ..protocol.service import add_GRPCInferenceServiceServicer_to_server
from ..utils import deserialize_bytes_tensor, triton_to_np_dtype
from .core import InferenceCore
from .log import log_off_loop
from .memory import DEFAULT_MAX_REQUEST_BYTES
from .model import datatype_to_pb
from .qos import tenant_from_headers
from .types import (InferError, InferRequest, InputTensor,
                    RequestedOutput, ShmRef, apply_request_deadline,
                    apply_request_priority, reshape_input)
# the pb param codecs live in wire.py (shared with the response
# templates); re-exported here for the rest of the server package
from .wire import (build_pb_response, encode_pb_response, pb_param_to_py,
                   py_to_pb_param)


def _read_trace_metadata(req: InferRequest, context) -> None:
    """Fill the request's trace-propagation and QoS-identity fields from
    invocation metadata (`triton-request-id` / `traceparent` stamped by
    the instrumented clients; `triton-tenant` / `authorization` resolving
    the tenant, same precedence as the HTTP frontend)."""
    tenant_hdr = auth_hdr = None
    try:
        md = context.invocation_metadata() or ()
        for key, value in md:
            if key == "triton-request-id":
                req.client_request_id = value
            elif key == "traceparent":
                req.traceparent = value
            elif key == "triton-tenant":
                tenant_hdr = value
            elif key == "authorization":
                auth_hdr = value
    except Exception:
        pass  # metadata unavailable (e.g. gRPC-Web bridge test doubles)
    req.tenant = tenant_from_headers(tenant_hdr, auth_hdr)


def _decode_pb_request(request: pb.ModelInferRequest) -> InferRequest:
    req = InferRequest(
        model_name=request.model_name,
        model_version=request.model_version,
        id=request.id,
        parameters={k: pb_param_to_py(v) for k, v in request.parameters.items()},
    )
    # the v2 `timeout` parameter (µs) becomes the request's absolute
    # deadline; expired requests are dropped at dequeue with zero compute.
    # `priority` (0 = highest) is consumed into the QoS tier the same way
    apply_request_deadline(req)
    apply_request_priority(req)
    raw = list(request.raw_input_contents)
    # raw_input_contents carries entries ONLY for non-shm inputs, in input
    # order (reference wire semantics: grpc/_utils.py packs raw buffers in a
    # parallel list, shm inputs contribute no entry).
    n_raw_expected = sum(
        1 for t in request.inputs
        if "shared_memory_region" not in t.parameters
    )
    if raw and len(raw) != n_raw_expected:
        raise InferError(
            "raw_input_contents does not match the number of non-shared-"
            f"memory inputs (got {len(raw)}, expected {n_raw_expected})"
        )
    raw_idx = 0
    for t in request.inputs:
        shape = tuple(int(s) for s in t.shape)
        params = {k: pb_param_to_py(v) for k, v in t.parameters.items()}
        tensor = InputTensor(name=t.name, datatype=t.datatype, shape=shape, parameters=params)
        shm_name = params.get("shared_memory_region")
        if shm_name:
            try:
                tensor.shm = ShmRef(
                    region_name=shm_name,
                    byte_size=int(params["shared_memory_byte_size"]),
                    offset=int(params.get("shared_memory_offset", 0)),
                )
            except (KeyError, TypeError, ValueError) as e:
                raise InferError(
                    f"malformed shared-memory parameters for input "
                    f"'{t.name}': {e}")
        elif raw:
            tensor.data = _raw_to_array(raw[raw_idx], t.datatype, shape, t.name)
            raw_idx += 1
        elif t.HasField("contents"):
            tensor.data = _contents_to_array(t.contents, t.datatype, shape, t.name)
        else:
            raise InferError(f"input '{t.name}' has no data")
        req.inputs.append(tensor)
    for o in request.outputs:
        params = {k: pb_param_to_py(v) for k, v in o.parameters.items()}
        out = RequestedOutput(
            name=o.name,
            class_count=int(params.get("classification", 0)),
            parameters=params,
        )
        shm_name = params.get("shared_memory_region")
        if shm_name:
            try:
                out.shm = ShmRef(
                    region_name=shm_name,
                    byte_size=int(params["shared_memory_byte_size"]),
                    offset=int(params.get("shared_memory_offset", 0)),
                )
            except (KeyError, TypeError, ValueError) as e:
                raise InferError(
                    f"malformed shared-memory parameters for output "
                    f"'{o.name}': {e}")
        req.outputs.append(out)
    return req


def _raw_to_array(chunk: bytes, datatype: str, shape, name: str) -> np.ndarray:
    if datatype == "BYTES":
        try:
            flat = deserialize_bytes_tensor(chunk)
        except Exception as e:
            # the codec raises the CLIENT exception class on a truncated
            # length-prefixed stream — uncaught it escapes the InferError
            # handlers as UNKNOWN/500 instead of a clean client error
            # (surfaced by the gRPC fuzz pass)
            raise InferError(
                f"malformed BYTES payload for input '{name}': {e}")
        return reshape_input(flat, shape, name)
    dt = triton_to_np_dtype(datatype)
    if dt is None:
        raise InferError(f"unsupported datatype '{datatype}' for input '{name}'")
    # math.prod over python ints (empty shape -> 1): np.prod pays a
    # ufunc-reduction dispatch per request on this per-tensor hot path
    count = math.prod(shape)
    if len(chunk) != count * dt.itemsize:
        raise InferError(
            f"unexpected total byte size {len(chunk)} for input '{name}', "
            f"expecting {count * dt.itemsize}"
        )
    return reshape_input(np.frombuffer(chunk, dtype=dt), shape, name)


_CONTENTS_FIELD = {
    "BOOL": "bool_contents",
    "INT8": "int_contents",
    "INT16": "int_contents",
    "INT32": "int_contents",
    "INT64": "int64_contents",
    "UINT8": "uint_contents",
    "UINT16": "uint_contents",
    "UINT32": "uint_contents",
    "UINT64": "uint64_contents",
    "FP32": "fp32_contents",
    "FP64": "fp64_contents",
    "BYTES": "bytes_contents",
}


def _contents_to_array(contents, datatype: str, shape, name: str) -> np.ndarray:
    field = _CONTENTS_FIELD.get(datatype)
    if field is None:
        raise InferError(
            f"typed contents not supported for datatype '{datatype}' (input '{name}')"
        )
    values = list(getattr(contents, field))
    if datatype == "BYTES":
        return reshape_input(
            np.array(values, dtype=np.object_), shape, name)
    return reshape_input(
        np.array(values, dtype=triton_to_np_dtype(datatype)), shape, name)


# Response encoding lives in server/wire.py: ``build_pb_response`` is the
# slow path (streams use it — their parameter flags vary per frame),
# ``encode_pb_response`` adds the per-(model, output-set) template fast
# path the unary RPC rides.


class InferenceServicer:
    def __init__(self, core: InferenceCore):
        self._core = core

    # -- health / metadata -------------------------------------------------
    async def ServerLive(self, request, context):
        return pb.ServerLiveResponse(live=self._core.live)

    async def ServerReady(self, request, context):
        # mirrors HTTP /v2/health/ready: not-ready during startup warmup
        # or while any model is mid-load (see InferenceCore.ready)
        return pb.ServerReadyResponse(ready=self._core.ready())

    async def ModelReady(self, request, context):
        # registry-ready AND not quarantined after device faults
        # (mirrors HTTP /v2/models/{m}/ready; InferenceCore.model_ready)
        return pb.ModelReadyResponse(
            ready=self._core.model_ready(request.name, request.version)
        )

    async def ServerMetadata(self, request, context):
        md = self._core.server_metadata()
        return pb.ServerMetadataResponse(
            name=md["name"], version=md["version"], extensions=md["extensions"]
        )

    async def ModelMetadata(self, request, context):
        try:
            model = self._core.registry.get(request.name, request.version)
        except InferError as e:
            await context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        md = model.metadata()
        resp = pb.ModelMetadataResponse(
            name=md["name"], versions=md["versions"], platform=md["platform"]
        )
        for io, dest in ((md["inputs"], resp.inputs), (md["outputs"], resp.outputs)):
            for t in io:
                dest.add(name=t["name"], datatype=t["datatype"], shape=t["shape"])
        return resp

    async def ModelConfig(self, request, context):
        try:
            model = self._core.registry.get(request.name, request.version)
        except InferError as e:
            await context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        return pb.ModelConfigResponse(config=model.config)

    async def ModelStatistics(self, request, context):
        try:
            stats = self._core.statistics(request.name or None, request.version)
        except InferError as e:
            await context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        resp = pb.ModelStatisticsResponse()
        for s in stats:
            ms = resp.model_stats.add()
            ms.name = s["name"]
            ms.version = s["version"]
            ms.last_inference = s["last_inference"]
            ms.inference_count = s["inference_count"]
            ms.execution_count = s["execution_count"]
            ist = s["inference_stats"]
            for key in ("success", "fail", "queue", "compute_input", "compute_infer", "compute_output"):
                getattr(ms.inference_stats, key).count = ist[key]["count"]
                getattr(ms.inference_stats, key).ns = ist[key]["ns"]
        return resp

    # -- repository --------------------------------------------------------
    async def RepositoryIndex(self, request, context):
        resp = pb.RepositoryIndexResponse()
        for entry in self._core.registry.index(ready_only=request.ready):
            resp.models.add(
                name=entry["name"],
                version=entry.get("version", "1"),
                state=entry["state"],
                reason=entry.get("reason", ""),
            )
        return resp

    async def RepositoryModelLoad(self, request, context):
        params = request.parameters
        config_override = None
        files = {}
        for k, v in params.items():
            which = v.WhichOneof("parameter_choice")
            if k == "config" and which == "string_param":
                config_override = v.string_param
            elif k.startswith("file:") and which == "bytes_param":
                import base64

                files[k] = base64.b64encode(v.bytes_param).decode()
        try:
            await self._core.load_model(
                request.model_name, config_override=config_override,
                files=files or None
            )
        except InferError as e:
            await context.abort(grpc.StatusCode.INTERNAL, str(e))
        return pb.RepositoryModelLoadResponse()

    async def RepositoryModelUnload(self, request, context):
        unload_dependents = False
        p = request.parameters.get("unload_dependents")
        if p is not None and p.WhichOneof("parameter_choice") == "bool_param":
            unload_dependents = p.bool_param
        try:
            self._core.registry.unload(request.model_name, unload_dependents)
        except InferError as e:
            await context.abort(grpc.StatusCode.INTERNAL, str(e))
        self._core.retire_name_caches(request.model_name)
        log_off_loop(
            self._core.log.info,
            f"successfully unloaded model '{request.model_name}'")
        return pb.RepositoryModelUnloadResponse()

    # -- shared memory -----------------------------------------------------
    async def SystemSharedMemoryStatus(self, request, context):
        resp = pb.SystemSharedMemoryStatusResponse()
        for name, r in self._core.system_shm.status(request.name or None).items():
            resp.regions[name].name = r["name"]
            resp.regions[name].key = r["key"]
            resp.regions[name].offset = r["offset"]
            resp.regions[name].byte_size = r["byte_size"]
        return resp

    async def SystemSharedMemoryRegister(self, request, context):
        try:
            self._core.system_shm.register(
                request.name, request.key, request.offset, request.byte_size
            )
        except InferError as e:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.SystemSharedMemoryRegisterResponse()

    async def SystemSharedMemoryUnregister(self, request, context):
        self._core.system_shm.unregister(request.name or None)
        return pb.SystemSharedMemoryUnregisterResponse()

    async def CudaSharedMemoryStatus(self, request, context):
        resp = pb.CudaSharedMemoryStatusResponse()
        for name, r in self._core.xla_shm.status(request.name or None).items():
            resp.regions[name].name = r["name"]
            resp.regions[name].device_id = r["device_id"]
            resp.regions[name].byte_size = r["byte_size"]
        return resp

    async def CudaSharedMemoryRegister(self, request, context):
        try:
            self._core.xla_shm.register(
                request.name, request.raw_handle, request.device_id, request.byte_size
            )
        except InferError as e:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.CudaSharedMemoryRegisterResponse()

    async def CudaSharedMemoryUnregister(self, request, context):
        self._core.xla_shm.unregister(request.name or None)
        return pb.CudaSharedMemoryUnregisterResponse()

    # -- trace / logging ---------------------------------------------------
    async def TraceSetting(self, request, context):
        from .trace import TRACE_DEFAULTS, validate_trace_update

        model = request.model_name or ""
        if model:
            try:
                self._core.registry.get(model)
                # empty value in model scope clears the override (back to
                # inheriting global); explicit values override
                update = {k: list(v.value)
                          for k, v in request.settings.items() if v.value}
                cleared = []
                for k, v in request.settings.items():
                    if v.value:
                        continue
                    if k not in TRACE_DEFAULTS:
                        # same contract as HTTP: a typo'd clear must not
                        # silently succeed
                        raise InferError(
                            f"unknown trace setting '{k}'", 400)
                    cleared.append(k)
                validate_trace_update(update, model_scope=True)
            except InferError as e:
                code = (grpc.StatusCode.UNIMPLEMENTED
                        if e.http_status == 501
                        else grpc.StatusCode.INVALID_ARGUMENT)
                await context.abort(code, str(e))
            if update or cleared:
                self._core.tracer.update_model(model, update, cleared)
            resp = pb.TraceSettingResponse()
            for k, vals in self._core.tracer.effective_settings(
                    model).items():
                resp.settings[k].value.extend(vals)
            return resp
        # an empty value list (SetInParent with no values) clears the key back
        # to its default — reference update_trace_settings(None) contract
        update = {}
        try:
            for k, v in request.settings.items():
                if v.value:
                    update[k] = list(v.value)
                else:
                    # empty clears to default; a typo'd clear flows into
                    # the shared validator, which rejects unknown keys —
                    # same contract as model scope
                    update[k] = list(TRACE_DEFAULTS.get(k, []))
            validate_trace_update(update)
        except InferError as e:
            code = (grpc.StatusCode.UNIMPLEMENTED if e.http_status == 501
                    else grpc.StatusCode.INVALID_ARGUMENT)
            await context.abort(code, str(e))
        if update:  # get_trace_settings sends an empty map — a read, not an
            # update; it must not reset the sampling counters or count budget
            try:
                self._core.tracer.apply(update)
            except InferError as e:  # the profiler did not start (503)
                await context.abort(_grpc_code(e), str(e))
        resp = pb.TraceSettingResponse()
        for k, vals in self._core.trace_settings.items():
            resp.settings[k].value.extend(vals)
        return resp

    async def FlightRecorder(self, request, context):
        """Debug surface: the flight recorder's recent ring + pinned
        outliers, as the same JSON the HTTP endpoint serves (see
        protocol/debug_pb2.py for why JSON-in-proto).  Snapshot +
        serialization run off-loop — a large ring must not stall
        in-flight inference (same contract as the HTTP endpoint)."""
        import json as _json

        from ..protocol import debug_pb2 as pb_debug
        from .flight_recorder import parse_snapshot_limit

        model = request.model_name or None
        try:
            # proto uint32 cannot carry a negative or non-integer, but the
            # validation mirrors HTTP's ?limit= contract anyway so both
            # wire surfaces stay byte-for-byte identical in behavior (and
            # a future int-typed field cannot silently regress it)
            limit = parse_snapshot_limit(request.limit or 0)
        except InferError as e:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        body = await asyncio.get_running_loop().run_in_executor(
            None, lambda: _json.dumps(
                self._core.flight_recorder.snapshot(
                    model=model, limit=limit)))
        return pb_debug.FlightRecorderResponse(payload_json=body)

    async def DeviceStats(self, request, context):
        """Debug surface: the device/scheduler observability snapshot
        (device_stats + SLO state) — same JSON as HTTP's
        ``GET /v2/debug/device_stats``, same off-loop serialization."""
        import json as _json

        from ..protocol import debug_pb2 as pb_debug

        model = request.model_name or None

        def _snap():
            out = self._core.device_stats.snapshot(model=model)
            out["slo"] = self._core.slo.snapshot(model=model)
            # byte-admission ledger, same shape as the HTTP surface
            out["memory"] = self._core.memory.snapshot()
            from . import kvcache

            out["kv_cache"] = kvcache.snapshot()
            return _json.dumps(out)

        body = await asyncio.get_running_loop().run_in_executor(None, _snap)
        return pb_debug.DeviceStatsResponse(payload_json=body)

    async def Costs(self, request, context):
        """Debug surface: the per-tenant cost-attribution ledger
        (server/costs.py) — same JSON as HTTP's ``GET /v2/debug/costs``,
        same off-loop serialization."""
        import json as _json

        from ..protocol import debug_pb2 as pb_debug

        model = request.model_name or None
        body = await asyncio.get_running_loop().run_in_executor(
            None, lambda: _json.dumps(
                self._core.cost_ledger.snapshot(model=model)))
        return pb_debug.CostsResponse(payload_json=body)

    async def LogSettings(self, request, context):
        for k, v in request.settings.items():
            which = v.WhichOneof("parameter_choice")
            if which:
                self._core.log_settings[k] = getattr(v, which)
        resp = pb.LogSettingsResponse()
        for k, val in self._core.log_settings.items():
            if isinstance(val, bool):
                resp.settings[k].bool_param = val
            elif isinstance(val, int):
                resp.settings[k].uint32_param = val
            else:
                resp.settings[k].string_param = str(val)
        return resp

    # -- inference ---------------------------------------------------------
    async def ModelInfer(self, request, context):
        try:
            t_recv = time.monotonic_ns()
            req = _decode_pb_request(request)
            _read_trace_metadata(req, context)
            # span tracing: proto decode is the DECODE child span
            # (arrival_ns stays at construction — queue statistics must not
            # absorb proto-decode time); this frontend finalizes so
            # SERIALIZE/NETWORK_WRITE land in the trace
            req.decode_start_ns = t_recv
            req.decode_end_ns = time.monotonic_ns()
            req.trace_handoff = True
            req.protocol = "grpc"
            # the memory governor's ledger entry: serialized message size
            req.wire_bytes = request.ByteSize()
            resp = await self._core.infer(req)
        except InferError as e:
            rid = getattr(req, "client_request_id", "") \
                if "req" in locals() else ""
            if e.http_status >= 500:
                log_off_loop(
                    self._core.log.error,
                    f"grpc ModelInfer '{request.model_name}' failed: {e}",
                    rid)
            elif self._core.log.verbose_enabled():
                log_off_loop(
                    self._core.log.verbose, 1,
                    f"grpc ModelInfer '{request.model_name}' -> "
                    f"{e.http_status}: {e}", rid)
            ra = getattr(e, "retry_after_s", None)
            if ra is not None:
                # server pushback (gRPC A6): the resilience layer reads
                # this trailing metadata and backs off for exactly this
                # horizon instead of its computed jitter
                try:
                    context.set_trailing_metadata(
                        (("retry-after-ms", str(int(ra * 1000))),))
                except Exception:
                    pass  # metadata already sent / bridge test double
            await context.abort(_grpc_code(e), str(e))
        if self._core.log.verbose_enabled():
            log_off_loop(
                self._core.log.verbose, 1,
                f"grpc ModelInfer '{request.model_name}' -> OK",
                req.client_request_id)
        if req.client_request_id:
            # echo the correlation id in trailing metadata (the response
            # parameters carry it too, for clients that never see metadata)
            try:
                context.set_trailing_metadata(
                    (("triton-request-id", req.client_request_id),))
            except Exception:
                pass  # metadata already sent / transport gone
        trace = resp.trace
        try:
            t_ser0 = time.monotonic_ns() if trace is not None else 0
            # wire fast path: template-stamped response message (see
            # server/wire.py) — the one remaining payload copy is the
            # protobuf-required bytes materialization
            pb_resp = encode_pb_response(
                resp, cache=self._core.grpc_wire_templates,
                generation=self._core.registry.generation(resp.model_name))
            if trace is not None:
                t_ser1 = time.monotonic_ns()
                trace.add_span("SERIALIZE", t_ser0, t_ser1)
                # grpc.aio serializes+writes after the handler returns; this
                # span covers the handoff work still visible from here
                trace.add_span("NETWORK_WRITE", t_ser1, time.monotonic_ns())
            resp.count_request(t_recv)
        except BaseException as e:
            # encode failures after the core reported success must still
            # land in the flight record as failures (same contract as the
            # HTTP frontend)
            if trace is not None:
                trace.mark_failed(e)
            raise
        finally:
            if trace is not None:
                await trace.emit_async()
        return pb_resp

    async def ModelStreamInfer(self, request_iterator, context):
        """Bidi stream: requests arrive as they're sent; each produces one or
        more ``ModelStreamInferResponse``s (errors travel in-band in
        ``error_message``, reference _infer_stream.py:142-167)."""
        async for request in request_iterator:
            try:
                req = _decode_pb_request(request)
                _read_trace_metadata(req, context)
                req.protocol = "grpc"
                req.wire_bytes = request.ByteSize()
                enable_empty_final = bool(
                    req.parameters.get("triton_enable_empty_final_response", False)
                )
                agen = self._core.infer_stream(req)
                try:
                    async for resp in agen:
                        is_empty_final = (
                            not resp.outputs
                            and resp.parameters.get("triton_final_response") is True
                        )
                        if is_empty_final and not enable_empty_final:
                            continue
                        tr = resp.trace
                        if tr is None:
                            yield pb.ModelStreamInferResponse(
                                infer_response=build_pb_response(resp)
                            )
                            continue
                        # traced stream: proto encode + transport handoff
                        # per flushed chunk, batched at the token stride
                        # inside record_write
                        t0 = time.monotonic_ns()
                        yield pb.ModelStreamInferResponse(
                            infer_response=build_pb_response(resp)
                        )
                        tr.record_write(t0, time.monotonic_ns())
                finally:
                    # deterministic close: a broken bidi transport must
                    # reach the core's stream envelope (cancel accounting,
                    # the stream trace record) now, not at GC time
                    await agen.aclose()
            except InferError as e:
                # the bidi wire has no per-message grpc code, so the
                # status rides in-band as a "[NNN] " prefix — streaming
                # clients (grpc/_utils.stream_error_to_exception) map it
                # back to a typed status so shed/deadline failures stay
                # classifiable on streams too
                yield pb.ModelStreamInferResponse(
                    error_message=f"[{e.http_status}] {e}")
            except Exception as e:  # pragma: no cover - defensive
                yield pb.ModelStreamInferResponse(error_message=str(e))


def _grpc_code(e: InferError) -> grpc.StatusCode:
    return {
        400: grpc.StatusCode.INVALID_ARGUMENT,
        404: grpc.StatusCode.NOT_FOUND,
        # oversize wire payloads (the --max-request-bytes cap; normally
        # rejected by the channel option before the handler runs, but a
        # handler-raised 413 — e.g. through the gRPC-Web bridge — must
        # map to the same code the transport rejection carries)
        413: grpc.StatusCode.RESOURCE_EXHAUSTED,
        # resilience layer: shed load / drain / blown deadline map to the
        # codes the client retry policy gates on (RESOURCE_EXHAUSTED and
        # UNAVAILABLE retryable; DEADLINE_EXCEEDED deliberately not)
        429: grpc.StatusCode.RESOURCE_EXHAUSTED,
        503: grpc.StatusCode.UNAVAILABLE,
        504: grpc.StatusCode.DEADLINE_EXCEEDED,
        500: grpc.StatusCode.INTERNAL,
    }.get(e.http_status, grpc.StatusCode.UNKNOWN)


def build_grpc_server(
    core: InferenceCore, address: str = "[::]:8001", tls=None,
    reuse_port: bool = False,
    max_request_bytes: int = DEFAULT_MAX_REQUEST_BYTES,
) -> "grpc.aio.Server":
    cap = max(0, int(max_request_bytes or 0))
    server = grpc.aio.server(
        options=[
            ("grpc.max_send_message_length", -1),
            # wire ingress cap (server/memory.py layer 1): a REAL channel
            # option, so an oversize message is refused by the transport
            # — RESOURCE_EXHAUSTED carrying both sizes ("Received message
            # larger than max (N vs. M)") — before the body ever
            # materializes in this process.  0 = explicit opt-out
            # (--max-request-bytes 0), restoring the old unbounded -1
            ("grpc.max_receive_message_length", cap if cap else -1),
            # explicit either way: ON for the multi-process frontend
            # topology (N workers share the port, kernel balances
            # accepts), OFF for single-process so a double-bind fails
            # loudly instead of silently splitting traffic (gRPC's
            # Linux default is ON)
            ("grpc.so_reuseport", 1 if reuse_port else 0),
        ]
    )
    add_GRPCInferenceServiceServicer_to_server(InferenceServicer(core), server)
    if tls is not None:
        server.add_secure_port(address, tls.grpc_credentials())
    else:
        server.add_insecure_port(address)
    return server
