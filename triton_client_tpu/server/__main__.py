"""CLI: ``python -m triton_client_tpu.server`` — run the v2 serving harness.

Examples::

    # serve the built-in model zoo (simple, simple_identity, ...):
    python -m triton_client_tpu.server --zoo

    # serve a Triton-style model repository directory:
    python -m triton_client_tpu.server --model-repository ./models
"""

from __future__ import annotations

import argparse
import asyncio
import os

from .core import InferenceCore
from .frontends import start_frontends
from .registry import ModelRegistry
from .tls import maybe_tls


def main() -> None:
    parser = argparse.ArgumentParser(description="triton_client_tpu serving harness")
    parser.add_argument("--model-repository", default=None, help="model repository dir")
    parser.add_argument("--zoo", action="store_true", help="register the built-in model zoo")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--http-port", type=int, default=8000)
    parser.add_argument("--grpc-port", type=int, default=8001)
    parser.add_argument("--frontends", type=int, default=1, metavar="N",
                        help="number of frontend processes sharing the "
                        "HTTP/gRPC ports via SO_REUSEPORT (the kernel "
                        "load-balances accepted connections), so the "
                        "serving data plane scales past one Python "
                        "process's GIL.  Each worker exposes its own "
                        "metrics port at --metrics-port + index; client "
                        "shared-memory registrations are shared across "
                        "workers through a manifest directory.  Default 1 "
                        "(single process, no SO_REUSEPORT)")
    parser.add_argument("--frontend-worker", type=int, default=None,
                        help=argparse.SUPPRESS)  # internal: worker index
    parser.add_argument("--worker-restart-limit", type=int, default=5,
                        metavar="K",
                        help="self-healing supervisor storm bound: a "
                        "crashed frontend worker is restarted with capped "
                        "exponential backoff, but K crashes of one worker "
                        "inside --worker-restart-window fail the whole "
                        "fleet fast (a broken binary must not hot-loop); "
                        "1 restores the old fail-fast-on-first-crash "
                        "behavior (default 5)")
    parser.add_argument("--worker-restart-window", type=float, default=30.0,
                        metavar="S",
                        help="sliding window (seconds) the storm bound "
                        "counts crashes over; crashes aging out of it also "
                        "reset the restart backoff (default 30)")
    parser.add_argument("--autoscale", action="append", default=None,
                        metavar="MODEL=MIN..MAX",
                        help="enable closed-loop instance autoscaling for "
                        "MODEL between MIN and MAX concurrent batches "
                        "(repeatable; either bound may be omitted around "
                        "'..').  Scale-out triggers on SLO burn rate at/"
                        "over --slo-burn-threshold or a deep batcher "
                        "backlog; scale-in on sustained idle duty cycle.  "
                        "Model configs can declare the same via "
                        "autoscale.min_instances / autoscale.max_instances "
                        "parameters")
    parser.add_argument("--autoscale-interval", type=float, default=1.0,
                        metavar="S",
                        help="fleet control-loop evaluation period "
                        "(default 1.0s)")
    parser.add_argument("--verbose", "-v", action="store_true")
    parser.add_argument("--ssl-certfile", default=None,
                        help="serve HTTPS/secure-gRPC with this PEM cert chain")
    parser.add_argument("--ssl-keyfile", default=None,
                        help="PEM private key matching --ssl-certfile")
    parser.add_argument("--capture-slower-than", default="p99",
                        metavar="P|MS",
                        help="flight-recorder watchdog threshold: a live "
                        "per-model quantile (p50/p90/p95/p99/p999, default "
                        "p99) or an absolute milliseconds value — requests "
                        "beyond it (and every failure) are pinned with a "
                        "full span tree")
    parser.add_argument("--flight-recorder-size", type=int, default=1024,
                        help="ring-buffer capacity of the always-on "
                        "flight recorder (recent-request summaries)")
    parser.add_argument("--flight-recorder-outliers", type=int, default=32,
                        help="pinned-outlier buffer capacity (slow/failed "
                        "requests with full span trees)")
    parser.add_argument("--no-flight-recorder", action="store_true",
                        help="disable per-request flight recording "
                        "entirely (the /v2/debug/flight_recorder surface "
                        "stays up but records nothing)")
    parser.add_argument("--slo", action="append", default=None,
                        metavar="MODEL=P99_MS[:AVAILABILITY]",
                        help="per-model SLO (repeatable): p99 latency "
                        "target in ms plus an availability objective "
                        "(default 0.999).  Drives the nv_slo_burn_rate / "
                        "nv_slo_budget_remaining gauges (5m/1h "
                        "multi-window burn rates over 1-availability "
                        "error budget) and burn-rate-triggered flight-"
                        "recorder pinning; model configs can declare the "
                        "same via slo.p99_ms / slo.availability "
                        "parameters")
    parser.add_argument("--slo-burn-threshold", type=float, default=None,
                        metavar="X",
                        help="multi-window breach threshold: a model is "
                        "breaching (and SLO-bad requests are pinned) when "
                        "BOTH the 5m and 1h burn rates exceed this "
                        "(default 14.4, the canonical fast-burn page "
                        "threshold)")
    parser.add_argument("--profile-hz", type=float, default=None,
                        metavar="HZ",
                        help="always-on host sampling profiler rate "
                        "(folded stacks per thread role, nv_host_* "
                        "metrics, /v2/debug/profile).  Default from "
                        "TRITON_TPU_PROFILE_HZ, else 19; 0 disables the "
                        "sampler (the loop-lag probe and GC accounting "
                        "stay on — they are effectively free)")
    parser.add_argument("--incident-dir", default=None, metavar="DIR",
                        help="directory for automatic incident bundles "
                        "(postmortems on SLO burn, worker crash, watchdog "
                        "storm, chaos draws, SIGUSR2, or POST "
                        "/v2/debug/incident) and the faulthandler dump "
                        "file.  Default from TRITON_TPU_INCIDENT_DIR, "
                        "else <tmpdir>/tc-tpu-incidents")
    parser.add_argument("--incident-keep", type=int, default=8, metavar="N",
                        help="keep-last-N incident bundle retention: the "
                        "oldest bundles beyond N are pruned after each "
                        "write, so a flapping trigger cannot fill the "
                        "disk (default 8)")
    parser.add_argument("--no-device-stats", action="store_true",
                        help="disable the device/scheduler stats "
                        "collector (nv_tpu_* metrics, batcher tick "
                        "profiling) — the A/B lever bench.py uses to "
                        "bound its fast-path cost")
    parser.add_argument("--drain-timeout", type=float, default=10.0,
                        metavar="S",
                        help="graceful-drain budget on SIGINT/SIGTERM: "
                        "stop accepting (new requests get 503 + "
                        "Retry-After, readiness goes false), wait this "
                        "long for in-flight requests, then tear down")
    from .memory import DEFAULT_MAX_REQUEST_BYTES

    parser.add_argument("--max-request-bytes", type=int,
                        default=DEFAULT_MAX_REQUEST_BYTES, metavar="N",
                        help="wire ingress cap on BOTH frontends: any "
                        "request larger than N bytes is refused before "
                        "its body materializes (HTTP 413 / gRPC "
                        "RESOURCE_EXHAUSTED carrying the limit).  A bare "
                        "serve is bounded by default (64 MiB); 0 is the "
                        "explicit opt-out restoring unbounded ingress")
    parser.add_argument("--mem-budget-bytes", type=int, default=0,
                        metavar="N",
                        help="host byte budget for queued + in-flight "
                        "request/response payloads: over-budget arrivals "
                        "are shed tier-aware (best-effort and largest "
                        "first) with typed 429 + Retry-After instead of "
                        "growing toward the OOM killer (0 = track only, "
                        "never shed)")
    parser.add_argument("--max-queue-size", type=int, default=0,
                        help="default per-model admission bound: requests "
                        "beyond this many pending per model are shed with "
                        "HTTP 429 / gRPC RESOURCE_EXHAUSTED + Retry-After "
                        "(0 = unbounded; a model config's max_queue_size "
                        "parameter overrides per model)")
    parser.add_argument("--shed-retry-after", type=float, default=0.25,
                        metavar="S",
                        help="BASE pushback horizon (seconds) sent with "
                        "shed responses (Retry-After / retry-after-ms); "
                        "the actual horizon scales with the shed tier's "
                        "queue depth")
    parser.add_argument("--qos-tiers", type=int, default=4,
                        help="number of QoS priority tiers; the v2 request "
                        "priority parameter (0 = highest) maps to tier "
                        "min(priority, tiers-1) and the last tier is the "
                        "preemptible best-effort lane (default 4)")
    parser.add_argument("--qos-weights", default=None, metavar="W0,W1,...",
                        help="weighted-fair dequeue weights, one per tier "
                        "(e.g. '8,4,2,1'); default: strict priority")
    parser.add_argument("--qos-tenant-rate", type=float, default=0.0,
                        metavar="RPS",
                        help="default per-tenant token-bucket rate in "
                        "requests/s (0 = no tenant rate limiting); the "
                        "tenant comes from the triton-tenant header or "
                        "basic-auth username, else 'anonymous'")
    parser.add_argument("--qos-tenant-burst", type=float, default=None,
                        help="token-bucket burst allowance (default: "
                        "max(1, rate))")
    parser.add_argument("--qos-tenant-limit", action="append", default=None,
                        metavar="NAME=RATE[:BURST]",
                        help="per-tenant rate override (repeatable); "
                        "RATE 0 exempts the tenant from rate limiting")
    parser.add_argument("--qos-best-effort-fraction", type=float,
                        default=0.5, metavar="F",
                        help="fraction of a model's max_queue_size the "
                        "best-effort tier may fill before it is shed "
                        "(tier 0 always gets 100%%; intermediate tiers "
                        "interpolate; default 0.5)")
    parser.add_argument("--kv-cache-bytes", action="append", default=None,
                        metavar="MODEL=N | N",
                        help="prefix/KV-cache byte budget: 'MODEL=N' pins "
                        "a per-model block-store budget, a bare 'N' sets "
                        "the default for every decode model (repeatable; "
                        "equivalent to TRITON_TPU_KV_CACHE_BYTES[_MODEL]; "
                        "0/unset = cache off).  Block granularity comes "
                        "from TRITON_TPU_KV_BLOCK_TOKENS (default 64)")
    parser.add_argument("--cache-budget-bytes", type=int, default=0,
                        help="byte budget across all response-cache "
                        "entries; inserts evict LRU entries to fit "
                        "(0 = entry-count bound only).  Per-model TTL "
                        "comes from the model config's "
                        "response_cache.ttl_s parameter")
    parser.add_argument("--chaos", type=float, default=0.0, metavar="RATE",
                        help="fault-injection rate in [0,1]: each request "
                        "draws from a seeded RNG and at RATE gets a fault "
                        "from --chaos-kinds (testing the retry/shed/"
                        "deadline paths end to end; injected faults are "
                        "pinned by the flight recorder)")
    parser.add_argument("--chaos-kinds", default="error",
                        help="comma list of latency,error,abort,"
                        "worker_kill,load_fail,mem_pressure,device_error "
                        "(default: error).  device_error fires at the "
                        "decode worker's dispatch boundaries: it "
                        "invalidates the donated bucket buffers and "
                        "raises an XLA-shaped failure, driving the real "
                        "rebuild / generation-recovery / quarantine path")
    parser.add_argument("--chaos-seed", type=int, default=0,
                        help="RNG seed — a fixed seed reproduces the "
                        "exact fault sequence")
    parser.add_argument("--chaos-latency-ms", type=float, default=50.0,
                        help="added delay for latency faults")
    parser.add_argument("--chaos-model", action="append", default=None,
                        metavar="NAME",
                        help="restrict injection to this model "
                        "(repeatable; default: all models)")
    parser.add_argument("--chaos-transient", type=float, default=0.0,
                        metavar="S",
                        help="recovery window after each injected fault "
                        "(seconds): models time-correlated transient "
                        "faults, so prompt retries land clean "
                        "(0 = independent per-request draws)")
    parser.add_argument("--chaos-pressure-s", type=float, default=1.0,
                        metavar="S",
                        help="mem_pressure window: how long each draw "
                        "holds the shrunken byte budget before it "
                        "restores on its own (default 1.0s)")
    parser.add_argument("--chaos-pressure-factor", type=float, default=0.5,
                        metavar="F",
                        help="mem_pressure shrink: the live byte budget "
                        "drops to F x --mem-budget-bytes while a "
                        "pressure window holds (default 0.5)")
    parser.add_argument("--device-fault-threshold", type=int, default=3,
                        metavar="K",
                        help="dispatch faults inside --device-fault-window "
                        "that quarantine a model: not-ready on both "
                        "protocols, typed retryable 503s with pushback "
                        "until a probe dispatch succeeds (default 3)")
    parser.add_argument("--device-fault-window", type=float, default=30.0,
                        metavar="S",
                        help="sliding window for the K-fault quarantine "
                        "detector (default 30s)")
    parser.add_argument("--device-fault-probe-backoff", type=float,
                        default=1.0, metavar="S",
                        help="initial delay before a quarantined model's "
                        "first probe dispatch; doubles per failed probe "
                        "(default 1s)")
    parser.add_argument("--device-fault-probe-backoff-max", type=float,
                        default=30.0, metavar="S",
                        help="probe backoff ceiling (default 30s)")
    parser.add_argument("--tick-stall-ms", type=float, default=None,
                        metavar="MS",
                        help="arm the decode readback watchdog: a tick/"
                        "prefill readback that takes longer than MS to "
                        "resolve reports a tick_stall device fault and "
                        "quarantines the model (a wedged dispatch cannot "
                        "be killed host-side — this reroutes traffic and "
                        "captures the incident while it is stuck; sets "
                        "TRITON_TPU_TICK_STALL_MS)")
    parser.add_argument("--metrics-port", type=int, default=8002,
                        help="dedicated Prometheus /metrics port (Triton "
                        "convention; 0 disables — /metrics stays on the "
                        "main HTTP port either way)")
    parser.add_argument("--otlp-endpoint", default=None, metavar="URL",
                        help="OTLP/HTTP collector to export trace spans "
                        "to (e.g. http://collector:4318 — /v1/traces is "
                        "appended when the URL has no path).  Dependency-"
                        "free: records are encoded as proto-JSON "
                        "ResourceSpans and batched by a background "
                        "exporter that never blocks the serving path")
    parser.add_argument("--coordinator-address", default=None,
                        help="host:port of process 0 — enables multi-host "
                        "(jax.distributed over DCN); every host runs this "
                        "server and shares the global device mesh")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--serve-mesh", default=None, metavar="SPEC",
                        help="device mesh served models shard over: '1' "
                        "(default, one chip), 'all', an integer N, or an "
                        "explicit shape like 'dp=1,pp=2,ep=2,sp=1,tp=2' "
                        "(sets TRITON_TPU_SERVE_MESH)")
    args = parser.parse_args()
    if args.serve_mesh is not None:
        os.environ["TRITON_TPU_SERVE_MESH"] = args.serve_mesh
    if args.tick_stall_ms is not None:
        if args.tick_stall_ms <= 0:
            parser.error("--tick-stall-ms must be positive")
        # env-var handoff like --serve-mesh: the decode worker arms its
        # watchdog from the environment at lazy init
        os.environ["TRITON_TPU_TICK_STALL_MS"] = str(args.tick_stall_ms)
    for spec in (args.kv_cache_bytes or []):
        # env-var handoff like the flags above: the decode worker builds
        # its block store from the environment at lazy init (kvcache.py)
        from .kvcache import cache_env_key

        name, sep, val = spec.partition("=")
        raw = val if sep else name
        try:
            nbytes = int(raw)
        except ValueError:
            parser.error(f"--kv-cache-bytes {spec!r}: budget must be an "
                         "integer byte count")
        if nbytes < 0:
            parser.error(f"--kv-cache-bytes {spec!r}: budget must be >= 0")
        key = cache_env_key(name) if sep else "TRITON_TPU_KV_CACHE_BYTES"
        os.environ[key] = str(nbytes)
    if args.device_fault_threshold < 1:
        parser.error("--device-fault-threshold must be >= 1")
    if args.device_fault_window <= 0:
        parser.error("--device-fault-window must be positive")
    if args.frontends < 1:
        parser.error("--frontends must be >= 1")
    # autoscale flags validate BEFORE the supervisor branch: a typo'd
    # spec must be an instant flag error, not N workers crash-looping
    # into a "crash storm" verdict with the real message buried in
    # their stderr
    from .fleet import parse_autoscale_spec

    autoscale_bounds = {}
    for spec in (args.autoscale or []):
        try:
            name, bounds = parse_autoscale_spec(spec)
        except ValueError as e:  # typo'd spec — fail at startup, loudly
            parser.error(str(e))
        autoscale_bounds[name] = bounds
    if args.autoscale_interval <= 0:
        parser.error("--autoscale-interval must be positive")
    worker_index = args.frontend_worker
    if args.frontends > 1 and worker_index is None:
        # supervisor: spawn N frontend workers sharing the ports via
        # SO_REUSEPORT and babysit them — no models load in this process
        _run_supervisor(parser, args)
        return
    from ..parallel import initialize_multihost

    if (args.num_processes is not None or args.process_id is not None) \
            and not (args.coordinator_address
                     or os.environ.get("JAX_COORDINATOR_ADDRESS")):
        parser.error("--num-processes/--process-id require "
                     "--coordinator-address (or JAX_COORDINATOR_ADDRESS)")
    from .compile_cache import enable_compile_cache

    # before the first compile; the directory is printed so a cold start
    # and a warm one can be told apart from the log
    print(f"compile cache: {enable_compile_cache()}")
    import jax

    if initialize_multihost(args.coordinator_address, args.num_processes,
                            args.process_id):
        print(f"multi-host: process {jax.process_index()}/"
              f"{jax.process_count()}, {len(jax.devices())} global devices")
    # this process now owns its accelerator (a chip belongs to one process
    # at a time); say which, so a CPU fallback is visible in the first lines
    devices = jax.local_devices()
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind!r} count={len(devices)}")
    try:
        tls = maybe_tls(args.ssl_certfile, args.ssl_keyfile)
    except ValueError as e:
        parser.error(str(e))

    registry = ModelRegistry(repository_path=args.model_repository)
    if args.model_repository:
        for entry in registry.index():
            try:
                registry.load(entry["name"])
                print(f"loaded model '{entry['name']}'")
            except Exception as e:
                print(f"failed to load '{entry['name']}': {e}")
    if args.zoo or not args.model_repository:
        from ..models import zoo

        zoo.register_all(registry)
        print(f"registered model zoo: {[e['name'] for e in registry.index()]}")
        from ..models import language

        # the env-preset transformers size themselves by platform at
        # registration: log what each resolved, so a tiny CPU preset can
        # never pass for the full-width model
        print("transformer presets: " + " ".join(
            f"{k}={v}" for k, v in language.resolved_presets().items()))

    core = InferenceCore(registry)
    core.default_max_queue_size = max(0, args.max_queue_size)
    core.shed_retry_after_s = max(0.0, args.shed_retry_after)
    if args.max_request_bytes < 0:
        parser.error("--max-request-bytes must be >= 0 (0 = unbounded)")
    if args.mem_budget_bytes < 0:
        parser.error("--mem-budget-bytes must be >= 0 (0 = track only)")
    core.memory.budget_bytes = args.mem_budget_bytes
    if args.mem_budget_bytes:
        print(f"memory governor: host budget {args.mem_budget_bytes} bytes")
    from .qos import QosManager, parse_tenant_limit

    try:
        weights = ([int(w) for w in args.qos_weights.split(",")]
                   if args.qos_weights else None)
        tenant_rates = {}
        for spec in (args.qos_tenant_limit or []):
            name, rate, burst = parse_tenant_limit(spec)
            tenant_rates[name] = (rate, burst)
        core.qos = QosManager(
            tiers=args.qos_tiers,
            tenant_rate=max(0.0, args.qos_tenant_rate),
            tenant_burst=args.qos_tenant_burst,
            tenant_rates=tenant_rates,
            best_effort_fraction=args.qos_best_effort_fraction,
            weights=weights)
    except ValueError as e:
        parser.error(str(e))
    if args.cache_budget_bytes > 0:
        core.response_cache.budget_bytes = args.cache_budget_bytes
    # device-fault containment knobs (the manager itself is always on)
    core.device_faults.threshold = args.device_fault_threshold
    core.device_faults.window_s = args.device_fault_window
    core.device_faults.probe_backoff_s = max(
        0.05, args.device_fault_probe_backoff)
    core.device_faults.probe_backoff_max_s = max(
        core.device_faults.probe_backoff_s,
        args.device_fault_probe_backoff_max)
    if args.chaos > 0.0:
        from .chaos import build_injector

        try:
            core.chaos = build_injector(
                args.chaos, kinds_csv=args.chaos_kinds,
                seed=args.chaos_seed, latency_ms=args.chaos_latency_ms,
                models=args.chaos_model,
                transient_s=max(0.0, args.chaos_transient),
                pressure_s=max(0.0, args.chaos_pressure_s),
                pressure_factor=args.chaos_pressure_factor)
        except ValueError as e:
            parser.error(str(e))
        print(f"chaos injection ON: rate={args.chaos} "
              f"kinds={core.chaos.kinds} seed={args.chaos_seed}")
        if "worker_kill" in core.chaos.kinds:
            # a worker_kill draw must look exactly like a real crash: hard
            # process exit, no drain, no atexit — the self-healing
            # supervisor (or the operator's init system) is what heals it
            core.chaos.worker_kill_cb = lambda: os._exit(70)
            print("chaos: worker_kill armed — this process hard-exits "
                  "when the fault fires")
    from .fleet import FleetController

    # the controller is always attached (rolling updates + nv_fleet_*
    # actuation counters need it); its loop only ever actuates models
    # with explicit --autoscale bounds or autoscale.* config parameters
    core.fleet = FleetController(core, interval_s=args.autoscale_interval,
                                 bounds=autoscale_bounds)
    for name, (lo, hi) in sorted(autoscale_bounds.items()):
        print(f"autoscale: {name} instances in [{lo}, {hi}]")
    try:
        core.flight_recorder.configure(
            capacity=args.flight_recorder_size,
            outlier_capacity=args.flight_recorder_outliers,
            capture_slower_than=args.capture_slower_than,
            enabled=not args.no_flight_recorder)
    except Exception as e:  # invalid threshold spec — fail at startup
        parser.error(str(e))
    from .device_stats import parse_slo_spec

    if args.no_device_stats:
        core.device_stats.enabled = False
    # host self-observation: the CLI flag wins over the env default the
    # profiler was constructed with; the incident dir also hosts the
    # faulthandler dump (enabled below) so every postmortem artifact of
    # one process lands in one place
    if args.profile_hz is not None:
        if args.profile_hz < 0:
            parser.error("--profile-hz must be >= 0 (0 = sampler off)")
        core.profiler.hz = args.profile_hz
    if args.incident_keep < 1:
        parser.error("--incident-keep must be >= 1")
    core.incidents.keep = args.incident_keep
    if args.incident_dir:
        core.incidents.dir = args.incident_dir
    os.makedirs(core.incidents.dir, exist_ok=True)
    # faulthandler on by default: a hard hang or fatal signal dumps every
    # thread's stack into the incident dir instead of dying silently.
    # The file object must outlive the process (faulthandler keeps only
    # the fd) — parked on the core.
    import faulthandler

    core._faulthandler_file = open(
        os.path.join(core.incidents.dir,
                     f"faulthandler-{os.getpid()}.log"), "w")
    faulthandler.enable(file=core._faulthandler_file)
    print(f"incident capture: dir={core.incidents.dir} "
          f"keep={core.incidents.keep} profiler_hz={core.profiler.hz:g} "
          "(SIGUSR2 triggers a manual bundle)")
    if args.slo_burn_threshold is not None:
        if args.slo_burn_threshold <= 0:
            parser.error("--slo-burn-threshold must be positive")
        core.slo.burn_threshold = args.slo_burn_threshold
    for spec in (args.slo or []):
        try:
            name, objective = parse_slo_spec(spec)
        except ValueError as e:  # typo'd SLO — fail at startup, loudly
            parser.error(str(e))
        core.slo.set_objective(name, objective)
        print(f"SLO: {name} p99<={objective.p99_ms:g}ms "
              f"availability={objective.availability:g}")

    # replica identity: every trace record this process emits carries it,
    # so a cross-replica journey join can tell which replica served which
    # attempt.  TRITON_TPU_REPLICA wins (fleet operators name replicas);
    # otherwise host:port plus the frontend worker index when sharded.
    replica = os.environ.get("TRITON_TPU_REPLICA", "")
    if not replica:
        replica = f"{args.host}:{args.http_port}"
        if worker_index is not None:
            replica += f"#w{worker_index}"
    core.tracer.replica = replica
    if args.otlp_endpoint:
        try:
            core.enable_otlp(args.otlp_endpoint, replica=replica)
        except ValueError as e:
            parser.error(str(e))
        print(f"OTLP export: {args.otlp_endpoint} (replica={replica})")

    # per-worker metrics port: the main ports are kernel-balanced across
    # workers, so the dedicated metrics/debug port is the one per-process
    # surface — worker i serves it at base + i
    metrics_port = ((args.metrics_port + (worker_index or 0))
                    if args.metrics_port else None)

    async def serve():
        import signal

        from .frontends import install_aio_noise_filter, stop_frontends

        # grpc.aio poller wakeup races print benign BlockingIOError
        # tracebacks through the default handler; filter that one
        # signature (see frontends.install_aio_noise_filter)
        install_aio_noise_filter(asyncio.get_running_loop())
        warmed = await core.warmup_models()
        if warmed:
            print(f"warmed up: {warmed}")
        core.fleet.start()  # the closed-loop evaluation tick
        # hold the returned handles: a dropped grpc.aio.Server is torn down
        # by its finalizer, silently closing the port
        frontends = await start_frontends(
            core, args.host, args.http_port, args.grpc_port, tls=tls,
            metrics_port=metrics_port,
            reuse_port=worker_index is not None,
            max_request_bytes=args.max_request_bytes)
        scheme = "https" if tls else "http"
        metrics = (f" metrics={args.host}:{metrics_port}"
                   if metrics_port else "")
        worker = (f" [frontend worker {worker_index}/{args.frontends}]"
                  if worker_index is not None else "")
        print(
            f"serving v2 protocol: {scheme}={args.host}:{args.http_port} "
            f"grpc{'s' if tls else ''}={args.host}:{args.grpc_port}"
            f"{metrics}{worker}"
        )
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:  # non-unix event loops
                pass
        # SIGUSR2 = "bundle the process, keep serving": the operator's
        # live postmortem trigger (the bundle writes on its own thread)
        if hasattr(signal, "SIGUSR2"):
            try:
                loop.add_signal_handler(
                    signal.SIGUSR2,
                    lambda: core.incidents.trigger(
                        "sigusr2", reason="operator SIGUSR2"))
            except NotImplementedError:  # non-unix event loops
                pass
        await stop.wait()
        # graceful drain BEFORE the listeners close: new requests get a
        # proper 503 + Retry-After (and readiness flips false so a load
        # balancer stops routing) while in-flight ones run to completion —
        # killing the sockets first would sever them with connection resets
        print("shutting down: draining in-flight requests "
              f"(up to {args.drain_timeout:g}s)")
        await core.shutdown(drain_s=max(0.0, args.drain_timeout))
        await stop_frontends(*frontends)

    # optional uvloop (TRITON_TPU_UVLOOP=1): the same env gate the aio
    # clients honor now accelerates the server's event loop too — both
    # ends of the socket.  Graceful stdlib fallback when not installed.
    from .._uvloop import maybe_install_uvloop

    if maybe_install_uvloop():
        print("event loop: uvloop")
    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass  # second ^C mid-drain, or non-unix loop without handlers


def _run_supervisor(parser, args) -> None:
    """``--frontends N`` parent: spawn N workers that re-exec this module
    with ``--frontend-worker i``, each binding the SAME HTTP/gRPC ports
    with SO_REUSEPORT (the kernel balances accepted connections across
    them).  Shutdown reuses the PR 4 drain machinery per worker: signals
    are forwarded and every worker runs its own graceful drain.

    The supervisor is SELF-HEALING (server/fleet.py): a worker that dies
    on its own is respawned with capped exponential backoff — the
    replacement re-execs with the same SO_REUSEPORT ports and the same
    shm-manifest directory, so it rejoins the kernel's accept balancing
    and re-resolves client shared-memory registrations from the manifest
    with no client action.  Restarts are counted into the shared fleet
    state file (``nv_fleet_worker_restart_total`` on every worker's
    metrics surface).  Only a crash STORM — ``--worker-restart-limit``
    crashes of one worker inside ``--worker-restart-window`` — fails the
    fleet fast (drain the siblings rather than hot-loop a broken
    binary)."""
    import shutil
    import signal
    import socket
    import subprocess
    import sys
    import tempfile
    import time

    from .fleet import (FLEET_STATE_ENV, RestartPolicy, SupervisorState,
                        crash_reason_from_exit)

    if not hasattr(socket, "SO_REUSEPORT"):
        parser.error("--frontends > 1 requires SO_REUSEPORT (Linux)")
    if (args.coordinator_address or args.num_processes is not None
            or args.process_id is not None):
        parser.error("--frontends > 1 is incompatible with multi-host "
                     "serving (each host runs one server process)")
    if args.worker_restart_limit < 1:
        parser.error("--worker-restart-limit must be >= 1")
    # each worker hosts a full InferenceCore replica: host-placed models
    # replicate cheaply, but a chip belongs to one process at a time — N
    # workers would all open it and crash-loop into a storm verdict.  The
    # supervisor must not open the device to find out, so the platform
    # has to be pinned to the CPU from outside.
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if first != "cpu":
        parser.error(
            "--frontends > 1 starts one full server per worker, and an "
            "accelerator can be opened by one process only: set "
            "JAX_PLATFORMS=cpu for host-placed replicas, or serve the "
            "device from a single frontend process (--frontends 1)")
    # client shm registrations land on ONE kernel-picked worker; the
    # manifest directory lets every sibling resolve them (server/shm.py).
    # The fleet state file rides the same directory: workers read restart
    # counters back out of it for nv_fleet_worker_restart_total.
    manifest = tempfile.mkdtemp(prefix="tc-tpu-shm-manifest-")
    fleet_state = SupervisorState(os.path.join(manifest, "fleet-state.json"))
    env = dict(os.environ, TRITON_TPU_SHM_MANIFEST=manifest)
    env[FLEET_STATE_ENV] = fleet_state.path

    def spawn(i: int) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "triton_client_tpu.server",
               *sys.argv[1:], "--frontend-worker", str(i)]
        p = subprocess.Popen(cmd, env=env)
        print(f"frontend worker {i}: pid {p.pid}", flush=True)
        return p

    procs: list = []
    rc = 0
    try:
        procs = [spawn(i) for i in range(args.frontends)]
        policies = [RestartPolicy(storm_limit=args.worker_restart_limit,
                                  window_s=args.worker_restart_window)
                    for _ in procs]
        restart_at = [None] * len(procs)  # pending respawn deadlines
        crash_reason = [None] * len(procs)  # why the pending respawn
        print(f"frontend supervisor: {args.frontends} workers sharing "
              f"http={args.host}:{args.http_port} "
              f"grpc={args.host}:{args.grpc_port} (SO_REUSEPORT, "
              f"self-healing: restart with backoff, fail-fast after "
              f"{args.worker_restart_limit} crashes/"
              f"{args.worker_restart_window:g}s)")
        state = {"stopping": False}

        def forward(signum, _frame):
            # graceful drain per worker: each one sheds new work (503 +
            # Retry-After, readiness false) and finishes in-flight
            # requests inside its own --drain-timeout.  Pending respawns
            # are cancelled — a stopping fleet heals nothing.
            state["stopping"] = True
            for p in procs:
                if p is not None and p.poll() is None:
                    try:
                        p.send_signal(signum)
                    except OSError:
                        pass

        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, forward)

        def fail_fast() -> None:
            state["stopping"] = True
            for q in procs:
                if q is not None and q.poll() is None:
                    try:
                        q.send_signal(signal.SIGTERM)
                    except OSError:
                        pass

        while True:
            now = time.monotonic()
            if not state["stopping"]:
                for i, p in enumerate(procs):
                    if p is None or p.poll() is None:
                        continue
                    # a worker died on its own (any exit while not
                    # stopping is unexpected — the server runs forever)
                    code = p.returncode or 0
                    procs[i] = None
                    # decode WHY before the returncode is lost: signal
                    # name, the chaos worker_kill exit-70 convention, or
                    # the plain exit code — stamped into the fleet state
                    # so the workers' worker-crash incident bundles can
                    # say what killed their sibling
                    crash_reason[i] = crash_reason_from_exit(p.returncode)
                    delay = policies[i].on_crash(now)
                    if delay is None:
                        print(f"frontend worker {i}: "
                              f"{policies[i].storm_limit} crashes inside "
                              f"{policies[i].window_s:g}s — crash storm, "
                              "failing fast (draining siblings)",
                              file=sys.stderr, flush=True)
                        rc = max(rc, 1 if code <= 0 else code)
                        fail_fast()
                        restart_at = [None] * len(procs)
                        break
                    print(f"frontend worker {i} exited rc={code} "
                          f"({crash_reason[i]}); restarting in {delay:g}s "
                          "(SO_REUSEPORT rebind + shm manifest re-issued)",
                          file=sys.stderr, flush=True)
                    restart_at[i] = now + delay
                for i, due in enumerate(restart_at):
                    if due is not None and now >= due \
                            and not state["stopping"]:
                        restart_at[i] = None
                        procs[i] = spawn(i)
                        fleet_state.record_restart(
                            str(i), reason=crash_reason[i])
            alive = any(p is not None and p.poll() is None for p in procs)
            pending = any(due is not None for due in restart_at)
            if state["stopping"] and not alive:
                break
            if not state["stopping"] and not alive and not pending:
                break  # defensive: nothing left to supervise
            time.sleep(0.2)
        # a signal-killed worker (negative returncode) is a failure, not
        # an exotic success; healed crashes don't count against the exit
        rc = max([rc] + [1 if (p.returncode or 0) < 0
                         else (p.returncode or 0)
                         for p in procs if p is not None])
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
        shutil.rmtree(manifest, ignore_errors=True)
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
