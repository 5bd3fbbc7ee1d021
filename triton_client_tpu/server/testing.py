"""In-process server harness for hermetic tests and co-located serving.

``ServerHarness`` runs the HTTP and gRPC frontends on a background-thread
event loop inside the current process.  This is both the test fixture
(SURVEY.md §4: integration tests need a live server; the reference outsources
that to external CI) and the production co-located topology for the xla
shared-memory zero-copy path (client and server share the TPU process, see
``_xla_broker``).

``ClusterHarness`` stacks N of them — each with its OWN registry and core,
so per-server state (pending counts, chaos injectors, flight recorders)
stays per-server — and adds ``kill``/``restart`` so failover tests can
take a replica down mid-run and bring it back on the same ports.
"""

from __future__ import annotations

import asyncio
import os
import socket
import threading
import time
from typing import Callable, List, Optional

from .._xla_broker import broker
from .core import InferenceCore
from .frontends import start_frontends, stop_frontends
from .registry import ModelRegistry


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# broker().server_present is a process-global flag (it switches the xla
# shared-memory clients between zero-copy co-located writes and staging
# writes), but ClusterHarness runs N harnesses in ONE process — so the
# flag must be refcounted: killing replica 0 while replicas 1..N-1 still
# serve must not flip it off for unrelated co-located traffic.
_PRESENT_LOCK = threading.Lock()
_PRESENT_COUNT = 0


def _server_present(delta: int) -> None:
    global _PRESENT_COUNT
    with _PRESENT_LOCK:
        _PRESENT_COUNT = max(0, _PRESENT_COUNT + delta)
        broker().server_present = _PRESENT_COUNT > 0


class ServerHarness:
    def __init__(
        self,
        registry: Optional[ModelRegistry] = None,
        http_port: Optional[int] = None,
        grpc_port: Optional[int] = None,
        host: str = "127.0.0.1",
        tls=None,
        metrics_port: Optional[int] = None,
        max_request_bytes: Optional[int] = None,
        replica: str = "",
    ):
        self.registry = registry or ModelRegistry()
        self.core = InferenceCore(self.registry)
        self.host = host
        self.tls = tls
        self.metrics_port = metrics_port
        # wire ingress cap for both frontends; None = the shared default
        # (a bare harness is bounded exactly like a bare CLI serve)
        self.max_request_bytes = max_request_bytes
        self.http_port = http_port or free_port()
        self.grpc_port = grpc_port or free_port()
        # replica identity stamped into every trace record this harness
        # emits (same contract as the CLI server): explicit name, else
        # host:port — the join key for cross-replica journey assertions
        self.replica = replica or f"{self.host}:{self.http_port}"
        self.core.tracer.replica = self.replica
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        self._stop_event: Optional[asyncio.Event] = None

    @property
    def http_url(self) -> str:
        return f"{self.host}:{self.http_port}"

    @property
    def grpc_url(self) -> str:
        return f"{self.host}:{self.grpc_port}"

    def start(self) -> "ServerHarness":
        from .compile_cache import enable_compile_cache

        enable_compile_cache()  # before the warmup compiles in _serve
        self._present = True
        _server_present(+1)
        self._thread = threading.Thread(target=self._run, daemon=True, name="tc-tpu-server")
        self._thread.start()
        if not self._started.wait(timeout=30):
            raise RuntimeError("server harness failed to start within 30s")
        return self

    def _run(self) -> None:
        loop = asyncio.new_event_loop()
        self._loop = loop
        asyncio.set_event_loop(loop)
        # same benign-noise filter the CLI server installs: grpc.aio
        # poller wakeup races must not flood harness/bench stderr
        from .frontends import install_aio_noise_filter

        install_aio_noise_filter(loop)
        loop.run_until_complete(self._serve())
        loop.close()

    async def _serve(self) -> None:
        self._stop_event = asyncio.Event()
        # warm before serving: first requests must not pay XLA compilation
        # for models that declare warmup samples (Triton model_warmup)
        await self.core.warmup_models()
        from .memory import DEFAULT_MAX_REQUEST_BYTES

        cap = (DEFAULT_MAX_REQUEST_BYTES if self.max_request_bytes is None
               else self.max_request_bytes)
        runner, grpc_server, metrics_runner = await start_frontends(
            self.core, self.host, self.http_port, self.grpc_port,
            tls=self.tls, metrics_port=self.metrics_port,
            max_request_bytes=cap)
        self._started.set()
        await self._stop_event.wait()
        await stop_frontends(runner, grpc_server, metrics_runner)
        await self.core.shutdown()

    def stop(self) -> None:
        if self._loop is not None and self._stop_event is not None:
            self._loop.call_soon_threadsafe(self._stop_event.set)
        if self._thread is not None:
            self._thread.join(timeout=10)
        # idempotent: a double stop() must decrement the refcount once
        if getattr(self, "_present", False):
            self._present = False
            _server_present(-1)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class ClusterHarness:
    """N in-process servers behind one fixture — the test bed for the
    client-side cluster layer (``triton_client_tpu.cluster``).

    ``registry_factory`` is called once per server: every replica gets a
    fresh ``ModelRegistry`` + ``InferenceCore``, exactly like N separate
    processes would (shared registries would alias pending gauges and
    model state across "replicas" and fake out every failover assertion).

    ``kill(i)`` stops replica *i* (its ports go connection-refused);
    ``restart(i)`` brings a replica back **on the same ports** so breaker
    half-open recovery is testable.  ``chaos(i, injector)`` degrades one
    replica — the straggler in hedging benchmarks.
    """

    def __init__(self, registry_factory: Callable[[], "ModelRegistry"],
                 n: int = 3, host: str = "127.0.0.1",
                 core_setup: Optional[Callable[[ServerHarness], None]]
                 = None):
        if n < 1:
            raise ValueError("ClusterHarness needs at least one server")
        self._registry_factory = registry_factory
        self.host = host
        # per-replica post-start hook (SLO objectives, fleet controllers,
        # queue limits, ...): applied to every replica INCLUDING ones a
        # restart() brings back — a healed replica must rejoin with the
        # same policy surface its predecessor ran, like a real process
        # respawned from the same config
        self._core_setup = core_setup
        # replicas get stable names ("replica-0", ...) that survive
        # kill/restart cycles — a journey's per-replica lanes must keep
        # their identity across the failover they are asserting about
        self.harnesses: List[Optional[ServerHarness]] = [
            ServerHarness(registry_factory(), host=host,
                          replica=f"replica-{i}") for i in range(n)]
        # ports are pinned at construction so restart(i) can rebind them
        self._http_ports = [h.http_port for h in self.harnesses]
        self._grpc_ports = [h.grpc_port for h in self.harnesses]

    @property
    def http_urls(self) -> List[str]:
        return [f"{self.host}:{p}" for p in self._http_ports]

    @property
    def grpc_urls(self) -> List[str]:
        return [f"{self.host}:{p}" for p in self._grpc_ports]

    def start(self) -> "ClusterHarness":
        for h in self.harnesses:
            h.start()
            if self._core_setup is not None:
                self._core_setup(h)
        return self

    def stop(self) -> None:
        for i, h in enumerate(self.harnesses):
            if h is not None:
                h.stop()
                self.harnesses[i] = None

    def kill(self, i: int) -> None:
        """Take replica ``i`` down (graceful drain, then ports closed —
        the client sees 503s during the drain and connection-refused
        after, both retryable)."""
        h = self.harnesses[i]
        if h is not None:
            h.stop()
            self.harnesses[i] = None

    def restart(self, i: int) -> None:
        """Bring replica ``i`` back on its original ports (fresh registry
        and core, like a real process restart)."""
        if self.harnesses[i] is not None:
            raise RuntimeError(f"server {i} is already running")
        h = ServerHarness(self._registry_factory(),
                          http_port=self._http_ports[i],
                          grpc_port=self._grpc_ports[i], host=self.host,
                          replica=f"replica-{i}")
        h.start()
        if self._core_setup is not None:
            self._core_setup(h)
        self.harnesses[i] = h

    def chaos(self, i: int, injector) -> None:
        """Install a chaos injector on replica ``i`` (None clears it)."""
        self.harnesses[i].core.chaos = injector

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class ReplicaSupervisor:
    """Self-healing for :class:`ClusterHarness` — the in-process analog
    of the ``--frontends`` supervisor, sharing its crash arithmetic
    (``fleet.RestartPolicy``) and restart accounting
    (``fleet.SupervisorState``) so fleet drills exercise the SAME policy
    the production supervisor runs.

    ``crash(i)`` is the kill signal (wire it to a chaos injector's
    ``worker_kill_cb``): the replica is stopped, the policy's backoff is
    paid on a worker thread, the replica is restarted on its original
    ports, and the restart lands in the state file — with
    ``TRITON_TPU_FLEET_STATE`` pointing there, every surviving replica's
    ``/metrics`` shows ``nv_fleet_worker_restart_total`` climbing.  A
    storm verdict (policy returns None) leaves the replica down, like
    the production fail-fast."""

    def __init__(self, cluster: ClusterHarness, policy=None,
                 state_path: Optional[str] = None):
        import tempfile

        from .fleet import RestartPolicy, SupervisorState

        self.cluster = cluster
        self.policy_factory = policy or (
            lambda: RestartPolicy(base_delay_s=0.05, max_delay_s=1.0))
        self._policies = {}
        if state_path is None:
            fd, state_path = tempfile.mkstemp(prefix="tc-tpu-fleet-state-",
                                              suffix=".json")
            os.close(fd)
            os.unlink(state_path)
        self.state = SupervisorState(state_path)
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()

    def crash(self, i: int, reason: str = "chaos:worker_kill") -> None:
        """Kill replica ``i`` and heal it with backoff, off-thread (safe
        to call from a serving event loop via ``worker_kill_cb`` — the
        kill itself must not deadlock the loop it is called from).
        ``reason`` is stamped into the fleet state alongside the restart
        count, the same way the production supervisor decodes a dead
        worker's returncode."""
        t = threading.Thread(target=self._heal, args=(i, reason),
                             daemon=True)
        with self._lock:
            self._threads.append(t)
        t.start()

    def _heal(self, i: int, reason: str = "") -> None:
        with self._lock:
            policy = self._policies.setdefault(i, self.policy_factory())
            delay = policy.on_crash()
        try:
            self.cluster.kill(i)
        except Exception:  # noqa: BLE001 — already down is fine
            pass
        if delay is None:
            return  # crash storm: stay down (production fail-fast)
        time.sleep(delay)
        with self._lock:
            if self.cluster.harnesses[i] is not None:
                return  # someone else already brought it back
            self.cluster.restart(i)
            self.state.record_restart(str(i), reason=reason or None)

    def join(self, timeout: float = 30.0) -> None:
        """Wait for in-flight heals (test teardown barrier)."""
        deadline = time.monotonic() + timeout
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
