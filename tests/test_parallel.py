"""Parallelism primitives (triton_client_tpu/parallel/)."""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from triton_client_tpu import parallel  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    """Subprocess env that finds the package wherever the checkout lives."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


class TestFactorizeMesh:
    AXES = ("dp", "pp", "ep", "sp", "tp")

    def _check(self, n, limits, **kw):
        shape = parallel.factorize_mesh(n, limits, self.AXES, **kw)
        assert int(np.prod(list(shape.values()))) == n
        for ax, lim in limits.items():
            assert lim % shape[ax] == 0, (ax, shape)
        return shape

    def test_product_and_divisibility(self):
        limits = {"tp": 8, "sp": 4, "pp": 4, "ep": 2}
        for n in (1, 2, 4, 8, 16, 32):
            self._check(n, limits, priority=("tp", "sp", "pp", "ep"),
                        remainder_axis="dp")

    def test_spread_before_deepen(self):
        shape = self._check(8, {"tp": 8, "sp": 4, "pp": 4, "ep": 2},
                            priority=("tp", "sp", "pp", "ep"),
                            remainder_axis="dp")
        # 8 devices spread one factor of 2 across tp/sp/pp before deepening
        assert shape["tp"] == 2 and shape["sp"] == 2 and shape["pp"] == 2

    def test_non_power_of_two_remainder_on_dp(self):
        shape = self._check(12, {"tp": 2, "sp": 1, "pp": 1, "ep": 1},
                            priority=("tp", "sp", "pp", "ep"),
                            remainder_axis="dp")
        assert shape["tp"] == 2 and shape["dp"] == 6

    def test_limit_indivisible_axis_stays_one(self):
        # limit 6 is not divisible by 4: axis may reach 2 but not 4
        shape = self._check(16, {"tp": 6, "sp": 1, "pp": 1, "ep": 1},
                            priority=("tp",), remainder_axis="dp")
        assert shape["tp"] == 2 and shape["dp"] == 8


class TestRingAttention:
    def _reference(self, q, k, v, causal=True):
        B, H, S, K = q.shape
        s = jnp.einsum("bhqk,bhsk->bhqs", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(K)
        if causal:
            pos = jnp.arange(S)
            s = jnp.where(pos[:, None] >= pos[None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqs,bhsk->bhqk", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        sp = 4
        devices = jax.devices("cpu")[:sp]
        mesh = parallel.build_mesh({"sp": sp}, ("sp",), devices)
        B, H, S, K = 2, 2, 32, 8
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, K)),
                               jnp.float32) for _ in range(3))

        ring = jax.jit(jax.shard_map(
            lambda q, k, v: parallel.ring_attention(q, k, v, "sp",
                                                    causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        ))
        got = np.asarray(ring(q, k, v))
        want = np.asarray(self._reference(q, k, v, causal=causal))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestGradSync:
    def test_replicated_axes(self):
        axes = ("dp", "pp", "tp")
        assert parallel.replicated_axes(P(None, "tp"), axes) == ("dp", "pp")
        assert parallel.replicated_axes(P("pp", ("dp", "tp")), axes) == ()
        assert parallel.replicated_axes(P(None), axes) == ("dp", "pp", "tp")

    def test_sync_sums_over_replicated_axes_only(self):
        n = 4
        mesh = parallel.build_mesh({"dp": 2, "tp": 2}, ("dp", "tp"),
                                   jax.devices("cpu")[:n])
        specs = {"w": P(None, "tp"), "b": P(None)}

        def body(w, b):
            grads = {"w": w * 0 + 1.0, "b": b * 0 + 1.0}
            synced = parallel.sync_replicated_grads(
                grads, specs, ("dp", "tp"))
            return synced["w"], synced["b"]

        f = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "tp"), P(None)),
            out_specs=(P(None, "tp"), P(None)),
            check_vma=False,
        ))
        w = jnp.zeros((2, 4), jnp.float32)
        b = jnp.zeros((3,), jnp.float32)
        gw, gb = f(w, b)
        # w sharded over tp → synced over dp only (2 replicas)
        np.testing.assert_array_equal(np.asarray(gw), np.full((2, 4), 2.0))
        # b fully replicated → synced over dp*tp (4 replicas)
        np.testing.assert_array_equal(np.asarray(gb), np.full((3,), 4.0))


class TestMultihost:
    def test_single_process_distributed_init(self, tmp_path):
        """jax.distributed with num_processes=1 in a subprocess: the server's
        multi-host bootstrap path runs end to end."""
        script = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import jax\n"
            "from triton_client_tpu.parallel import initialize_multihost\n"
            "assert not initialize_multihost()  # no args, no env -> off\n"
            "assert initialize_multihost('localhost:%d', 1, 0)\n"
            "assert initialize_multihost()  # idempotent once active\n"
            "assert jax.process_index() == 0 and jax.process_count() == 1\n"
            "import jax.numpy as jnp\n"
            "assert float(jnp.sum(jnp.ones(4))) == 4.0\n"
            "print('MULTIHOST-OK')\n"
        )
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        proc = subprocess.run(
            [sys.executable, "-c", script % port],
            capture_output=True, text=True, timeout=120,
            env=_child_env(),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MULTIHOST-OK" in proc.stdout

    def test_two_process_cross_host_collective(self, tmp_path):
        """REAL multi-process jax.distributed: two OS processes, 4 CPU
        devices each, one 8-device global mesh; a sharded sum must
        all-reduce across the process boundary (the DCN path the server's
        pod bootstrap rides) and agree on both ranks."""
        script = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import numpy as np
import jax
from triton_client_tpu.parallel import initialize_multihost

coord, pid = sys.argv[1], int(sys.argv[2])
assert initialize_multihost(coord, 2, pid)
assert jax.process_count() == 2 and jax.process_index() == pid
assert jax.device_count() == 8 and len(jax.local_devices()) == 4
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

mesh = Mesh(np.array(jax.devices()).reshape(8), ("x",))
sharding = NamedSharding(mesh, P("x"))
local = np.arange(8, dtype=np.float32)[pid * 4:(pid + 1) * 4]
ga = jax.make_array_from_process_local_data(sharding, local)
total = jax.jit(lambda a: jax.numpy.sum(a),
                out_shardings=NamedSharding(mesh, P()))(ga)
assert float(total) == 28.0, float(total)
print(f"RANK{pid}-OK", flush=True)
"""
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        coord = f"localhost:{port}"
        sfile = tmp_path / "two_proc.py"
        sfile.write_text(script)
        procs = [subprocess.Popen(
                     [sys.executable, str(sfile), coord, str(i)],
                     stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                     text=True, env=_child_env())
                 for i in range(2)]
        outs = [p.communicate(timeout=180) for p in procs]
        for i, (p, (out, err)) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {i}:\n{err[-2000:]}"
            assert f"RANK{i}-OK" in out
