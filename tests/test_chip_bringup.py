"""What PR 21 (chip bring-up) changed that a CPU run can check: where the
compile cache is placed, that ``--frontends N`` refuses to fan out over an
accelerator, that a kernel forced on raises instead of quietly running its
reference, and that ``chip_smoke.py`` fails loudly without a chip."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**extra):
    env = dict(os.environ, **extra)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


_CACHE_PROBE = (
    "import json, jax\n"
    "from triton_client_tpu.server.compile_cache import enable_compile_cache\n"
    "a = enable_compile_cache(); b = enable_compile_cache()\n"
    "print(json.dumps([a, b, jax.config.jax_compilation_cache_dir]))\n")


class TestCompileCachePlacement:
    """Subprocesses: the helper's whole job is process-global jax config."""

    def _probe(self, env):
        import json

        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    def test_unset_is_one_fixed_directory_inside_the_checkout(self):
        env = _child_env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        first, second, configured = self._probe(env)
        assert first == second == configured == os.path.join(
            REPO, ".jax_cache")
        # a second process lands on the same path: the directory is part
        # of how the cache is found again
        assert self._probe(env)[0] == first

    def test_placed_from_outside_is_left_alone(self, tmp_path):
        placed = str(tmp_path / "placed")
        first, second, configured = self._probe(
            _child_env(JAX_COMPILATION_CACHE_DIR=placed))
        # the helper reports the outside placement and sets nothing:
        # the configured value is JAX's own reading of the variable
        assert first == second == configured == placed

    def test_nothing_else_in_the_tree_sets_a_cache_directory(self):
        hits = []
        for root, _dirs, files in os.walk(REPO):
            if any(part.startswith(".") or part in ("tests", "chiprun_out")
                   for part in os.path.relpath(root, REPO).split(os.sep)
                   if part != "."):
                continue
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                with open(path, errors="replace") as f:
                    text = f.read()
                if "compilation_cache_dir" in text or \
                        "set_cache_dir" in text:
                    hits.append(os.path.relpath(path, REPO))
        assert hits == ["triton_client_tpu/server/compile_cache.py"]


class TestFrontendsNeedAPinnedCpu:
    def _run(self, platforms):
        env = _child_env()
        if platforms is None:
            env.pop("JAX_PLATFORMS", None)
        else:
            env["JAX_PLATFORMS"] = platforms
        return subprocess.run(
            [sys.executable, "-m", "triton_client_tpu.server", "--zoo",
             "--frontends", "2", "--http-port", "0", "--grpc-port", "0"],
            env=env, capture_output=True, text=True, timeout=60)

    @pytest.mark.parametrize("platforms", [None, "tpu,cpu", "tpu"])
    def test_refused_unless_the_platform_is_the_cpu(self, platforms):
        # the supervisor must decide WITHOUT opening the device: N workers
        # opening one chip is a crash storm, so anything but a pinned CPU
        # is an argument error (exit 2), not a warning
        out = self._run(platforms)
        assert out.returncode == 2, (out.returncode, out.stderr[-800:])
        assert "--frontends > 1" in out.stderr
        assert "one process only" in out.stderr
        assert "frontend worker" not in out.stdout  # nothing was spawned


class TestKernelsRaiseWhenForcedOn:
    """``force=True`` means "the compiled kernel or an error": off TPU the
    kernel cannot compile, and the call must say so — never hand back the
    jnp reference under the kernel's name."""

    def test_flash_attention_forced_on_cpu_raises(self):
        import jax
        import jax.numpy as jnp

        from triton_client_tpu.ops import flash_attention

        q = jnp.ones((1, 2, 128, 64), jnp.float32)
        with pytest.raises(Exception) as ei:
            jax.block_until_ready(
                flash_attention(q, q, q, causal=True, force=True))
        assert "interpret" in str(ei.value).lower() or \
            "cpu" in str(ei.value).lower()

    def test_serving_path_never_selects_interpret_mode(self):
        # interpret=True is for tests: unreachable from server/ and models/
        for sub in ("server", "models"):
            for root, _dirs, files in os.walk(
                    os.path.join(REPO, "triton_client_tpu", sub)):
                for name in files:
                    if name.endswith(".py"):
                        with open(os.path.join(root, name)) as f:
                            assert "interpret=True" not in f.read(), name

    def test_model_selects_the_int8_kernel_by_shape(self):
        # tiny widths (K=64) cannot take the kernel: the model code picks
        # the XLA einsum from the shape instead of calling a kernel that
        # would raise — so an int8 tiny model still serves
        from triton_client_tpu.ops import int8_matmul_fits

        assert int8_matmul_fits(4096, 1024) and int8_matmul_fits(1024, 4096)
        assert not int8_matmul_fits(64, 128)
        assert not int8_matmul_fits(128, 64)
        assert not int8_matmul_fits(16384, 1024)


class TestChipSmokeWithoutAChip:
    def test_exits_nonzero_names_the_device_and_prints_no_result(self):
        # this sandbox has no accelerator: the smoke must fail, say what
        # is missing, and print nothing that parses as a result line —
        # it does not run the tiny presets and pass
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env=_child_env(), capture_output=True, text=True, timeout=300)
        assert out.returncode not in (0, 2), out.stdout[-500:]
        assert "no TPU device" in out.stderr
        assert "Unable to initialize backend" in out.stderr
        assert '"ok"' not in out.stdout

    def test_alone_in_a_directory_it_fails_and_prints_no_result(self, tmp_path):
        # the script without the program: nothing to drive, so no result
        import shutil

        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode != 0
        assert "triton_client_tpu" in out.stderr
        assert out.stdout.strip() == ""

    def test_result_line_has_exactly_the_contract_keys(self):
        # what the chip check parses as the last line of stdout: "ok" and
        # "device" {platform, kind, count} and nothing else
        import importlib.util
        import json

        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        line = mod.result_line(
            {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
        assert "\n" not in line
        assert json.loads(line) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    def test_parent_imports_stay_jax_free(self):
        probe = (
            "import sys\n"
            "import triton_client_tpu.grpc, triton_client_tpu.http\n"
            "import triton_client_tpu.perf_analyzer\n"
            "import triton_client_tpu.genai_perf\n"
            "import triton_client_tpu.utils.xla_shared_memory as x\n"
            "import numpy as np\n"
            "h = x.create_shared_memory_region('p', 64, 0)\n"
            "x.set_shared_memory_region(h, [np.arange(16, dtype=np.int32)])\n"
            "x.get_contents_as_numpy(h, np.int32, [16])\n"
            "x.destroy_shared_memory_region(h)\n"
            "assert 'jax' not in sys.modules, 'client imports pulled jax'\n")
        out = subprocess.run([sys.executable, "-c", probe],
                             env=_child_env(), capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
