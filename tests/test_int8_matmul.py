"""Fused quantize+int8-matmul pallas kernel (ops/int8_matmul.py).

The kernel's math must be the XLA int8 serving path's math exactly: same
per-row dynamic scale, same round/clip, same s32 accumulation, same
dequant epilogue — so the encoder's int8 closeness guarantees
(test_transformer.py::TestInt8EncoderServing) transfer unchanged when the
FFN matmuls switch to the kernel.  Runs in the pallas interpreter on CPU.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import importlib

from triton_client_tpu.ops import int8_matmul, int8_matmul_reference

_mod = importlib.import_module("triton_client_tpu.ops.int8_matmul")


def _mk(m, k, n, seed=0, dtype=jnp.bfloat16):
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.randint(kw, (k, n), -127, 128, jnp.int8)
    ws = (jnp.abs(jax.random.normal(ks, (n,), jnp.float32)) + 0.01) * 0.02
    return x, w, ws


class TestKernelMatchesReference:
    def test_exact_vs_reference(self):
        x, w, ws = _mk(64, 256, 128)
        got = int8_matmul(x, w, ws, block_m=32, block_n=128, interpret=True)
        want = int8_matmul_reference(x, w, ws)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-3)

    def test_padded_m(self):
        # M=50 not a multiple of block_m: kernel pads rows with zeros and
        # slices them off; padded rows must not perturb real ones
        x, w, ws = _mk(50, 128, 128, seed=1)
        got = int8_matmul(x, w, ws, block_m=32, block_n=128, interpret=True)
        want = int8_matmul_reference(x, w, ws)
        assert got.shape == (50, 128)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-3)

    def test_batched_leading_dims(self):
        x, w, ws = _mk(48, 128, 256, seed=2)
        x3 = x.reshape(4, 12, 128)
        got = int8_matmul(x3, w, ws, block_m=16, block_n=128, interpret=True)
        want = int8_matmul_reference(x3, w, ws)
        assert got.shape == (4, 12, 256)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-3)

    def test_scale_shape_row_vector(self):
        # w_scale arrives as [1, N] from the transformer's scanned
        # *_scale leaves; [N] and [1, N] must agree
        x, w, ws = _mk(32, 128, 128, seed=3)
        a = int8_matmul(x, w, ws, block_m=32, block_n=128, interpret=True)
        b = int8_matmul(x, w, ws.reshape(1, -1),
                        block_m=32, block_n=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestNoSilentFallback:
    def test_cpu_backend_uses_reference(self):
        # no interpret/force on CPU -> identical to reference (bitwise):
        # the kernel cannot compile off TPU
        x, w, ws = _mk(16, 128, 128, seed=4)
        got = int8_matmul(x, w, ws)
        want = int8_matmul_reference(x, w, ws)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_unaligned_k_raises_when_kernel_selected(self):
        # K % 128 != 0 can't take the kernel: selected (interpret/force)
        # it raises — explicit blocks or not — instead of running XLA
        x, w, ws = _mk(16, 96, 128, seed=5)
        assert not _mod.fits(96, 128)
        with pytest.raises(ValueError, match="does not fit the kernel"):
            int8_matmul(x, w, ws, interpret=True)
        with pytest.raises(ValueError, match="does not fit the kernel"):
            int8_matmul(x, w, ws, block_m=32, block_n=128, force=True)

    def test_huge_k_raises_when_kernel_selected(self, monkeypatch):
        monkeypatch.setattr(_mod, "_MAX_RESIDENT_K", 64)
        x, w, ws = _mk(16, 128, 128, seed=6)
        with pytest.raises(ValueError, match="does not fit the kernel"):
            int8_matmul(x, w, ws, interpret=True)

    def test_forced_on_cpu_raises_not_reference(self):
        # force = "the kernel or an error": off TPU the compiled kernel
        # cannot run, and the call must say so
        x, w, ws = _mk(32, 128, 128, seed=7)
        with pytest.raises(Exception) as ei:
            jax.block_until_ready(int8_matmul(x, w, ws, force=True))
        assert not isinstance(ei.value, AssertionError)


class TestQuantizationSemantics:
    def test_per_row_scale_isolation(self):
        # a huge outlier in one row must not change other rows' results
        x, w, ws = _mk(32, 128, 128, seed=7, dtype=jnp.float32)
        x_hot = x.at[3].multiply(1000.0)
        base = np.asarray(int8_matmul_reference(x, w, ws))
        hot = np.asarray(int8_matmul_reference(x_hot, w, ws))
        np.testing.assert_array_equal(np.delete(base, 3, 0),
                                      np.delete(hot, 3, 0))

    def test_int32_accumulation_no_overflow(self):
        # worst-case rows (all ±127 after quantize) at K=8192 stay inside
        # s32: 127*127*8192 = 1.3e8 << 2^31
        k = 8192
        x = jnp.ones((8, k), jnp.float32)
        w = jnp.full((k, 128), 127, jnp.int8)
        ws = jnp.ones((128,), jnp.float32)
        out = np.asarray(int8_matmul_reference(x, w, ws), np.float64)
        assert np.all(np.isfinite(out))
        np.testing.assert_allclose(out, 127.0 * k, rtol=1e-6)


class TestFusedModeSelection:
    """TRITON_TPU_INT8_FUSED drives which FFN matmuls take the kernel in
    the encoder's int8 path (models/transformer.py:_int8_fused_mode)."""

    def _mode(self, monkeypatch, val):
        from triton_client_tpu.models import transformer as tr
        if val is None:
            monkeypatch.delenv("TRITON_TPU_INT8_FUSED", raising=False)
        else:
            monkeypatch.setenv("TRITON_TPU_INT8_FUSED", val)
        return tr._int8_fused_mode()

    def test_default_is_w2_only(self, monkeypatch):
        # the measured default: FFN-down wins, FFN-up loses
        # (benchmarks/BERT_PROFILE.md §6)
        assert self._mode(monkeypatch, None) == frozenset(("w2",))

    def test_off_and_all(self, monkeypatch):
        assert self._mode(monkeypatch, "0") == frozenset()
        assert self._mode(monkeypatch, "1") == frozenset(("w1", "w2"))
        assert self._mode(monkeypatch, "all") == frozenset(("w1", "w2"))
        assert self._mode(monkeypatch, "w1,w2") == frozenset(("w1", "w2"))

    def test_weight_resident_default_blocks(self):
        # K>=2048 with a <=4MB weight picks the weight-resident schedule
        # (block_n = N); kernel output still matches the reference
        x, w, ws = _mk(16, 2048, 128, seed=8)
        got = int8_matmul(x, w, ws, interpret=True)
        want = int8_matmul_reference(x, w, ws)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-3)

    def test_unknown_selector_rejected(self, monkeypatch):
        # typos must fail loudly, not silently disable the kernel
        with pytest.raises(ValueError, match="unknown selector"):
            self._mode(monkeypatch, "ffn_down")
        # case-insensitive: W2 means w2
        assert self._mode(monkeypatch, "W2") == frozenset(("w2",))

    def test_auto_block_n_divides_n(self):
        # N=640 passes the N%128 gate but 640 % 512 != 0 — auto selection
        # drops to the largest dividing block (128) and the kernel runs
        x, w, ws = _mk(16, 128, 640, seed=9)
        got = int8_matmul(x, w, ws, interpret=True)
        want = int8_matmul_reference(x, w, ws)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-6)

    def test_explicit_non_dividing_block_n_raises(self):
        # an explicitly-requested block that can't cover N must fail
        # loudly, not silently measure the XLA path
        x, w, ws = _mk(16, 128, 1024, seed=10)
        with pytest.raises(ValueError, match="does not divide"):
            int8_matmul(x, w, ws, block_n=384, interpret=True)


class TestMInnerSchedule:
    def test_m_inner_matches_reference(self):
        # weight-resident grid order: output tiles land in the same
        # places, numerics identical to the default schedule
        x, w, ws = _mk(48, 128, 256, seed=11)
        got = int8_matmul(x, w, ws, block_m=16, block_n=128,
                          m_inner=True, interpret=True)
        want = int8_matmul_reference(x, w, ws)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-6)

    def test_sched_env_typo_rejected(self, monkeypatch):
        monkeypatch.setenv("TRITON_TPU_INT8_SCHED", "minner")
        x, w, ws = _mk(16, 128, 128, seed=12)
        with pytest.raises(ValueError, match="TRITON_TPU_INT8_SCHED"):
            int8_matmul(x, w, ws, interpret=True)

    def test_sched_env_selects_m_inner(self, monkeypatch):
        monkeypatch.setenv("TRITON_TPU_INT8_SCHED", "m_inner")
        x, w, ws = _mk(32, 128, 256, seed=13)
        got = int8_matmul(x, w, ws, block_m=16, block_n=128, interpret=True)
        want = int8_matmul_reference(x, w, ws)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-6)
