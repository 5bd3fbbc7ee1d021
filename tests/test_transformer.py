"""Flagship transformer: sharded-vs-single-device equivalence.

The strongest correctness check for manual-collective SPMD code: one train
step on the full 8-device (dp/pp/ep/sp/tp) mesh must match the same step on a
1-device mesh (where every collective is a no-op).  Validates ring attention,
GPipe ppermute scheduling, tp/ep psums, and the per-leaf gradient psum rule.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_client_tpu.models import transformer as tr


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4,
                head_dim=8, d_ff=64, n_experts=2, dtype=jnp.float32)
    base.update(kw)
    return tr.TransformerConfig(**base)


def _mesh1(cfg):
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1, 1, 1, 1)
    return jax.sharding.Mesh(dev, tr.MESH_AXES)


def _data(cfg, B=8, S=32, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    return jnp.asarray(tokens), jnp.asarray(labels)


@pytest.mark.parametrize("moe", [True, False])
def test_train_step_sharded_matches_single_device(moe):
    cfg = _cfg(n_experts=2 if moe else 0)
    tokens, labels = _data(cfg)
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    opt = tr.adam_init(params)

    mesh8 = tr.make_mesh(8, cfg)
    assert np.prod(list(mesh8.shape.values())) == 8
    step8 = tr.make_train_step(mesh8, cfg, n_micro=2)
    p8, o8, loss8 = step8(jax.tree.map(jnp.copy, params),
                          jax.tree.map(jnp.copy, opt), tokens, labels)

    step1 = tr.make_train_step(_mesh1(cfg), cfg, n_micro=2)
    p1, o1, loss1 = step1(jax.tree.map(jnp.copy, params),
                          jax.tree.map(jnp.copy, opt), tokens, labels)

    np.testing.assert_allclose(float(loss8), float(loss1), rtol=1e-4)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p8[k]), np.asarray(p1[k]), rtol=1e-2, atol=1e-3,
            err_msg=f"param {k} diverged between 8-dev and 1-dev")


def test_grads_sharded_match_single_device_all_axes():
    """Raw-gradient equivalence on a mesh exercising dp AND ep (Adam is
    invariant to per-leaf constant scaling, so the train-step test alone
    cannot catch gradient scale errors — this can)."""
    cfg = _cfg(n_experts=2)
    tokens, labels = _data(cfg)
    params = tr.init_params(jax.random.PRNGKey(3), cfg)

    dev = np.asarray(jax.devices()[:8]).reshape(2, 1, 2, 1, 2)  # dp,pp,ep,sp,tp
    mesh8 = jax.sharding.Mesh(dev, tr.MESH_AXES)
    g8, loss8 = tr.make_grad_fn(mesh8, cfg, n_micro=2)(params, tokens, labels)
    g1, loss1 = tr.make_grad_fn(_mesh1(cfg), cfg, n_micro=2)(params, tokens, labels)

    np.testing.assert_allclose(float(loss8), float(loss1), rtol=1e-4)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g8[k]), np.asarray(g1[k]), rtol=1e-3, atol=1e-6,
            err_msg=f"grad {k} diverged on dp/ep mesh")

    # and on the tp/sp/pp-heavy factorization
    mesh_b = tr.make_mesh(8, cfg)
    gb, _ = tr.make_grad_fn(mesh_b, cfg, n_micro=2)(params, tokens, labels)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(gb[k]), np.asarray(g1[k]), rtol=1e-3, atol=1e-6,
            err_msg=f"grad {k} diverged on tp/sp/pp mesh")


def test_forward_sharded_matches_single_device():
    cfg = _cfg(n_experts=2)
    tokens, _ = _data(cfg)
    params = tr.init_params(jax.random.PRNGKey(1), cfg)
    mesh8 = tr.make_mesh(8, cfg)
    f8 = tr.make_forward(mesh8, cfg)
    f1 = tr.make_forward(_mesh1(cfg), cfg)
    l8 = np.asarray(f8(params, tokens))
    l1 = np.asarray(f1(params, tokens))
    np.testing.assert_allclose(l8, l1, rtol=1e-3, atol=1e-4)


def test_loss_decreases():
    cfg = _cfg(n_experts=2)
    tokens, labels = _data(cfg)
    params = tr.init_params(jax.random.PRNGKey(2), cfg)
    opt = tr.adam_init(params)
    mesh8 = tr.make_mesh(8, cfg)
    step = tr.make_train_step(mesh8, cfg, n_micro=2, lr=3e-3)
    losses = []
    for _ in range(5):
        params, opt, loss = step(params, opt, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_mesh_shape_factorization():
    cfg = tr.TINY
    for n in (1, 2, 4, 8, 16, 32):
        shape = tr.mesh_shape_for(n, cfg)
        assert int(np.prod(list(shape.values()))) == n
    s8 = tr.mesh_shape_for(8, cfg)
    nontrivial = [a for a, v in s8.items() if v > 1]
    assert len(nontrivial) >= 3, s8


class TestInt8EncoderServing:
    """Weight-only int8 storage + dynamic activation quantization for the
    encoder serving forward (TRITON_TPU_QUANT=int8): the layer matmuls run
    int8×int8 with int32 accumulation — the MXU's 2× path on v5e — while
    norms/embed/head stay full precision.  Closeness bar mirrors the decode
    stack's TestInt8Quantization."""

    def _cos(self, a, b):
        a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
        return float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b)))

    def test_quantized_logits_close_to_fp(self):
        cfg = _cfg(n_experts=0, causal=False)
        tokens, _ = _data(cfg)
        params = tr.init_params(jax.random.PRNGKey(5), cfg)
        mesh = _mesh1(cfg)
        fp = tr.make_forward(mesh, cfg)(
            tr.place_params(params, mesh, cfg), tokens)
        qp = tr.quantize_layer_weights(params, cfg)
        q = tr.make_forward(mesh, cfg, quantized=True)(
            tr.place_params(qp, mesh, cfg), tokens)
        assert self._cos(fp, q) > 0.99

    def test_quantized_sharded_matches_single_device(self):
        # the int8 path under tp/sp/pp collectives must agree with the
        # 1-device quantized forward (per-rank activation scales rescale
        # partial products BEFORE the psum — this is what that proves)
        cfg = _cfg(n_experts=0, causal=False)
        tokens, _ = _data(cfg)
        params = tr.quantize_layer_weights(
            tr.init_params(jax.random.PRNGKey(5), cfg), cfg)
        mesh1 = _mesh1(cfg)
        l1 = tr.make_forward(mesh1, cfg, quantized=True)(
            tr.place_params(params, mesh1, cfg), tokens)
        mesh8 = tr.make_mesh(8, cfg)
        l8 = tr.make_forward(mesh8, cfg, quantized=True)(
            tr.place_params(params, mesh8, cfg), tokens)
        np.testing.assert_allclose(np.asarray(l8), np.asarray(l1),
                                   rtol=1e-2, atol=1e-2)

    def test_moe_quantized_close_to_fp(self):
        # MoE goes weight-only (dequant-on-the-fly): routing decisions keep
        # the dense int8 path out of reach, but storage stays int8
        cfg = _cfg(n_experts=2)
        tokens, _ = _data(cfg)
        params = tr.init_params(jax.random.PRNGKey(6), cfg)
        mesh = _mesh1(cfg)
        fp = tr.make_forward(mesh, cfg)(
            tr.place_params(params, mesh, cfg), tokens)
        qp = tr.quantize_layer_weights(params, cfg)
        q = tr.make_forward(mesh, cfg, quantized=True)(
            tr.place_params(qp, mesh, cfg), tokens)
        assert self._cos(fp, q) > 0.99

    def test_env_resolution(self, monkeypatch):
        monkeypatch.delenv("TRITON_TPU_QUANT", raising=False)
        assert tr.resolve_quant("bert_large") == ""
        monkeypatch.setenv("TRITON_TPU_QUANT", "int8")
        assert tr.resolve_quant("bert_large") == "int8"
        # per-model override beats the global, unknown values fail loudly
        monkeypatch.setenv("TRITON_TPU_QUANT_BERT_LARGE", "bf16")
        assert tr.resolve_quant("bert_large") == ""
        assert tr.resolve_quant("other") == "int8"
        monkeypatch.setenv("TRITON_TPU_QUANT", "fp4")
        with pytest.raises(ValueError, match="TRITON_TPU_QUANT"):
            tr.resolve_quant("other")

    def test_bert_serving_forward_under_int8(self, monkeypatch):
        # end-to-end through the zoo entry: the model registry path the
        # server uses (cites BASELINE row 4's serving config)
        monkeypatch.setenv("TRITON_TPU_QUANT", "int8")
        from triton_client_tpu.models import language

        run = language._LazyTransformer(
            _cfg(n_experts=0, causal=False), seed=24, model_name="q_test")
        toks = jnp.zeros((2, 16), jnp.int32)
        out = run(toks)
        assert out.shape == (2, 16, run.cfg.vocab_size)
        assert any(k.endswith("_scale") for k in run._params)
        assert run._params["w1"].dtype == jnp.int8


# ---------------------------------------------------------------------------
# A served model holds its weights as the forward reads them
# (tr.serving_params): the cases are the dense stack, the plain-MoE preset
# and both under TRITON_TPU_QUANT=int8, at cfg.dtype = bfloat16.
# ---------------------------------------------------------------------------

_SERVED_CASES = {
    "dense": dict(n_experts=0, quant=False),
    "moe": dict(n_experts=2, quant=False),
    "int8": dict(n_experts=0, quant=True),
    "moe_int8": dict(n_experts=2, quant=True),
}
_F32_LEAVES = ("head", "router", "ln1", "ln2", "final_ln")

served_case = pytest.mark.parametrize("case", sorted(_SERVED_CASES))


def _served_cfg(case):
    # a vocabulary no other width equals, so that a shape names its leaf
    return _cfg(n_experts=_SERVED_CASES[case]["n_experts"], causal=False,
                vocab_size=48, dtype=jnp.bfloat16)


def _f32_params(case, cfg, seed=7):
    params = tr.init_params(jax.random.PRNGKey(seed), cfg)
    if _SERVED_CASES[case]["quant"]:
        params = tr.quantize_layer_weights(params, cfg)
    return params


@served_case
def test_serving_params_forward_bit_for_bit(case):
    cfg = _served_cfg(case)
    tokens, _ = _data(cfg, B=2, S=16)
    params = _f32_params(case, cfg)
    mesh = _mesh1(cfg)
    fwd = tr.make_forward(mesh, cfg, quantized=_SERVED_CASES[case]["quant"])
    cast_each_forward = fwd(tr.place_params(params, mesh, cfg), tokens)
    cast_once = fwd(
        tr.place_params(tr.serving_params(params, cfg), mesh, cfg), tokens)
    assert cast_once.dtype == cast_each_forward.dtype
    np.testing.assert_array_equal(np.asarray(cast_once),
                                  np.asarray(cast_each_forward))


@served_case
def test_serving_params_casts_what_the_forward_casts_and_no_more(case):
    cfg = _served_cfg(case)
    params = _f32_params(case, cfg)
    served = tr.serving_params(params, cfg)
    assert set(served) == set(params)
    for k, v in served.items():
        if params[k].dtype == jnp.int8:
            assert v.dtype == jnp.int8, k
        elif k in _F32_LEAVES or k.endswith("_scale"):
            assert v.dtype == jnp.float32, k
        else:
            assert k in tr._CAST_LEAVES and v.dtype == jnp.bfloat16, k
        assert v.shape == params[k].shape, k
    assert served["embed"].dtype == jnp.bfloat16
    # an f32 model (the tests' own, __graft_entry__) has nothing to narrow
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    assert all(v is params[k]
               for k, v in tr.serving_params(params, f32).items())


def _lazy(case, monkeypatch):
    from triton_client_tpu.models import language

    if _SERVED_CASES[case]["quant"]:
        monkeypatch.setenv("TRITON_TPU_QUANT", "int8")
    else:
        monkeypatch.delenv("TRITON_TPU_QUANT", raising=False)
    run = language._LazyTransformer(_served_cfg(case), seed=24,
                                    model_name="served_test")
    run._ensure()
    return run


@served_case
def test_lazy_transformer_holds_no_f32_cast_leaf(case, monkeypatch):
    run = _lazy(case, monkeypatch)
    quant = _SERVED_CASES[case]["quant"]
    for k in tr._CAST_LEAVES:
        if k in run._params:
            want = (jnp.int8 if quant and k != "embed" else jnp.bfloat16)
            assert run._params[k].dtype == want, k
    for k in _F32_LEAVES:
        if k in run._params:
            assert run._params[k].dtype == jnp.float32, k
    # and it answers as the forward over the f32 parameters does
    tokens, _ = _data(run.cfg, B=2, S=16)
    params = _f32_params(case, run.cfg, seed=24)
    want = tr.make_forward(run.mesh, run.cfg, quantized=quant)(
        tr.place_params(params, run.mesh, run.cfg), tokens)
    np.testing.assert_array_equal(np.asarray(run(tokens)), np.asarray(want))


def _weight_converts(lowered_text, params):
    """The ``convert`` lines of a lowered forward whose operand has the
    shape of a matrix or of the embedding, stacked or one layer's slice of
    it (the scan's body converts a slice; XLA lifts it to the stack)."""
    shapes = set()
    for k in tr._CAST_LEAVES:
        if k in params:
            shape = params[k].shape
            for dims in (shape, shape[1:]) if k != "embed" else (shape,):
                shapes.add("tensor<" + "x".join(map(str, dims)) + "x")
    return [line.strip() for line in lowered_text.splitlines()
            if "stablehlo.convert" in line
            and any(s in line.split(":", 1)[-1] for s in shapes)]


@served_case
def test_served_forward_lowers_without_a_weight_convert(case, monkeypatch):
    run = _lazy(case, monkeypatch)
    tokens, _ = _data(run.cfg, B=2, S=16)
    text = run._fwd.lower(run._params, tokens).as_text()
    quant = _SERVED_CASES[case]["quant"]
    found = _weight_converts(text, run._params)
    if case == "moe_int8":
        # expert matrices stay int8 and are widened on the fly (``_mw``):
        # those converts read int8, never f32
        assert found and all("xi8>" in line for line in found), found
    else:
        assert not found, found
    # the reader does see the cast where the parameters are f32
    params = _f32_params(case, run.cfg)
    f32_text = tr.make_forward(run.mesh, run.cfg, quantized=quant).lower(
        tr.place_params(params, run.mesh, run.cfg), tokens).as_text()
    assert any("xf32>" in line.split("->")[0]
               for line in _weight_converts(f32_text, params))


@pytest.mark.parametrize("moe", [True, False])
def test_train_step_takes_and_returns_f32(moe):
    # training keeps its master weights: what serving_params narrows for a
    # served forward stays f32 through a step, optimizer state too
    cfg = _cfg(n_experts=2 if moe else 0, dtype=jnp.bfloat16)
    tokens, labels = _data(cfg)
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    assert all(v.dtype == jnp.float32 for v in params.values())
    mesh = _mesh1(cfg)
    step = tr.make_train_step(mesh, cfg, n_micro=2)
    p, o, loss = step(tr.place_params(params, mesh, cfg),
                      tr.place_opt(tr.adam_init(params), mesh, cfg),
                      tokens, labels)
    assert np.isfinite(float(loss))
    assert set(p) == set(params)
    for k in params:
        assert p[k].dtype == jnp.float32, k
        assert o["mu"][k].dtype == jnp.float32, k
        assert o["nu"][k].dtype == jnp.float32, k
