"""A stack run several times over one set of weights (models/looped.py)
against the benchmark's plain reference (chipbench/references/ouro_2_6b.py)
at a tiny size on the CPU: hidden 64, 4 heads of 16, a SwiGLU of 176, 2
layers run 3 times, a vocabulary of 256, a prompt of 16 and 6 new tokens.

Tolerances.  With the bfloat16 weights upcast and everything computed in
float32 the program (kernel-shaped attention, a cache for every loop step,
two nested scans) and the reference (one full causal forward, a layer at a
time, no cache) do the same arithmetic in another order: 1e-5 relative on a
logit row (read: 1e-6 to 2e-6), 1e-6 absolute on an exit probability.  A
planted fault reads 0.3 and more.  The tokens are compared exactly: at these
sizes no arg-max lies within float32's rounding of its runner-up.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import triton_client_tpu.grpc as grpcclient  # noqa: E402
from chipbench.files import load_json, load_module  # noqa: E402
from chipbench.tests.tiny_ouro import TINY_OURO, program_config  # noqa: E402
from triton_client_tpu.models import language  # noqa: E402
from triton_client_tpu.models import looped as lp  # noqa: E402
from triton_client_tpu.server import ModelRegistry  # noqa: E402
from triton_client_tpu.server.model import ModelStats  # noqa: E402
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module("references", "ouro_2_6b")
TINY = program_config(TINY_OURO)
P, G, T = TINY.seq_len, TINY.new_tokens, TINY.total_ut_steps


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def params():
    return lp.init_params(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, TINY.vocab_size, (3, P)).astype(np.int32)


def _generate(params, tokens, cfg=TINY):
    out = jax.jit(lambda p, t: lp.generate(p, t, cfg))(
        params, jnp.asarray(tokens))
    return jax.tree_util.tree_map(np.asarray, out)


def _through_the_cache(params, prompt, forced, cfg=TINY, turn=None):
    """Prefill, then one decode step a forced token: the logits and the
    exit probabilities at the prompt's last position and at every forced
    one ``[b, 1 + len(forced[0]), ...]``.  ``turn`` alters the cache before
    each decode step reads it (a planted fault)."""

    @jax.jit
    def run(p, prompt, forced):
        cache, last, gates = lp.prefill(p, prompt, cfg)
        rows = [lp.readout(p, last, gates[..., -1], cfg)[:2]]
        for i in range(forced.shape[1]):
            if turn is not None:
                cache = turn(cache)
            cache, last, gates = lp.decode_step(
                p, cache, forced[:, i], prompt.shape[1] + i, cfg)
            rows.append(lp.readout(p, last, gates, cfg)[:2])
        return (jnp.stack([r[0] for r in rows], axis=1),
                jnp.stack([r[1] for r in rows], axis=1))

    return run(params, jnp.asarray(prompt), jnp.asarray(forced))


def _file_cfg(**changed):
    return dict(TINY_OURO, **changed)


# -- program against reference ----------------------------------------------

def test_prefill_then_decode_is_the_full_forward_at_every_position(params,
                                                                   tokens):
    """Teacher-forced on tokens of the test's own: the prefill's last
    position and each of the decode steps after it give the logits and the
    exit probabilities the reference's one causal forward gives there."""
    forced = np.random.default_rng(1).integers(
        0, TINY.vocab_size, (3, G - 1)).astype(np.int32)
    logits, pdf = _through_the_cache(_f32(params), tokens, forced)
    want = REF.Reference(TINY_OURO).forward(
        np.concatenate([tokens, forced], axis=1), np.arange(P - 1, P + G - 1))
    assert logits.shape == (3, G, TINY.vocab_size) and pdf.shape == (3, G, T)
    for n in range(3):
        for i in range(G):
            assert _rel_l2(logits[n, i], want["logits"][n, i]) < 1e-5, (n, i)
    np.testing.assert_allclose(pdf, want["exit_pdf"], atol=1e-6)
    np.testing.assert_allclose(np.asarray(pdf).sum(-1), 1.0, atol=1e-6)


def test_the_generation_is_greedy_and_the_references(params, tokens):
    """``generate`` in float32: every token is the arg-max of the
    reference's own logits at the position before it, the two rows returned
    are the reference's, and the counters read the step count a token."""
    got = _generate(_f32(params), tokens)
    assert got["tokens"].shape == (3, G)
    want = REF.Reference(TINY_OURO).forward(
        np.concatenate([tokens, got["tokens"][:, :G - 1]], axis=1),
        np.arange(P - 1, P + G - 1))
    np.testing.assert_array_equal(got["tokens"], want["logits"].argmax(-1))
    replayed = REF.Reference(TINY_OURO).replay(tokens, got["tokens"])
    for n in range(3):
        for row in range(2):
            assert _rel_l2(got["logits"][n, row],
                           replayed["logits"][n, row]) < 1e-5
    np.testing.assert_allclose(got["exit_pdf"], replayed["exit_pdf"],
                               atol=1e-6)
    np.testing.assert_array_equal(got["counters"]["loop_tokens"],
                                  [P + G - 1] * 3)
    np.testing.assert_array_equal(got["counters"]["loop_steps"],
                                  [T * (P + G - 1)] * 3)


@pytest.mark.parametrize("fault", ["stale_cache", "no_final_norm"])
def test_a_planted_fault_fails_the_comparison(params, tokens, monkeypatch,
                                              fault):
    """Loop step ``t`` reading step ``t - 1``'s cache, and the final norm
    left out between the steps: each moves the decoded rows far beyond
    anything rounding does."""
    forced = np.random.default_rng(1).integers(
        0, TINY.vocab_size, (3, G - 1)).astype(np.int32)
    turn = None
    if fault == "stale_cache":
        # the cache is [T, L, ...]: turned by one along its first axis, step
        # t finds what step t - 1 wrote
        def turn(cache):
            return tuple(jnp.roll(c, 1, axis=0) for c in cache)
    else:
        kept = lp._close_step

        def close(params, x, cfg):
            _, gate = kept(params, x, cfg)
            return x, gate

        monkeypatch.setattr(lp, "_close_step", close)
    logits, pdf = _through_the_cache(_f32(params), tokens, forced, turn=turn)
    want = REF.Reference(TINY_OURO).forward(
        np.concatenate([tokens, forced], axis=1), np.arange(P - 1, P + G - 1))
    if fault == "no_final_norm":
        # the prefill's row is wrong already
        assert _rel_l2(logits[:, 0], want["logits"][:, 0]) > 0.1
    else:
        assert _rel_l2(logits[:, 0], want["logits"][:, 0]) < 1e-5
    assert min(_rel_l2(logits[n, -1], want["logits"][n, -1])
               for n in range(3)) > 0.1
    limits = TINY_OURO["limits"]
    assert _rel_l2(logits[:, -1], want["logits"][:, -1]) \
        > limits["logit_rel_l2_median"]


def test_one_loop_step_is_a_plain_stack(params, tokens):
    """``total_ut_steps = 1``: the layers once, the final norm, the head;
    every exit probability is 1."""
    once = dataclasses.replace(TINY, total_ut_steps=1)
    got = _generate(_f32(params), tokens, once)
    np.testing.assert_array_equal(got["exit_pdf"], 1.0)
    np.testing.assert_array_equal(got["counters"]["loop_steps"],
                                  [P + G - 1] * 3)
    # by hand: the reference's own layer, twice, then norm and head
    cfg = _file_cfg(total_ut_steps=1)
    seqs = np.concatenate([tokens, got["tokens"][:, :G - 1]], axis=1)
    with jax.default_matmul_precision("highest"):
        xs = jnp.take(REF.outer_weights(cfg, "embed"), seqs, axis=0)
        for index in range(TINY.num_hidden_layers):
            w = REF.layer_weights(cfg, index)
            xs = jax.vmap(lambda x: REF.layer(x, w, cfg))(xs)
        rows = REF._rmsnorm(xs[:, [P - 1, P + G - 2]], TINY.rms_norm_eps) \
            @ REF.outer_weights(cfg, "head")
    for n in range(3):
        for row in range(2):
            assert _rel_l2(got["logits"][n, row], rows[n, row]) < 1e-5
    # and it is not what three steps give
    assert _rel_l2(got["logits"], _generate(_f32(params), tokens)["logits"]) \
        > 0.1


def test_a_threshold_under_one_leaves_earlier(params, tokens):
    """At 0.6 a token leaves at the first step whose cumulated probability
    reaches it: fewer steps counted than the step count, the logits those of
    the step it left at, as the reference's."""
    cfg = dataclasses.replace(TINY, early_exit_threshold=0.6)
    got = _generate(_f32(params), tokens, cfg)
    steps, toks = got["counters"]["loop_steps"], got["counters"]["loop_tokens"]
    assert (steps < T * toks).all() and (steps >= toks).all()
    reference = REF.Reference(_file_cfg(early_exit_threshold=0.6))
    replayed = reference.replay(tokens, got["tokens"])
    assert (replayed["exit_step"] < T - 1).any()
    for n in range(3):
        for row in range(2):
            assert _rel_l2(got["logits"][n, row],
                           replayed["logits"][n, row]) < 1e-5
    # the count is the reference's own, token by token
    whole = reference.forward(
        np.concatenate([tokens, got["tokens"][:, :G - 1]], axis=1),
        np.arange(P + G - 1))
    np.testing.assert_array_equal(steps, (whole["exit_step"] + 1).sum(-1))
    # the rule, on probabilities written down
    pdf = jnp.array([[0.5, 0.7, 0.1, 0.0], [0.2, 0.2, 0.3, 0.0],
                     [0.3, 0.1, 0.6, 1.0]])
    np.testing.assert_array_equal(lp.exit_step(pdf, 0.6), [1, 0, 2, 2])
    np.testing.assert_array_equal(lp.exit_step(pdf, 1.0), [2, 2, 2, 2])
    gates = jnp.array([[0.0, 3.0], [0.0, -3.0], [9.0, 0.0]])
    np.testing.assert_allclose(lp.exit_pdf(gates).sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(lp.exit_pdf(gates)[:, 0], [0.5, 0.25, 0.25])


def test_as_served_the_band_is_bfloat16s_and_int8_lies_outside(params,
                                                               tokens):
    """As served (bfloat16 matrices and cache, float32 stream): the
    reference, teacher-forced on the program's tokens, gives the two rows
    inside the band that bfloat16 leaves; int8 storage lies outside it."""
    got = _generate(params, tokens)
    want = REF.Reference(TINY_OURO).replay(tokens, got["tokens"])
    served = [_rel_l2(got["logits"][n, r], want["logits"][n, r])
              for n in range(3) for r in range(2)]
    assert np.median(served) < 0.02 and max(served) < 0.05, served
    assert np.abs(got["exit_pdf"] - want["exit_pdf"]).max() < 0.002
    np.testing.assert_array_equal(got["tokens"][:, 0],
                                  got["logits"][:, 0].argmax(-1))
    np.testing.assert_array_equal(got["tokens"][:, -1],
                                  got["logits"][:, 1].argmax(-1))
    quantized = _generate(lp.init_params(TINY, quantized=True), tokens)
    forced = REF.Reference(TINY_OURO).replay(tokens, quantized["tokens"])
    control = [_rel_l2(quantized["logits"][n, r], forced["logits"][n, r])
               for n in range(3) for r in range(2)]
    assert np.median(control) > 2 * np.median(served), (served, control)


def test_weights_are_the_references_bit_for_bit(params):
    layer = REF.layer_weights(TINY_OURO, 1)
    for name, want in layer.items():
        np.testing.assert_array_equal(
            np.asarray(params["layers"][name][1], np.float32), want)
    for name in ("embed", "head", "exit_gate", "exit_gate_bias"):
        np.testing.assert_array_equal(
            np.asarray(params[name], np.float32),
            REF.outer_weights(TINY_OURO, name))
    for name in lp._NORMS:
        np.testing.assert_array_equal(
            np.asarray(params["layers"][name], np.float32), 1.0)


def test_the_constant_is_the_configuration_file():
    """``OURO_2_6B`` is ``chipbench/configs/ouro_2_6b.json`` key by key,
    and the two FLOP counts agree."""
    cfg = load_json(ROOT, "chipbench", "configs", "ouro_2_6b.json")
    assert program_config(cfg) == lp.OURO_2_6B
    yardstick = load_module("flop_counts", "ouro_2_6b")
    assert lp.flops_per_inference(lp.OURO_2_6B) == pytest.approx(
        yardstick.flops_per_inference(cfg), rel=1e-12)
    assert yardstick.flops_per_inference(cfg) == pytest.approx(2.841e12,
                                                               rel=1e-3)
    # the whole model: 2.668 G parameters, counted from the shapes
    shapes = jax.eval_shape(lambda: lp.init_params(lp.OURO_2_6B))
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == 2_667_974_657
    assert 2 * count == pytest.approx(5.336e9, rel=1e-4)


@pytest.mark.parametrize("bad,match", [
    ({"num_key_value_heads": 2}, "key/value heads"),
    ({"total_ut_steps": 0}, "at least 1"), ({"new_tokens": 0}, "at least 1")])
def test_a_shape_the_block_cannot_run_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(TINY, **bad)


# -- the served path ---------------------------------------------------------

def test_device_counters_leave_out_the_rows_the_batcher_padded():
    stats = ModelStats()
    counters = {"loop_steps": np.array([63, 63]),
                "loop_tokens": np.array([21, 21])}
    stats.queue_device_counters(counters, 1, 21)  # the second row is padding
    entries = stats.extension_entries()
    assert entries["loop_steps"] == {"count": 63, "ns": 0}
    assert entries["loop_tokens"] == {"count": 21, "ns": 0}
    stats.queue_device_counters(counters, 2, 21)
    assert stats.loop_steps == 189 and stats.loop_tokens == 63


@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    registry.register_model(language.make_ouro_2_6b(TINY))
    with ServerHarness(registry) as h:
        yield h


def test_the_factory_serves_the_generation_and_its_counters(server, params,
                                                            tokens):
    inp = grpcclient.InferInput("INPUT_IDS", list(tokens.shape), "INT32")
    inp.set_data_from_numpy(tokens)
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        result = client.infer("ouro_2_6b", [inp])
    want = _generate(params, np.concatenate(
        [tokens, np.zeros((8 - len(tokens), P), np.int32)]))
    np.testing.assert_array_equal(result.as_numpy("TOKENS"),
                                  want["tokens"][:3])
    np.testing.assert_array_equal(result.as_numpy("LOGITS"),
                                  want["logits"][:3])
    np.testing.assert_array_equal(result.as_numpy("EXIT_PDF"),
                                  want["exit_pdf"][:3])
    assert result.as_numpy("LOGITS").shape == (3, 2, TINY.vocab_size)
    assert result.as_numpy("EXIT_PDF").shape == (3, 2, T)
    assert result.as_numpy("DEVICE_COUNTER.loop_steps") is None
    stats = server.core.statistics("ouro_2_6b")[0]["inference_stats"]
    # the padded rows' tokens are not counted
    assert stats["loop_tokens"]["count"] == 3 * (P + G - 1)
    assert stats["loop_steps"]["count"] == 3 * T * (P + G - 1)
    assert stats["expert_rows"]["count"] == 0


def test_the_int8_control_is_served_from_the_same_factory(monkeypatch,
                                                          tokens):
    monkeypatch.setenv("TRITON_TPU_QUANT_OURO_2_6B", "int8")
    run = language._LazyBlock(TINY, "ouro_2_6b", "looped", "generate")
    out = run(jnp.asarray(tokens))
    assert run._params["layers"]["w_gate"].dtype == jnp.int8
    assert "w_gate_scale" in run._params["layers"]
    assert np.isfinite(np.asarray(out["logits"])).all()


def test_a_mesh_of_two_is_refused(monkeypatch):
    monkeypatch.setenv("TRITON_TPU_SERVE_MESH_OURO_2_6B", "tp=2")
    run = language._LazyBlock(TINY, "ouro_2_6b", "looped", "generate")
    with pytest.raises(ValueError, match="exchange"):
        run(jnp.zeros((1, P), jnp.int32))


def test_the_zoo_registers_it_without_allocating():
    from triton_client_tpu.models import zoo

    registry = ModelRegistry()
    zoo.register_all(registry)
    model = registry.get("ouro_2_6b")
    assert model.config.input[0].dims == [128]
    assert [list(o.dims) for o in model.config.output] == [
        [16], [2, 49152], [2, 4]]
    assert model.config.max_batch_size == 16
    assert list(model.config.dynamic_batching.preferred_batch_size) == [8, 16]
