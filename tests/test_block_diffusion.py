"""Generation by diffusion over blocks (models/block_diffusion.py) against
the benchmark's plain reference (chipbench/references/sdar_30b_a3b.py) at a
tiny size on the CPU: hidden 64, 4 query heads over 2 key/value heads, 8
experts of which a token takes 2, 2 layers, a vocabulary of 512, a block of
4 denoised in 4 passes.

Tolerances.  With the bfloat16 weights upcast and everything computed in
float32 the program (kernel-shaped attention, sorted grouped experts, a
cache) and the reference (a full forward a pass, an expert at a time) do the
same arithmetic in another order: 1e-5 relative for one prefill (read:
4e-7), 1e-4 for rows that went through a whole trajectory.  The trajectory
itself (tokens, the pass each was committed at) is compared exactly: at
these sizes no arg-max lies within float32's rounding of its runner-up.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import triton_client_tpu.grpc as grpcclient  # noqa: E402
from chipbench.files import load_json, load_module  # noqa: E402
from chipbench.tests.tiny_sdar import TINY_SDAR, program_config  # noqa: E402
from triton_client_tpu.models import block_diffusion as bd  # noqa: E402
from triton_client_tpu.models import language  # noqa: E402
from triton_client_tpu.models import parts  # noqa: E402
from triton_client_tpu.server import ModelRegistry  # noqa: E402
from triton_client_tpu.server.model import ModelStats  # noqa: E402
from triton_client_tpu.server.testing import ServerHarness  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = load_module("references", "sdar_30b_a3b")
TINY = program_config(TINY_SDAR)
P, G, B = TINY.seq_len, TINY.new_tokens, TINY.block_length


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def params():
    return bd.init_params(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, TINY.vocab_size, (3, P)).astype(np.int32)


def _generate(params, tokens, cfg=TINY):
    out = jax.jit(lambda p, t: bd.generate(p, t, cfg))(
        params, jnp.asarray(tokens))
    return jax.tree_util.tree_map(np.asarray, out)


# -- program against reference ----------------------------------------------

def test_prefill_logits_at_every_position_are_the_references(params, tokens):
    _, rows, logits = jax.jit(
        lambda p, t: bd.prefill(p, t, TINY, want_logits=True))(
            _f32(params), jnp.asarray(tokens))
    counted = []
    want = np.stack(REF.Reference(TINY_SDAR).logits(
        list(tokens), [np.arange(P)] * 3, expert_rows=counted))
    assert logits.shape == (3, P, TINY.vocab_size)
    assert _rel_l2(logits, want) < 1e-5
    # the pairs each expert was routed, by prompt and layer; none dropped
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.stack(counted, axis=1))
    assert int(np.asarray(rows).sum()) == 3 * P * 2 * 2


@pytest.mark.parametrize("threshold,head_scale", [(None, 1.0), (0.9, 60.0)],
                         ids=["static", "threshold"])
def test_the_whole_trajectory_is_the_references(params, tokens, monkeypatch,
                                                threshold, head_scale):
    """Static low-confidence remasking, and the dynamic rule on a model
    whose head is scaled until some confidences clear the threshold: fewer
    passes, the same answer as the reference's."""
    cfg = dataclasses.replace(TINY, confidence_threshold=threshold)
    file_cfg = dict(TINY_SDAR, assumed={"generation": dict(
        TINY_SDAR["assumed"]["generation"], confidence_threshold=threshold)})
    p32 = _f32(params)
    p32["head"] = p32["head"] * head_scale
    drawn = REF.outer_weights
    monkeypatch.setattr(REF, "outer_weights", lambda c, name: drawn(
        c, name) * (head_scale if name == "head" else 1.0))
    got = _generate(p32, tokens, cfg)
    reference = REF.Reference(file_cfg)
    passes = 0
    for n, ids in enumerate(tokens):
        want = reference.generate(ids)
        np.testing.assert_array_equal(got["tokens"][n], want["TOKENS"])
        np.testing.assert_array_equal(got["commit_pass"][n],
                                      want["COMMIT_PASS"])
        for row in range(2):
            assert _rel_l2(got["logits"][n, row], want["LOGITS"][row]) < 1e-4
        passes = max(passes, want["passes"])
    counters = got["counters"]
    np.testing.assert_array_equal(counters["denoise_tokens"], [G] * 3)
    blocks = G // B
    if threshold is None:
        # 4 passes a block and none for the cache alone: 1.0 passes a token
        np.testing.assert_array_equal(counters["denoise_passes"],
                                      [4 * blocks] * 3)
        for block in got["commit_pass"].reshape(-1, B):
            assert sorted(block) == [0, 1, 2, 3]
    else:
        # a batch runs as long as its slowest sequence: no fewer passes than
        # the reference's slowest prompt alone, fewer than the static rule
        assert passes <= counters["denoise_passes"][0] < 4 * blocks
    # every pair of every pass is counted, prefill and passes together: the
    # first pass of every block but block 0 carries the block before it
    rows_a_token = TINY.num_experts_per_tok * TINY.num_hidden_layers
    np.testing.assert_array_equal(
        counters["expert_rows"].sum((1, 2)),
        (P + B * (counters["denoise_passes"] + blocks - 1)) * rows_a_token)


def test_passes_through_the_cache_are_the_full_forward(params, tokens):
    """Prefill, then a block through the cache: the logits of the block's
    rows equal the full forward's over prompt and block under the block
    mask; with the block's keys written to the cache so do the next
    block's."""
    p32 = _f32(params)
    rng = np.random.default_rng(1)
    blocks = rng.integers(0, TINY.vocab_size, (3, 2 * B)).astype(np.int32)

    @jax.jit
    def through_cache(p, prompt, blocks):
        cache, _, _ = bd.prefill(p, prompt, TINY)
        x, (k, v), _, _ = bd.block_pass(p, cache, blocks[:, :B], P, TINY)
        first = parts.head(p, x, TINY)
        cache = tuple(jax.lax.dynamic_update_slice_in_dim(c, new, P, 3)
                      for c, new in zip(cache, (k, v)))
        x, _, _, _ = bd.block_pass(p, cache, blocks[:, B:], P + B, TINY)
        return first, parts.head(p, x, TINY)

    first, second = through_cache(p32, jnp.asarray(tokens),
                                  jnp.asarray(blocks))
    _, _, full = jax.jit(lambda p, t: bd.prefill(
        p, t, dataclasses.replace(TINY, seq_len=P + 2 * B),
        want_logits=True))(p32, jnp.concatenate([tokens, blocks], axis=1))
    assert _rel_l2(first, full[:, P:P + B]) < 1e-5
    assert _rel_l2(second, full[:, P + B:]) < 1e-5
    # and a block cannot see the next one: the prompt's and the first
    # block's rows do not move when the second block changes
    _, _, other = jax.jit(lambda p, t: bd.prefill(
        p, t, dataclasses.replace(TINY, seq_len=P + 2 * B),
        want_logits=True))(p32, jnp.concatenate(
            [tokens, blocks[:, :B], (blocks[:, B:] + 1) % 512], axis=1))
    np.testing.assert_array_equal(np.asarray(other[:, :P + B]),
                                  np.asarray(full[:, :P + B]))


@pytest.fixture(scope="module")
def two_blocks(params, tokens):
    """In float32, behind one prefill: ``(first, lone, joint)``, a pass over
    a block of final tokens alone, a pass over the next block of ids
    against the cache that holds the first's keys, and one pass over
    both, each as ``(x, (k, v), rows, routes)``; ``joint(ids)`` runs the
    joint pass with other ids in the second block."""
    p32 = _f32(params)
    rng = np.random.default_rng(2)
    blocks = jnp.asarray(
        rng.integers(0, TINY.vocab_size, (3, 2 * B)).astype(np.int32))
    cache, _, _ = jax.jit(lambda p, t: bd.prefill(p, t, TINY))(
        p32, jnp.asarray(tokens))
    run = jax.jit(lambda c, t, at: bd.block_pass(p32, c, t, at, TINY))
    first = run(cache, blocks[:, :B], P)
    written = tuple(jax.lax.dynamic_update_slice_in_dim(c, new, P, 3)
                    for c, new in zip(cache, first[1]))
    lone = run(written, blocks[:, B:], P + B)
    joint = lambda ids: run(  # noqa: E731
        cache, jnp.concatenate([blocks[:, :B], ids], axis=1), P)
    return p32, blocks, first, lone, joint


@pytest.mark.parametrize("what", ["keys", "logits", "counts", "one_way"])
def test_a_block_riding_the_next_blocks_pass_is_a_pass_of_its_own(two_blocks,
                                                                 what):
    """``[block n final | block n+1]`` as one pass of ``2B`` rows: block
    ``n`` gets the keys and values a lone pass gives it, block ``n+1`` the
    logits a lone pass gives against the cache so written; every row's
    pairs are counted; block ``n``'s half cannot see block ``n+1``."""
    p32, blocks, first, lone, joint = two_blocks
    x, (k, v), rows, routes = joint(blocks[:, B:])
    if what == "keys":
        assert k.shape == v.shape == (
            TINY.num_hidden_layers, 3, TINY.num_key_value_heads, 2 * B,
            TINY.head_dim)
        for got, want in zip((k, v), first[1]):
            assert _rel_l2(got[..., :B, :], want) < 1e-6
    elif what == "logits":
        assert _rel_l2(parts.head(p32, x[:, B:], TINY),
                       parts.head(p32, lone[0], TINY)) < 1e-5
        np.testing.assert_array_equal(np.asarray(routes[:, B:]),
                                      np.asarray(lone[3]))
    elif what == "counts":
        np.testing.assert_array_equal(np.asarray(rows),
                                      np.asarray(first[2] + lone[2]))
        assert int(rows.sum()) == 3 * 2 * B * (
            TINY.num_experts_per_tok * TINY.num_hidden_layers)
    else:
        x2, (k2, v2), _, routes2 = joint((blocks[:, B:] + 1) % 512)
        for got, want in ((x2[:, :B], x[:, :B]),
                          (k2[..., :B, :], k[..., :B, :]),
                          (v2[..., :B, :], v[..., :B, :]),
                          (routes2[:, :B], routes[:, :B])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not np.array_equal(np.asarray(x2[:, B:]), np.asarray(x[:, B:]))


def test_the_head_runs_over_the_open_block_alone(params, tokens):
    """No pass puts the riding rows through the head: the matrix
    ``[D,V]`` meets ``[b,B,D]`` in every instance of the pass, never
    ``[b,2B,D]``; and the first pass of a later block is of ``2B``
    rows."""
    text = str(jax.make_jaxpr(lambda p, t: bd.generate(p, t, TINY))(
        params, jnp.asarray(tokens)))
    V, D = TINY.vocab_size, TINY.hidden_size
    assert f"f32[3,{B},{V}]" in text
    assert f"[3,{2 * B},{V}]" not in text
    assert f"bf16[3,{2 * B},{D}]" in text      # the joint pass is there


@pytest.mark.parametrize("blocks", [1, 2])
def test_no_pass_follows_the_last_block(params, tokens, blocks):
    """A generation of one block runs its denoising passes and nothing
    else; of two, the second's first pass carries the first and nothing
    carries the second.  The answer's first block is the same either way:
    it never saw what came after."""
    cfg = dataclasses.replace(TINY, new_tokens=blocks * B)
    got = _generate(params, tokens, cfg)
    T = TINY.denoising_steps
    np.testing.assert_array_equal(got["counters"]["denoise_passes"],
                                  [T * blocks] * 3)
    np.testing.assert_array_equal(
        got["counters"]["expert_rows"].sum((1, 2)),
        [(P + B * (T * blocks + blocks - 1))
         * TINY.num_experts_per_tok * TINY.num_hidden_layers] * 3)
    whole = _generate(params, tokens)
    np.testing.assert_array_equal(got["tokens"][:, :B], whole["tokens"][:, :B])
    np.testing.assert_array_equal(got["logits"][:, 0], whole["logits"][:, 0])


def test_replay_on_the_programs_own_answer(params, tokens):
    """As served (bfloat16): the reference, teacher-forced on the program's
    tokens, order and routing of the answering position, gives the two
    logit rows inside the band that bfloat16 activations leave; int8
    storage lies outside it."""
    got = _generate(params, tokens)
    L, k = TINY.num_hidden_layers, TINY.num_experts_per_tok
    assert got["routes"].shape == (3, 2, L, k)
    replayed = REF.Reference(TINY_SDAR).replay(
        tokens, got["tokens"], got["commit_pass"], got["routes"])
    want = replayed["logits"]
    assert want.shape == got["logits"].shape == (3, 2, TINY.vocab_size)
    served = [_rel_l2(got["logits"][n, r], want[n, r])
              for n in range(3) for r in range(2)]
    # (with 20 keys a row, a choice exchanged at another token still shows:
    # 0.02-0.03; over 1,056 keys it does not)
    assert np.median(served) < 0.012 and max(served) < 0.05, served
    # the experts the program chose are the reference's own, or lie within
    # rounding of its eighth
    assert replayed["route_shortfall"].shape == (3, 2)
    assert replayed["route_shortfall"].max() < 0.1
    # the tokens returned are the arg-max of the rows returned
    first = got["commit_pass"][:, :B].argmin(-1)
    np.testing.assert_array_equal(
        got["tokens"][np.arange(3), first], got["logits"][:, 0].argmax(-1))
    quantized = _generate(bd.init_params(TINY, quantized=True), tokens)
    forced = REF.Reference(TINY_SDAR).replay(
        tokens, quantized["tokens"], quantized["commit_pass"],
        quantized["routes"])["logits"]
    control = [_rel_l2(quantized["logits"][n, r], forced[n, r])
               for n in range(3) for r in range(2)]
    assert np.median(control) > 2 * np.median(served), (served, control)


def test_replay_takes_the_programs_routing_and_says_how_far_it_lies(params,
                                                                    tokens):
    """In float32 the program's choices are the reference's own: no
    shortfall, the rows equal to 1e-4.  An expert exchanged for one the
    router ranks low moves the row and shows as a shortfall."""
    got = _generate(_f32(params), tokens)
    reference = REF.Reference(TINY_SDAR)
    replayed = reference.replay(tokens, got["tokens"], got["commit_pass"],
                                got["routes"])
    assert replayed["route_shortfall"].max() < 1e-4
    for n in range(3):
        for r in range(2):
            assert _rel_l2(got["logits"][n, r],
                           replayed["logits"][n, r]) < 1e-4
    wrong = got["routes"].copy()
    unused = [e for e in range(TINY.num_experts)
              if e not in wrong[1, 0, 0]][-1]
    wrong[1, 0, 0, 0] = unused
    other = reference.replay(tokens, got["tokens"], got["commit_pass"], wrong)
    assert other["route_shortfall"][1, 0] > 0
    assert _rel_l2(other["logits"][1, 0], replayed["logits"][1, 0]) > 1e-3
    np.testing.assert_array_equal(other["logits"][0], replayed["logits"][0])


def test_weights_are_the_references_bit_for_bit(params):
    layer = REF.layer_weights(TINY_SDAR, 1)
    for name, want in layer.items():
        np.testing.assert_array_equal(
            np.asarray(params["layers"][name][1], np.float32), want)
    E = TINY.num_experts
    expert = REF.expert_weights(TINY_SDAR, 1, 5)
    for name, want in expert.items():
        np.testing.assert_array_equal(
            np.asarray(params["experts"]["we_" + name][E + 5], np.float32),
            want)
    for name in ("embed", "head"):
        np.testing.assert_array_equal(
            np.asarray(params[name], np.float32),
            REF.outer_weights(TINY_SDAR, name))


def test_the_constant_is_the_configuration_file():
    """``SDAR_30B_A3B_STAGE`` is ``chipbench/configs/sdar_30b_a3b.json``
    key by key, and the two FLOP counts agree."""
    cfg = load_json(ROOT, "chipbench", "configs", "sdar_30b_a3b.json")
    assert program_config(cfg) == bd.SDAR_30B_A3B_STAGE
    yardstick = load_module("flop_counts", "sdar_30b_a3b")
    assert bd.flops_per_inference(bd.SDAR_30B_A3B_STAGE) == pytest.approx(
        yardstick.flops_per_inference(cfg), rel=1e-12)
    assert yardstick.flops_per_inference(cfg) == pytest.approx(0.956e12,
                                                               rel=2e-3)


@pytest.mark.parametrize("bad", [
    {"block_length": 3}, {"seq_len": 18}, {"new_tokens": 10},
    {"denoising_steps": 3}])
def test_a_shape_the_loop_cannot_run_is_refused(bad):
    with pytest.raises(ValueError, match="block_length"):
        dataclasses.replace(TINY, **bad)


# -- the served path ---------------------------------------------------------

def test_device_counters_leave_out_the_rows_the_batcher_padded():
    stats = ModelStats()
    counters = {"expert_rows": np.array([[[3, 0, 1]], [[9, 9, 9]]]),
                "denoise_passes": np.array([10, 10]),
                "denoise_tokens": np.array([8, 8]),
                "experts_touched": np.array([7, 12])}
    stats.queue_device_counters(counters, 1, 56)  # the second row is padding
    entries = stats.extension_entries()
    assert entries["expert_rows"] == {"count": 4, "ns": 0}
    assert entries["expert_tokens"] == {"count": 56, "ns": 0}
    assert entries["denoise_passes"] == {"count": 10, "ns": 0}
    assert entries["denoise_tokens"] == {"count": 8, "ns": 0}
    assert entries["experts_touched"] == {"count": 7, "ns": 0}
    stats.queue_device_counters(counters, 2, 56)
    assert stats.denoise_passes == 30 and stats.experts_touched == 19
    with pytest.raises(KeyError, match="no device counter"):
        stats.queue_device_counters({"nonesuch": np.zeros(2)}, 2)


@pytest.fixture(scope="module")
def server():
    registry = ModelRegistry()
    registry.register_model(language.make_sdar_30b_a3b(TINY))
    with ServerHarness(registry) as h:
        yield h


def _infer(client, ids):
    inp = grpcclient.InferInput("INPUT_IDS", list(ids.shape), "INT32")
    inp.set_data_from_numpy(ids)
    return client.infer("sdar_30b_a3b", [inp])


def test_the_factory_serves_the_generation_and_its_counters(server, params,
                                                            tokens):
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        result = _infer(client, tokens)
    want = _generate(params, np.concatenate(
        [tokens, np.zeros((8 - len(tokens), P), np.int32)]))
    np.testing.assert_array_equal(result.as_numpy("TOKENS"),
                                  want["tokens"][:3])
    np.testing.assert_array_equal(result.as_numpy("COMMIT_PASS"),
                                  want["commit_pass"][:3])
    assert result.as_numpy("LOGITS").shape == (3, 2, TINY.vocab_size)
    np.testing.assert_array_equal(result.as_numpy("ROUTES"),
                                  want["routes"][:3])
    assert result.as_numpy("DEVICE_COUNTER.denoise_passes") is None
    stats = server.core.statistics("sdar_30b_a3b")[0]["inference_stats"]
    blocks = G // B
    assert stats["denoise_passes"]["count"] == 3 * 4 * blocks
    assert stats["denoise_tokens"]["count"] == 3 * G
    # a token passes the experts in the prefill or in its block's 4 passes
    # and once more riding the next block's first, but for the last block's
    tokens_a_row = P + 5 * G - B
    assert stats["expert_tokens"]["count"] == 3 * tokens_a_row * 2
    assert stats["expert_rows"]["count"] == 3 * tokens_a_row * 2 * 2
    # the padded rows' experts are not among those touched
    assert stats["experts_touched"]["count"] == int(
        want["counters"]["experts_touched"][2])
    assert 0 < stats["experts_touched"]["count"] <= 4 * blocks * 2 * 8


def test_a_mesh_of_two_is_refused(monkeypatch):
    monkeypatch.setenv("TRITON_TPU_SERVE_MESH_SDAR_30B_A3B", "ep=2")
    run = language._LazyBlock(TINY, "sdar_30b_a3b", "block_diffusion",
                              "generate")
    with pytest.raises(ValueError, match="exchange"):
        run(jnp.zeros((1, P), jnp.int32))


def test_the_zoo_registers_it_without_allocating():
    from triton_client_tpu.models import zoo

    registry = ModelRegistry()
    zoo.register_all(registry)
    model = registry.get("sdar_30b_a3b")
    assert model.config.input[0].dims == [1024]
    assert [list(o.dims) for o in model.config.output] == [
        [32], [32], [2, 151936], [2, 6, 8]]
    assert model.config.max_batch_size == 16
