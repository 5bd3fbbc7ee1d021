"""Test-fixture model zoo.

Recreates the live reference models that the reference's examples and tests
assume exist on the server (SURVEY.md §4 fixture summary: `simple`,
`simple_identity` (BYTES), `simple_sequence`, `repeat_int32` decoupled,
`custom_identity_int32`, ...), as trivial JAX functions — the TPU translation
of the reference's ONNX/custom-backend fixtures.

Behavioral specs come from the examples (SURVEY.md §2.7):

* ``simple`` — 2×INT32[1,16] in → OUTPUT0=sum, OUTPUT1=diff
  (simple_http_infer_client.py).
* ``simple_identity`` — BYTES[−1] passthrough (string clients).
* ``simple_dyna_sequence`` / ``simple_sequence`` — stateful accumulator keyed
  by sequence id; control flags start/end
  (simple_grpc_sequence_stream_infer_client.py:58-79).
* ``repeat_int32`` — decoupled: N responses per request (custom_repeat).
* ``square_int32`` — decoupled: value → value responses of that value.
* ``custom_identity_int32`` — passthrough, used by timeout tests.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Any, Dict, Iterator

import numpy as np

from ..server.model import EnsembleModel, JaxModel, Model, PyModel, make_config
from ..server.registry import ModelRegistry


def make_simple() -> JaxModel:
    import jax.numpy as jnp

    cfg = make_config(
        "simple",
        inputs=[("INPUT0", "INT32", [1, 16]), ("INPUT1", "INT32", [1, 16])],
        outputs=[("OUTPUT0", "INT32", [1, 16]), ("OUTPUT1", "INT32", [1, 16])],
        # the reference `simple` is a CPU ONNX model; host placement keeps
        # the protocol path off the per-request host<->device transfer.
        # Committed device inputs (xla shm) still run on the accelerator.
        instance_kind="KIND_CPU",
    )

    def fn(INPUT0, INPUT1):
        # wire-path requests arrive as plain numpy: int32 add/sub in numpy
        # is ~2 us where the jitted-jax dispatch costs ~100 us under the
        # serving loop's GIL contention (benchmarks/HOTPATH_PROFILE.md) —
        # this model IS the headline protocol benchmark, so the protocol
        # path must not pay accelerator-dispatch overhead for host math.
        # Device-resident inputs (zero-copy xla-shm) keep the jax path and
        # its device semantics.
        if type(INPUT0) is np.ndarray and type(INPUT1) is np.ndarray:
            return {"OUTPUT0": INPUT0 + INPUT1, "OUTPUT1": INPUT0 - INPUT1}
        return {"OUTPUT0": jnp.add(INPUT0, INPUT1),
                "OUTPUT1": jnp.subtract(INPUT0, INPUT1)}

    # jit=False: the numpy/jax branch is a host-side type dispatch (a jit
    # trace would bake the jax branch in), and two eager element-wise ops
    # need no fusion
    return JaxModel(cfg, fn, jit=False, analyzable=True)


def make_simple_string() -> PyModel:
    """Element-wise sum/diff over decimal-string tensors (the reference's
    ``simple_string`` fixture, driven by grpc_explicit_byte_content_client.py:61-87
    and simple_http_shm_string_client.py:78-104): BYTES in, BYTES out,
    arithmetic on the parsed integers."""
    cfg = make_config(
        "simple_string",
        inputs=[("INPUT0", "BYTES", [1, 16]), ("INPUT1", "BYTES", [1, 16])],
        outputs=[("OUTPUT0", "BYTES", [1, 16]), ("OUTPUT1", "BYTES", [1, 16])],
    )

    def _ints(arr):
        flat = np.asarray(arr, dtype=object).reshape(-1)
        return np.array(
            [int(v.decode() if isinstance(v, bytes) else v) for v in flat])

    def fn(inputs, params):
        shape = np.asarray(inputs["INPUT0"], dtype=object).shape

        def enc(vals):
            return np.array(
                [str(int(v)).encode() for v in vals], dtype=object
            ).reshape(shape)

        a, b = _ints(inputs["INPUT0"]), _ints(inputs["INPUT1"])
        return {"OUTPUT0": enc(a + b), "OUTPUT1": enc(a - b)}

    return PyModel(cfg, fn)


def make_simple_int8() -> JaxModel:
    """INT8 sum/diff (the reference's ``simple_int8`` fixture, driven by
    grpc_explicit_int8_content_client.py:59-87)."""
    import jax.numpy as jnp

    cfg = make_config(
        "simple_int8",
        inputs=[("INPUT0", "INT8", [1, 16]), ("INPUT1", "INT8", [1, 16])],
        outputs=[("OUTPUT0", "INT8", [1, 16]), ("OUTPUT1", "INT8", [1, 16])],
        instance_kind="KIND_CPU",
    )

    def fn(INPUT0, INPUT1):
        return {"OUTPUT0": jnp.add(INPUT0, INPUT1),
                "OUTPUT1": jnp.subtract(INPUT0, INPUT1)}

    return JaxModel(cfg, fn)


def make_simple_identity() -> PyModel:
    cfg = make_config(
        "simple_identity",
        inputs=[("INPUT0", "BYTES", [-1])],
        outputs=[("OUTPUT0", "BYTES", [-1])],
        max_batch_size=8,
    )

    def fn(inputs, params):
        return {"OUTPUT0": inputs["INPUT0"]}

    return PyModel(cfg, fn)


def make_custom_identity_int32() -> PyModel:
    """Passthrough with an optional request-controlled execution delay —
    the reference's client_timeout_test.cc drives every API against
    custom_identity_int32 with a server-side delay; here the delay comes in
    as the ``execute_delay_ms`` request parameter."""
    cfg = make_config(
        "custom_identity_int32",
        inputs=[("INPUT0", "INT32", [-1])],
        outputs=[("OUTPUT0", "INT32", [-1])],
        max_batch_size=8,
    )

    def fn(inputs, params):
        delay = params.get("execute_delay_ms", 0)
        try:
            delay_s = float(delay) / 1e3
        except (TypeError, ValueError):
            delay_s = 0.0
        if delay_s > 0:
            _time.sleep(min(delay_s, 30.0))
        return {"OUTPUT0": inputs["INPUT0"]}

    return PyModel(cfg, fn)


def make_identity_fp32() -> JaxModel:
    cfg = make_config(
        "identity_fp32",
        inputs=[("INPUT0", "FP32", [-1])],
        outputs=[("OUTPUT0", "FP32", [-1])],
        max_batch_size=64,
        instance_kind="KIND_CPU",
    )

    def fn(INPUT0):
        return {"OUTPUT0": INPUT0}

    return JaxModel(cfg, fn)


def make_identity_bf16() -> JaxModel:
    cfg = make_config(
        "identity_bf16",
        inputs=[("INPUT0", "BF16", [-1])],
        outputs=[("OUTPUT0", "BF16", [-1])],
        max_batch_size=64,
        instance_kind="KIND_CPU",
    )

    def fn(INPUT0):
        return {"OUTPUT0": INPUT0}

    return JaxModel(cfg, fn)


class SequenceModel(Model):
    """Stateful per-sequence accumulator.

    Matches the reference `simple_sequence` behavior spec: each request
    carries one INT32[1] value; OUTPUT is the running accumulation for that
    sequence id; `sequence_start` resets state, `sequence_end` finalizes it.
    Sequence ids may be int64 or string (reference FLAGS.dyna handling,
    simple_grpc_sequence_stream_infer_client.py:132-153)."""

    def __init__(self, name: str = "simple_sequence"):
        cfg = make_config(
            name,
            inputs=[("INPUT", "INT32", [1])],
            outputs=[("OUTPUT", "INT32", [1])],
            sequence_batching=True,
        )
        super().__init__(cfg)
        self._state: Dict[Any, int] = {}
        self._touched: Dict[Any, float] = {}
        self._idle_s = (
            cfg.sequence_batching.max_sequence_idle_microseconds / 1e6)
        self._lock = threading.Lock()

    def _evict_idle_locked(self, now: float) -> None:
        # Sequences whose client died mid-stream never send sequence_end;
        # without eviction the state dict grows without bound (Triton's
        # max_sequence_idle_microseconds semantics).
        stale = [k for k, t in self._touched.items()
                 if now - t > self._idle_s]
        for k in stale:
            self._state.pop(k, None)
            self._touched.pop(k, None)

    def execute(self, inputs, parameters):
        seq_id = parameters.get("sequence_id", 0)
        start = bool(parameters.get("sequence_start", False))
        end = bool(parameters.get("sequence_end", False))
        if not seq_id:
            from ..server.types import InferError

            raise InferError(
                f"inference request to model '{self.name}' must specify a "
                "non-zero or non-empty correlation ID"
            )
        value = int(np.asarray(inputs["INPUT"]).reshape(-1)[0])
        now = _time.monotonic()
        with self._lock:
            self._evict_idle_locked(now)
            if start or seq_id not in self._state:
                self._state[seq_id] = 0
            self._state[seq_id] += value
            acc = self._state[seq_id]
            if end:
                del self._state[seq_id]
                self._touched.pop(seq_id, None)
            else:
                self._touched[seq_id] = now
        return {"OUTPUT": np.array([acc], dtype=np.int32).reshape(1)}


class DynaSequenceModel(SequenceModel):
    """`simple_dyna_sequence` twist: like the reference custom backend, adds
    the (hash of the) correlation id on start so tests can distinguish
    sequences (behavior spec from simple_grpc_sequence_stream_infer_client.py
    expectations)."""

    def __init__(self):
        super().__init__("simple_dyna_sequence")

    def execute(self, inputs, parameters):
        seq_id = parameters.get("sequence_id", 0)
        start = bool(parameters.get("sequence_start", False))
        if start and seq_id:
            # seed the accumulator with a correlation-id-derived constant so
            # every response in the sequence carries it (distinguishes
            # interleaved sequences, as the reference backend does); wrap
            # uint64 correlation ids into int32 range deliberately
            corr = (hash(str(seq_id)) % 1000) if isinstance(seq_id, str) else int(seq_id)
            with self._lock:
                self._state[seq_id] = int(np.int64(corr).astype(np.int32))
                self._touched[seq_id] = _time.monotonic()
            parameters = dict(parameters)
            parameters["sequence_start"] = False
        return super().execute(inputs, parameters)


def make_repeat_int32() -> PyModel:
    """Decoupled: IN[n] values, DELAY[n] (us), WAIT scalar — emits one
    response per value (reference repeat backend driven by
    simple_grpc_custom_repeat.py)."""
    cfg = make_config(
        "repeat_int32",
        inputs=[("IN", "INT32", [-1]), ("DELAY", "UINT32", [-1]), ("WAIT", "UINT32", [1])],
        outputs=[("OUT", "INT32", [1]), ("IDX", "UINT32", [1])],
        decoupled=True,
    )

    def gen(inputs, params) -> Iterator[Dict[str, np.ndarray]]:
        import time

        values = np.asarray(inputs["IN"]).reshape(-1)
        delays = np.asarray(inputs.get("DELAY", np.zeros_like(values))).reshape(-1)
        wait = int(np.asarray(inputs.get("WAIT", [0])).reshape(-1)[0])
        for i, v in enumerate(values):
            if i < len(delays):
                time.sleep(int(delays[i]) / 1e6)
            yield {
                "OUT": np.array([v], dtype=np.int32),
                "IDX": np.array([i], dtype=np.uint32),
            }
        if wait:
            time.sleep(wait / 1e6)

    return PyModel(cfg, fn=None, decoupled_fn=gen)


def make_square_int32() -> PyModel:
    """Decoupled: scalar IN → IN responses each carrying IN (reference
    square backend / decoupled test model)."""
    cfg = make_config(
        "square_int32",
        inputs=[("IN", "INT32", [1])],
        outputs=[("OUT", "INT32", [1])],
        decoupled=True,
    )

    def gen(inputs, params):
        n = int(np.asarray(inputs["IN"]).reshape(-1)[0])
        for _ in range(max(n, 0)):
            yield {"OUT": np.array([n], dtype=np.int32)}

    return PyModel(cfg, fn=None, decoupled_fn=gen)


def make_dense_tpu() -> JaxModel:
    """TPU-resident batched MLP for device-path benchmarking: bf16 matmuls
    (MXU-shaped), dynamic batching so concurrent requests coalesce into one
    device execute (BASELINE config #4 dynamic-batching contract)."""
    D = 512
    cfg = make_config(
        "dense_tpu",
        inputs=[("INPUT", "FP32", [D])],
        outputs=[("OUTPUT", "FP32", [D])],
        max_batch_size=64,
        preferred_batch_sizes=[8, 16, 32, 64],
        max_queue_delay_us=2000,
        instance_kind="KIND_TPU",
        # two matmuls (D->2D->D): 2*D*2D + 2*2D*D = 8*D^2 FLOPs/element —
        # the nv_tpu_live_mfu numerator
        parameters={"flops_per_inference": str(8 * D * D)},
    )
    state = {}

    def fn(INPUT):
        import jax
        import jax.numpy as jnp

        if "run" not in state:  # lazy: no device work until first request
            k1, k2 = jax.random.split(jax.random.PRNGKey(0))
            w1 = jax.random.normal(k1, (D, 2 * D), jnp.bfloat16) * 0.05
            w2 = jax.random.normal(k2, (2 * D, D), jnp.bfloat16) * 0.05

            @jax.jit
            def run(x):
                h = jax.nn.relu(jnp.dot(x.astype(jnp.bfloat16), w1))
                return jnp.dot(h, w2).astype(jnp.float32)

            state["run"] = run
        return {"OUTPUT": state["run"](INPUT)}

    return JaxModel(cfg, fn, jit=False, analyzable=True)


def make_simple_cnn() -> JaxModel:
    """Tiny image classifier backing image_client.py (the behavioral stand-in
    for the reference's inception/densenet ONNX models, SURVEY.md §2.7):
    FP32 CHW [3,224,224] -> [1000] scores, with classification labels so
    ``class_count`` outputs exercise the "score:index:label" path."""
    labels = [f"class_{i}" for i in range(1000)]
    cfg = make_config(
        "simple_cnn",
        inputs=[("INPUT", "FP32", [3, 224, 224])],
        outputs=[("OUTPUT", "FP32", [1000])],
        max_batch_size=8,
        instance_kind="KIND_CPU",
        labels={"OUTPUT": labels},
    )
    state: Dict[str, Any] = {}

    def fn(INPUT):
        import jax
        import jax.numpy as jnp

        if "run" not in state:
            k1, k2 = jax.random.split(jax.random.PRNGKey(7))
            conv_w = jax.random.normal(k1, (8, 3, 4, 4), jnp.float32) * 0.1
            dense_w = jax.random.normal(k2, (8 * 14 * 14, 1000), jnp.float32) * 0.02

            @jax.jit
            def run(x):
                y = jax.lax.conv_general_dilated(
                    x, conv_w, window_strides=(4, 4), padding="VALID")
                y = jax.nn.relu(y)
                y = jax.lax.reduce_window(
                    y, -jnp.inf, jax.lax.max, (1, 1, 4, 4), (1, 1, 4, 4), "VALID")
                y = y.reshape(y.shape[0], -1)
                return jnp.dot(y, dense_w)

            state["run"] = run
        return {"OUTPUT": state["run"](INPUT)}

    return JaxModel(cfg, fn, jit=False, analyzable=True,
                    output_labels={"OUTPUT": labels})


def make_ensemble_scale_sum() -> Model:
    """Ensemble DAG fixture (reference behavioral spec:
    ensemble_image_client.py — preprocess -> model -> postprocess):
    scale_by_two(INPUT0) -> simple(sum/diff with INPUT1) -> outputs."""
    cfg = make_config(
        "ensemble_scale_sum",
        inputs=[("RAW0", "INT32", [1, 16]), ("RAW1", "INT32", [1, 16])],
        outputs=[("SUM", "INT32", [1, 16]), ("DIFF", "INT32", [1, 16])],
        platform="ensemble",
        backend="",
    )
    step = cfg.ensemble_scheduling.step.add()
    step.model_name = "scale_by_two"
    step.input_map["INPUT"] = "RAW0"
    step.output_map["OUTPUT"] = "scaled0"
    step = cfg.ensemble_scheduling.step.add()
    step.model_name = "simple"
    step.input_map["INPUT0"] = "scaled0"
    step.input_map["INPUT1"] = "RAW1"
    step.output_map["OUTPUT0"] = "SUM"
    step.output_map["OUTPUT1"] = "DIFF"
    return EnsembleModel(cfg)


def make_scale_by_two() -> JaxModel:
    cfg = make_config(
        "scale_by_two",
        inputs=[("INPUT", "INT32", [1, 16])],
        outputs=[("OUTPUT", "INT32", [1, 16])],
        instance_kind="KIND_CPU",
    )
    import jax.numpy as jnp

    def fn(INPUT):
        return {"OUTPUT": jnp.multiply(INPUT, 2)}

    return JaxModel(cfg, fn)


def register_all(registry: ModelRegistry) -> None:
    from . import language, vision

    registry.register_model(make_simple())
    registry.register_model(vision.make_resnet50())
    registry.register_model(language.make_bert_large())
    registry.register_model(language.make_llama_preprocess())
    registry.register_model(language.make_llama_tpu())
    registry.register_model(language.make_llama_postprocess())
    registry.register_model(language.make_ensemble_llama())
    registry.register_model(language.make_longctx_tpu())
    registry.register_model(language.make_moe_tpu())
    registry.register_model(language.make_kimi_k2())
    registry.register_model(language.make_sdar_30b_a3b())
    registry.register_model(language.make_ouro_2_6b())
    registry.register_model(language.make_lfm2_8b_a1b())
    registry.register_model(language.make_hy4_preview())
    registry.register_model(language.make_trinity_mini())
    from .decode import DecodeModel, make_llama_generate

    decode = DecodeModel()
    registry.register_model(decode.model)
    registry.register_model(make_llama_generate(decode))
    registry.register_model(make_simple_string())
    registry.register_model(make_simple_int8())
    registry.register_model(make_simple_identity())
    registry.register_model(make_custom_identity_int32())
    registry.register_model(make_identity_fp32())
    registry.register_model(make_identity_bf16())
    registry.register_model(SequenceModel())
    registry.register_model(DynaSequenceModel())
    registry.register_model(make_repeat_int32())
    registry.register_model(make_square_int32())
    registry.register_model(make_dense_tpu())
    registry.register_model(make_simple_cnn())
    registry.register_model(make_scale_by_two())
    registry.register_model(make_ensemble_scale_sum())
