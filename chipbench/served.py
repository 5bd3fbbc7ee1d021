"""The system under test, brought up in this process: the one that owns the
chip.  ``ServerHarness`` (real gRPC and HTTP frontends on free ports,
``InferenceCore``, the dynamic batcher) with a registry that holds only the
configuration's model.  This is the only module of the benchmark that
imports the program's server.
"""

from __future__ import annotations

import importlib
import os

import numpy as np

from .files import BenchmarkError, load_module

WARM_SEED = 0x3A11


class Served:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.served = cfg["served"]
        self.harness = None

    def start(self, extra_env: dict = None):
        for key, value in {**self.served.get("env", {}),
                           **(extra_env or {})}.items():
            os.environ[key] = value
        from triton_client_tpu.server.registry import ModelRegistry
        from triton_client_tpu.server.testing import ServerHarness

        module_name, _, attr = self.served["factory"].partition(":")
        factory = getattr(importlib.import_module(module_name), attr)
        registry = ModelRegistry()
        registry.register_model(factory())
        self.harness = ServerHarness(registry).start()
        return self

    @property
    def grpc_url(self) -> str:
        return self.harness.grpc_url

    def warm(self, batches) -> None:
        """Every shape the window will use, through the served path, twice:
        the first compiles (or reads the cache), the second must be
        steady.  A client-made batch of ``b`` rows reaches the model as the
        same ``[b, ...]`` program a coalesced batch of ``b`` callers does."""
        import triton_client_tpu.grpc as grpcclient

        served = self.served
        make = load_module("request_makers", served["requests"]).make
        with grpcclient.InferenceServerClient(self.grpc_url) as client:
            for b in batches:
                tensors = make(self.cfg, WARM_SEED, b, b)
                inputs = []
                for spec in served["inputs"]:
                    x = tensors[spec["name"]]
                    inp = grpcclient.InferInput(spec["name"], list(x.shape),
                                                spec["datatype"])
                    inp.set_data_from_numpy(x)
                    inputs.append(inp)
                for _ in range(2):
                    result = client.infer(served["model"], inputs)
                    for spec in served["outputs"]:
                        out = result.as_numpy(spec["name"])
                        if out is None or out.shape[0] != b \
                                or not np.isfinite(out).all():
                            raise BenchmarkError(
                                f"warm-up at batch {b} gave no finite "
                                f"{spec['name']}")

    def statistics(self) -> dict:
        """The model's v2 statistics, flattened: counts and server-clock
        nanosecond sums at the batcher and the step."""
        rows = self.harness.core.statistics(self.served["model"])
        if len(rows) != 1:
            raise BenchmarkError("expected one version of "
                                 f"{self.served['model']}, got {len(rows)}")
        row = rows[0]
        flat = {"inference_count": row["inference_count"],
                "execution_count": row["execution_count"]}
        for name, entry in row["inference_stats"].items():
            flat[f"{name}.count"] = entry["count"]
            flat[f"{name}.ns"] = entry["ns"]
        return flat

    def stop(self) -> None:
        if self.harness is not None:
            self.harness.stop()
            self.harness = None
