"""Where the benchmark's data and readers live, and how they are found.

Everything that belongs to one configuration, one traffic mix, one per-layer
metric, one reference or one FLOP count is a file of its own, found by the
name ``BENCHMARK.json`` (or the configuration) gives it.  Adding one never
edits a file that exists.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The run cannot produce a result line; exit non-zero, print none."""


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with everything its run needs."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(root, "BENCHMARK.json")
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if len(rows) != 1:
            raise BenchmarkError(
                f"BENCHMARK.json has no workload named {name!r}")
        self.name = name
        self.chips = int(rows[0]["chips"])
        self.config_name = rows[0]["config"]
        self.traffic_name = rows[0]["traffic"]
        cfg_rows = [c for c in bench["configs"]
                    if c["name"] == self.config_name]
        if len(cfg_rows) != 1:
            raise BenchmarkError(
                f"BENCHMARK.json has no config named {self.config_name!r}")
        self.config_file = os.path.join(root, cfg_rows[0]["file"])
        self.config = load_json(self.config_file)
        self.traffic_file = os.path.join(
            root, bench["paths"][0], "traffic", self.traffic_name + ".json")
        self.traffic = load_json(self.traffic_file)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]

    def declared(self, traced: bool) -> list:
        return self.per_layer if traced else self.end_to_end
