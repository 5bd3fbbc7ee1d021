"""Model repository / registry.

Covers the v2 repository API surface (client side surveyed at reference
http/_client.py:582-707: index, load with config/file override, unload with
dependents).  Two sources of models:

* **Programmatic**: ``register_factory(name, factory)`` — used by the model
  zoo and tests.
* **Directory repository**: Triton-style layout ``<repo>/<model>/config.pbtxt``
  (protobuf text format) + ``<repo>/<model>/1/model.py`` defining
  ``get_model(config) -> Model``.  Load-time file overrides (base64 payloads
  in load parameters) land in a temp dir, mirroring the reference's
  in-request model directory (http/_client.py:620-671).
"""

from __future__ import annotations

import base64
import os
import threading
from typing import Callable, Dict, List, Optional

from google.protobuf import json_format, text_format

from ..protocol import inference_pb2 as pb
from .model import Model
from .types import InferError


class ModelRegistry:
    def __init__(self, repository_path: Optional[str] = None):
        self._factories: Dict[str, Callable[[], Model]] = {}
        self._original_configs: Dict[str, bytes] = {}
        self._models: Dict[str, Model] = {}  # name -> DEFAULT (latest) version
        # name -> {version string -> Model}; programmatic models serve {"1"}
        self._version_sets: Dict[str, Dict[str, Model]] = {}
        self._states: Dict[str, tuple] = {}  # name -> (state, reason)
        # rolling-update staging area: name -> {version -> Model}.  Staged
        # instances are OUTSIDE the version sets — invisible to routing,
        # readiness, statistics, and the index — until promoted, so a cold
        # version can never serve (or report ready) mid-warmup.
        self._staged: Dict[str, Dict[str, Model]] = {}
        # bumped on every load/unload so per-model caches keyed on the name
        # (batchers, inline-execution profiles) can detect a swapped instance
        self._generations: Dict[str, int] = {}
        self._lock = threading.RLock()
        self._repository_path = repository_path
        if repository_path:
            for entry in sorted(os.listdir(repository_path)):
                if os.path.isdir(os.path.join(repository_path, entry)):
                    self._states.setdefault(entry, ("UNAVAILABLE", "unloaded"))

    # -- programmatic registration ----------------------------------------
    def register_factory(
        self, name: str, factory: Callable[[], Model], load_now: bool = True
    ) -> None:
        with self._lock:
            self._factories[name] = factory
            self._states[name] = ("UNAVAILABLE", "unloaded")
            if load_now:
                self.load(name)

    def register_model(self, model: Model) -> None:
        with self._lock:
            self._factories[model.name] = lambda m=model: m
            # The factory returns this same instance, so a load-time config
            # override mutates it; snapshot the registered config so a plain
            # reload restores it (Triton semantics: load re-reads the repo).
            self._original_configs[model.name] = model.config.SerializeToString()
            self._models[model.name] = model
            self._version_sets[model.name] = {"1": model}
            self._states[model.name] = ("READY", "")
            self._generations[model.name] = self._generations.get(model.name, 0) + 1

    # -- v2 repository API --------------------------------------------------
    def load(self, name: str, config_override: Optional[str] = None, files=None) -> None:
        with self._lock:
            try:
                if name in self._factories and not files:
                    model = self._factories[name]()
                    if config_override:
                        model.config = _parse_config_json(config_override, name)
                    elif name in self._original_configs:
                        orig = self._original_configs[name]
                        if model.config.SerializeToString() != orig:
                            cfg = pb.ModelConfig()
                            cfg.ParseFromString(orig)
                            model.config = cfg
                    vset = {"1": model}
                elif self._repository_path or files:
                    model, vset = self._load_from_directory(
                        name, config_override, files)
                else:
                    raise InferError(f"failed to load '{name}': model not found")
            except InferError:
                self._states[name] = ("UNAVAILABLE", "load failed")
                raise
            version_list = sorted(vset, key=int)
            for v, m in vset.items():
                m.served_version = v
                m._version_list = version_list
            self._models[name] = model
            self._version_sets[name] = vset
            self._states[name] = ("READY", "")
            self._generations[name] = self._generations.get(name, 0) + 1
            # a full (re)load supersedes any half-finished rolling
            # update: staged instances are dropped, not leaked
            for m in self._staged.pop(name, {}).values():
                try:
                    m.unload()
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass

    def unload(self, name: str, unload_dependents: bool = False) -> None:
        with self._lock:
            model = self._models.pop(name, None)
            if model is None:
                raise InferError(f"failed to unload '{name}': model is not loaded")
            for m in self._version_sets.pop(name, {"_": model}).values():
                m.unload()
            for m in self._staged.pop(name, {}).values():
                try:
                    m.unload()
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
            self._states[name] = ("UNAVAILABLE", "unloaded")
            self._generations[name] = self._generations.get(name, 0) + 1
            if unload_dependents and model.config.HasField("ensemble_scheduling"):
                for step in model.config.ensemble_scheduling.step:
                    if step.model_name in self._models:
                        self.unload(step.model_name)

    def index(self, ready_only: bool = False) -> List[dict]:
        with self._lock:
            out = []
            for name in sorted(self._states):
                state, reason = self._states[name]
                if ready_only and state != "READY":
                    continue
                versions = sorted(self._version_sets.get(name, {"1": None}),
                                  key=int)
                for v in versions:  # one index row per served version
                    entry = {"name": name, "version": v, "state": state}
                    if reason:
                        entry["reason"] = reason
                    out.append(entry)
            return out

    def get(self, name: str, version: str = "") -> Model:
        with self._lock:
            model = self._models.get(name)
            vset = self._version_sets.get(name)
        if model is None:
            raise InferError(
                f"Request for unknown model: '{name}' is not found", http_status=400
            )
        if version:
            m = (vset or {}).get(version)
            if m is None:
                raise InferError(
                    f"Request for unknown model: '{name}' version {version} is not found",
                    http_status=400,
                )
            return m
        return model  # unversioned -> the policy's latest

    # -- rolling-update staging (server/fleet.py drives these) --------------
    def stage_version(self, name: str, model: Model, version: str) -> None:
        """Park a NEW version instance of a loaded name in the staging
        area: it takes no traffic and reports not-ready until
        :meth:`promote`.  The registry generation does not move — the old
        version's batchers, templates, and caches stay live and serving.
        """
        try:
            int(version)
        except (TypeError, ValueError):
            raise InferError(
                f"cannot stage '{name}' version '{version}': versions "
                "are numeric strings")
        with self._lock:
            if name not in self._models:
                raise InferError(
                    f"cannot stage a version for '{name}': model is not "
                    "loaded")
            vset = self._version_sets.get(name) or {}
            staged = self._staged.setdefault(name, {})
            if version in vset or version in staged:
                raise InferError(
                    f"cannot stage '{name}' version {version}: that "
                    "version is already served or staged")
            model.served_version = version
            staged[version] = model

    def staged_version(self, name: str, version: str) -> Optional[Model]:
        with self._lock:
            return self._staged.get(name, {}).get(version)

    def abort_stage(self, name: str, version: str) -> Optional[Model]:
        """Drop a staged instance (failed warmup / abandoned update)."""
        with self._lock:
            staged = self._staged.get(name)
            model = staged.pop(version, None) if staged else None
            if staged is not None and not staged:
                self._staged.pop(name, None)
            return model

    def promote(self, name: str, version: str) -> Model:
        """THE atomic flip of a rolling update: move the staged instance
        into the served version set AND make it the default (unversioned)
        target, under one lock acquisition.  In-flight requests keep the
        old instance references they already resolved; the old version
        stays served and explicitly addressable."""
        with self._lock:
            staged = self._staged.get(name, {})
            model = staged.pop(version, None)
            if model is None:
                raise InferError(
                    f"no staged version {version} for '{name}' to promote")
            if not staged:
                self._staged.pop(name, None)
            vset = self._version_sets.setdefault(name, {})
            vset[version] = model
            version_list = sorted(vset, key=int)
            for m in vset.values():
                m._version_list = version_list
            self._models[name] = model
            self._states[name] = ("READY", "")
            return model

    def demote(self, name: str, version: str,
               fallback: Optional[str] = None) -> Model:
        """Remove one served version (rolling-update rollback): the
        default returns to ``fallback`` (when still served) or the
        highest remaining version.  Refuses to demote the only version —
        that is an unload, and it should look like one."""
        with self._lock:
            vset = self._version_sets.get(name) or {}
            if version not in vset:
                raise InferError(
                    f"cannot demote '{name}' version {version}: not served")
            if len(vset) == 1:
                raise InferError(
                    f"cannot demote the only served version of '{name}' "
                    "(unload the model instead)")
            model = vset.pop(version)
            version_list = sorted(vset, key=int)
            for m in vset.values():
                m._version_list = version_list
            if fallback is not None and fallback in vset:
                self._models[name] = vset[fallback]
            elif self._models.get(name) is model:
                self._models[name] = vset[version_list[-1]]
            return model

    def generation(self, name: str) -> int:
        """Monotonic per-name counter; changes whenever the served instance
        behind ``name`` is swapped (load/reload/unload)."""
        with self._lock:
            return self._generations.get(name, 0)

    def set_state(self, name: str, state: str, reason: str = "") -> None:
        """Transition a name's repository state (READY / LOADING /
        UNAVAILABLE).  The core holds a name in LOADING while its warmup
        samples run — readiness probes must not route traffic at a model
        that would pay XLA compilation on its first request."""
        with self._lock:
            self._states[name] = (state, reason)

    def get_state(self, name: str):
        """Current (state, reason) of a name ("" state when unknown)."""
        with self._lock:
            return self._states.get(name, ("", ""))

    def any_loading(self) -> bool:
        """True while any model is mid-load/warmup (server readiness gate)."""
        with self._lock:
            return any(s == "LOADING" for s, _ in self._states.values())

    def is_ready(self, name: str, version: str = "") -> bool:
        with self._lock:
            if self._states.get(name, ("", ""))[0] != "READY":
                return False
            model = self._models.get(name)
            vset = self._version_sets.get(name) or {}
        return model is not None and (not version or version in vset)

    def ready_models(self) -> List[Model]:
        """One (default/latest) instance per ready name."""
        with self._lock:
            return list(self._models.values())

    def all_version_models(self, blocking: bool = True) -> List[Model]:
        """Every served version instance (warmup, statistics, metrics —
        surfaces that report or touch each version separately).
        ``blocking=False`` is for a caller that must not wait (the
        collector's hook runs on whichever thread allocated): none where
        another thread holds the lock, as for the length of a load."""
        if not self._lock.acquire(blocking):
            return []
        try:
            return [m for vs in self._version_sets.values()
                    for m in vs.values()]
        finally:
            self._lock.release()

    def version_models(self, name: str) -> List[Model]:
        """Every served version of one name, ascending."""
        with self._lock:
            vset = self._version_sets.get(name)
            if vset:
                return [vset[v] for v in sorted(vset, key=int)]
            m = self._models.get(name)
            return [m] if m is not None else []

    # -- directory loading --------------------------------------------------
    def _load_from_directory(self, name: str, config_override, files) -> Model:
        import importlib.util
        import tempfile

        model_dir = None
        if files:
            # In-request model directory: files like "file:1/model.py" -> b64
            # content (reference cc_client_test.cc:1202-1350 behavior).
            tmp = tempfile.mkdtemp(prefix=f"tc_tpu_model_{name}_")
            for fname, b64 in files.items():
                rel = fname[len("file:"):] if fname.startswith("file:") else fname
                dest = os.path.normpath(os.path.join(tmp, rel))
                # request-controlled names must stay inside the temp dir
                if not dest.startswith(tmp + os.sep):
                    raise InferError(
                        f"failed to load '{name}': invalid file path '{rel}'"
                    )
                os.makedirs(os.path.dirname(dest), exist_ok=True)
                with open(dest, "wb") as f:
                    f.write(base64.b64decode(b64))
            model_dir = tmp
        elif self._repository_path:
            model_dir = os.path.join(self._repository_path, name)
        if model_dir is None or not os.path.isdir(model_dir):
            raise InferError(f"failed to load '{name}': not found in repository")

        if config_override:
            config = _parse_config_json(config_override, name)
        else:
            cfg_path = os.path.join(model_dir, "config.pbtxt")
            if not os.path.exists(cfg_path):
                raise InferError(f"failed to load '{name}': missing config.pbtxt")
            config = pb.ModelConfig()
            with open(cfg_path) as f:
                text_format.Parse(f.read(), config)
            if not config.name:
                config.name = name

        # numbered version directories (Triton layout: <model>/<N>/model.py)
        available = sorted(
            int(d) for d in os.listdir(model_dir)
            if d.isdigit() and os.path.exists(
                os.path.join(model_dir, d, "model.py")))
        if not available:
            raise InferError(f"failed to load '{name}': missing 1/model.py")
        chosen = _apply_version_policy(name, config, available)

        def load_version(v: int) -> Model:
            impl_path = os.path.join(model_dir, str(v), "model.py")
            spec = importlib.util.spec_from_file_location(
                f"tc_tpu_models.{name}.v{v}", impl_path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            if not hasattr(mod, "get_model"):
                raise InferError(
                    f"failed to load '{name}' version {v}: model.py lacks "
                    "get_model(config)")
            cfg_v = pb.ModelConfig()
            cfg_v.CopyFrom(config)  # get_model may mutate its config
            model = mod.get_model(cfg_v)
            # warmup input_data_file samples resolve against <model_dir>/warmup/
            model.model_dir = model_dir
            return model

        vset: Dict[str, Model] = {}
        try:
            for v in chosen:
                vset[str(v)] = load_version(v)
        except Exception:
            # a later version failing must not leak the instances (and any
            # device memory) earlier versions already constructed
            for m in vset.values():
                try:
                    m.unload()
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
            raise
        return vset[str(max(chosen))], vset


def _apply_version_policy(name: str, config: pb.ModelConfig,
                          available: List[int]) -> List[int]:
    """Which of the repository's numbered versions get served
    (``ModelVersionPolicy``: latest{n} default 1 / all / specific{..})."""
    which = config.version_policy.WhichOneof("policy_choice")
    if which == "all":
        return available
    if which == "specific":
        wanted = sorted(int(v) for v in config.version_policy.specific.versions)
        missing = [v for v in wanted if v not in available]
        if missing:
            raise InferError(
                f"failed to load '{name}': version_policy requests "
                f"version(s) {missing} not present in the repository")
        if not wanted:
            raise InferError(
                f"failed to load '{name}': version_policy specific lists "
                "no versions")
        return wanted
    n = (config.version_policy.latest.num_versions
         if which == "latest" else 0) or 1
    return available[-n:]


def _parse_config_json(config_json: str, name: str) -> pb.ModelConfig:
    try:
        cfg = json_format.Parse(config_json, pb.ModelConfig())
        if not cfg.name:
            cfg.name = name
        return cfg
    except Exception as e:
        raise InferError(f"failed to parse config override for '{name}': {e}")
