"""The one place a result line is built, and the check it has to pass.

``build`` returns the last line of standard output or raises: it never
returns a line that ``validate`` would refuse.  The shape is the contract's:
``correct``, ``attempted``, ``failed``, ``metrics`` (every metric the cell
declares for this mode, each with ``value`` and ``unit``), ``device``
(``platform``, ``kind``, ``count``, ``memory_peak_bytes`` and, traced,
``window_s`` and ``busy_s`` with 0 < busy_s <= window_s), optionally
``breakdown``, and last ``compared``: each number behind ``correct`` beside
its limit.
"""

from __future__ import annotations

import json
import math
import re

_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_KEYS = ("correct", "attempted", "failed", "metrics", "device", "breakdown",
         "compared")
_DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
_TRACE_KEYS = ("window_s", "busy_s")


class ResultLineError(Exception):
    """The run's numbers cannot make a valid result line."""


def _number(x, what: str) -> float:
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise ResultLineError(f"{what} is not a number: {x!r}")
    if not math.isfinite(x):
        raise ResultLineError(f"{what} is not finite: {x!r}")
    return x


def _count(x, what: str) -> int:
    if isinstance(x, bool) or not isinstance(x, int) or x < 0:
        raise ResultLineError(f"{what} is not a count: {x!r}")
    return x


def validate(line: str, declared: list, traced: bool,
             platform: str = "tpu") -> dict:
    """Raise ``ResultLineError`` unless ``line`` is a result line for a cell
    that declares ``declared`` (entries of ``BENCHMARK.json`` with ``name``
    and ``unit``) in this mode.  Returns the parsed object."""
    if "\n" in line:
        raise ResultLineError("the result is not one line")
    try:
        obj = json.loads(line)
    except ValueError as e:
        raise ResultLineError(f"not JSON: {e}") from None
    if not isinstance(obj, dict):
        raise ResultLineError("not a JSON object")
    extra = [k for k in obj if k not in _KEYS]
    if extra:
        raise ResultLineError(f"keys beyond the contract's: {extra}")
    for key in ("correct", "attempted", "failed", "metrics", "device",
                "compared"):
        if key not in obj:
            raise ResultLineError(f"key {key!r} is missing")
    if list(obj)[-1] != "compared":
        raise ResultLineError("'compared' does not come last")
    if not isinstance(obj["correct"], bool):
        raise ResultLineError("'correct' is not true or false")
    _count(obj["attempted"], "attempted")
    _count(obj["failed"], "failed")
    if obj["failed"] > obj["attempted"]:
        raise ResultLineError("more requests failed than were attempted")

    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        raise ResultLineError("'metrics' is not an object")
    want = {m["name"]: m["unit"] for m in declared}
    missing = sorted(set(want) - set(metrics))
    if missing:
        raise ResultLineError(f"metrics missing from the line: {missing}")
    undeclared = sorted(set(metrics) - set(want))
    if undeclared:
        raise ResultLineError(f"metrics the cell does not declare: {undeclared}")
    for name, entry in metrics.items():
        if not isinstance(entry, dict) or set(entry) != {"value", "unit"}:
            raise ResultLineError(f"metric {name}: not {{value, unit}}")
        value = _number(entry["value"], f"metric {name}")
        unit = entry["unit"]
        if not isinstance(unit, str) or not _UNIT.match(unit):
            raise ResultLineError(f"metric {name}: bad unit {unit!r}")
        if unit != want[name]:
            raise ResultLineError(
                f"metric {name}: unit {unit!r}, declared {want[name]!r}")
        share = (name.endswith("_roofline")
                 or "mfu" in re.split(r"[._\-]", name))
        if share and not 0 < value <= 100:
            raise ResultLineError(
                f"metric {name}: a share of a peak reads {value}")

    device = obj["device"]
    if not isinstance(device, dict):
        raise ResultLineError("'device' is not an object")
    allowed = _DEVICE_KEYS + (_TRACE_KEYS if traced else ())
    if sorted(device) != sorted(allowed):
        raise ResultLineError(
            f"'device' has keys {sorted(device)}, wants {sorted(allowed)}")
    if device["platform"] != platform:
        raise ResultLineError(
            f"platform is {device['platform']!r}, not {platform!r}")
    if not isinstance(device["kind"], str) or not device["kind"]:
        raise ResultLineError("device kind is empty")
    if _count(device["count"], "device count") < 1:
        raise ResultLineError("device count is 0")
    if _count(device["memory_peak_bytes"], "memory_peak_bytes") < 1:
        raise ResultLineError("memory_peak_bytes is 0")
    if traced:
        window = _number(device["window_s"], "window_s")
        busy = _number(device["busy_s"], "busy_s")
        if not window > 0:
            raise ResultLineError(f"window_s is {window}")
        if not 0 < busy <= window:
            raise ResultLineError(
                f"busy_s {busy} is not above 0 and at most window_s {window}")

    if "breakdown" in obj:
        bd = obj["breakdown"]
        if not isinstance(bd, dict) or sorted(bd) != ["device_ops",
                                                      "idle_gaps"]:
            raise ResultLineError("'breakdown' is not {device_ops, idle_gaps}")
        for key, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                raise ResultLineError(f"breakdown.{key}: over 10 entries")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str)):
                    raise ResultLineError(f"breakdown.{key}: bad row {row!r}")
                _number(row[1], f"breakdown.{key}")

    compared = obj["compared"]
    if not isinstance(compared, dict) or not compared:
        raise ResultLineError("'compared' holds no number")
    for name, entry in compared.items():
        if not isinstance(entry, dict) or set(entry) != {"value", "limit"}:
            raise ResultLineError(f"compared {name}: not {{value, limit}}")
        if entry["value"] is not None:
            _number(entry["value"], f"compared {name}")
        _number(entry["limit"], f"compared {name} limit")
    return obj


def build(declared: list, values: dict, *, correct: bool, attempted: int,
          failed: int, device: dict, traced: bool, compared: dict,
          breakdown: dict = None, platform: str = "tpu") -> str:
    """The result line for these numbers, validated; raises otherwise.

    ``values`` maps metric names to numbers (or None where a reader found
    nothing: that is an error for a declared metric, never a 0)."""
    metrics = {}
    for m in declared:
        value = values.get(m["name"])
        if value is None:
            raise ResultLineError(
                f"metric {m['name']} has no value in this run")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    obj = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        obj["breakdown"] = breakdown
    obj["compared"] = compared
    line = json.dumps(obj, allow_nan=False)
    validate(line, declared, traced, platform)
    return line
