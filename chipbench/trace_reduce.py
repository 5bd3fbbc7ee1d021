"""From a profiler trace to device busy time, its window and a breakdown.

The window is the span of the ``chipbench_window`` annotation the harness
holds open on a host thread while it traces, so it is on the trace's own
clock.  Busy time is the union of the intervals of the device's op line
(``XLA Ops``), clipped to that window, so 0 <= busy <= window holds by
arithmetic whatever nests or overlaps in the trace.  Over several chips the
busy time is averaged.  The pure functions take plain ``(name, start, end)``
tuples in nanoseconds so that tests need no trace file.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_NAME = "chipbench_window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """``%fusion.1 = bf16[32,384]{...} fusion(...)`` -> ``fusion.1
    bf16[32,384]``: an op's name and the shape it writes, on one line."""
    m = _HLO.match(name)
    text = f"{m.group(1)} {m.group(2)}" if m else name
    return " ".join(text.split())[:96]


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


def clip(events, lo, hi):
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    out = []
    for name, start, end in events:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            out.append((name, start, end))
    return out


def union(events):
    """Disjoint, sorted ``(start, end)`` covering the events' union."""
    merged = []
    for _, start, end in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def self_times(events):
    """Total self time by name: each event's span less what the events
    nested inside it cover (a ``while`` op holds its body's ops)."""
    totals = {}
    stack = []  # (name, end, covered_by_children), parents first

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, covered = stack.pop()
            totals[name] = totals.get(name, 0) + (end - start) - covered
            if stack:
                stack[-1][3] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and end > stack[-1][1]:
            end = stack[-1][1]  # a child never outlasts its parent
        if end > start:
            stack.append([name, end, start, 0])
    close(float("inf"))
    return totals


def reduce(device_lines: dict, window, module_lines: dict = None) -> dict:
    """``device_lines`` maps a device plane's name to its op line's events;
    ``window`` is ``(lo, hi)`` in the same nanoseconds.  Returns
    ``busy_s``, ``window_s`` and ``breakdown``."""
    lo, hi = window
    if not hi > lo:
        raise TraceError(f"the traced window is empty: {window}")
    if not device_lines:
        raise TraceError("the trace holds no device plane with an op line")
    busy_ns = []
    ops = {}
    gaps = {}
    for plane, events in sorted(device_lines.items()):
        inside = clip(events, lo, hi)
        if not inside:
            raise TraceError(
                f"no device operation of {plane} ran inside the window")
        spans = union(inside)
        busy_ns.append(sum(e - s for s, e in spans))
        for name, ns in self_times(inside).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0) + ns
        modules = sorted(clip((module_lines or {}).get(plane, []), lo, hi),
                         key=lambda e: e[1])
        edges = [lo] + [t for span in spans for t in span] + [hi]
        for gap_lo, gap_hi in zip(edges[0::2], edges[1::2]):
            if gap_hi > gap_lo:
                after = next((m[0] for m in modules if m[1] >= gap_hi - 1000),
                             "the window's end")
                key = f"device idle before {after}"
                gaps[key] = gaps.get(key, 0) + (gap_hi - gap_lo)
    chips = len(busy_ns)
    busy_s = sum(busy_ns) / chips / 1e9
    window_s = (hi - lo) / 1e9
    if not 0 < busy_s <= window_s:
        raise TraceError(f"busy_s {busy_s} outside (0, window_s {window_s}]")

    def top(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:10]
        return [[name, ns / chips / 1e9] for name, ns in rows]

    return {"busy_s": busy_s, "window_s": window_s,
            "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)}}


def read_xplane(trace_dir: str):
    """``(device op lines, module lines, window)`` of the one trace under
    ``trace_dir``, read with ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise TraceError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    op_lines, module_lines, windows, seen = {}, {}, [], []
    for plane in data.planes:
        for line in plane.lines:
            seen.append(f"{plane.name}|{line.name}")
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                if line.name in (OP_LINE, MODULE_LINE):
                    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
                    (op_lines if line.name == OP_LINE
                     else module_lines)[plane.name] = events
            elif not plane.name.startswith("/device:"):
                windows += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name == WINDOW_NAME]
    if len(windows) != 1:
        raise TraceError(f"expected one {WINDOW_NAME} annotation in the "
                         f"trace, found {len(windows)}; lines: {seen[:40]}")
    if not op_lines:
        raise TraceError(f"no '{OP_LINE}' line on a {DEVICE_PLANE_PREFIX}* "
                         f"plane; lines: {seen[:40]}")
    return op_lines, module_lines, windows[0]


def reduce_trace_dir(trace_dir: str) -> dict:
    op_lines, module_lines, window = read_xplane(trace_dir)
    return reduce(op_lines, window, module_lines)
