"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy union, device seconds per program (HLO
module name), device seconds per operation, and the idle gaps named by the
benchmark's own host annotations (``jax.profiler.TraceAnnotation`` names
that start with ``bench.``), which the profiler puts on the same clock.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A TPU
device plane is named ``/device:TPU:<n>`` and carries the lines ``XLA
Modules`` (one event per program execution) and ``XLA Ops`` (one per
operation, control flow enclosing its body). Host threads are lines of the
``/host:CPU`` plane.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """{"devices": {n: {"modules": [(name, start, end)], "ops": [...]}},
    "host": [(name, start, end)]} with times in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(int(m.group(1)), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name = op_name(ev.name) if key == "ops" else ev.name
                    dev[key].append((name, s, s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = ev.start_ns * 1e-9
                        out["host"].append((ev.name, s, s + ev.duration_ns * 1e-9))
    out["host"].sort(key=lambda e: e[1])
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(merged, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def module_name(event_name: str) -> str:
    """``jit_segment_f(1234567)`` -> ``jit_segment_f``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def self_seconds(events, lo: float, hi: float) -> dict:
    """Seconds per operation name inside [lo, hi], each event keeping only
    what the events nested inside it do not cover (a ``while`` encloses its
    body's operations on the same line)."""
    out: dict[str, float] = {}
    stack: list[tuple[str, float]] = []  # (name, end)
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= e - s
        out[name] = out.get(name, 0.0) + e - s
        stack.append((name, e))
    return out


def reduce(trace: dict, step_name: str = "bench.step") -> dict:
    """The numbers the metric readers use. The window is the span from the
    first traced step's start to the last one's end on the host's line."""
    steps = [(s, e) for name, s, e in trace["host"] if name == step_name]
    if not steps:
        raise ValueError(f"no {step_name!r} annotation in the trace")
    lo, hi = steps[0][0], steps[-1][1]
    busy, per_module, per_op, gaps = [], {}, {}, []
    for dev in trace["devices"].values():
        events = dev["ops"] or dev["modules"]
        merged = union((s, e) for _, s, e in events if e > lo and s < hi)
        busy.append(covered(merged, lo, hi))
        for name, s, e in dev["modules"]:
            if e > lo and s < hi:
                key = module_name(name)
                per_module[key] = per_module.get(key, 0.0) + min(e, hi) - max(s, lo)
        for name, secs in self_seconds(dev["ops"], lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + secs
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n_dev = max(len(trace["devices"]), 1)
    inner = [ev for ev in trace["host"] if ev[0] != step_name]

    def doing(a, b):
        mid = 0.5 * (a + b)
        names = [n for n, s, e in inner if s <= mid <= e]
        if names:
            return names[-1]
        return step_name if any(s <= mid <= e for s, e in steps) else "between steps"

    by_host: dict[str, float] = {}
    for a, b in gaps:
        key = doing(a, b)
        by_host[key] = by_host.get(key, 0.0) + (b - a) / n_dev
    first = next(iter(trace["devices"].values()), {"ops": [], "modules": []})
    merged0 = union((s, e) for _, s, e in (first["ops"] or first["modules"]))
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / n_dev,
        "steps": [
            {"wall_s": e - s, "busy_s": covered(merged0, s, e)} for s, e in steps
        ],
        "module_s": {k: v / n_dev for k, v in per_module.items()},
        "op_s": {k: v / n_dev for k, v in per_op.items()},
        "gap_s": by_host,
    }


def program_seconds(reduced: dict | None, names: list[str]) -> float | None:
    """Device seconds of the programs (HLO modules) ``names`` in a reduced
    trace; ``None`` where there is no trace or none of them ran."""
    if not reduced:
        return None
    return sum(v for k, v in reduced["module_s"].items() if k in names) or None


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
