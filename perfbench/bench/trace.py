"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``. A device is a plane named
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per execution
of a compiled program, named after the jitted function (``jit_svd_halko``,
``jit__fused_scan``...) with an id in parentheses, and its ``XLA Ops`` line
one event per operation. Busy time is the union of the operations'
intervals; a layer's device time is the summed duration of the module
executions whose names start with one of the layer's prefixes
(``perfbench/layers/*.json``). Host threads are the lines of ``/host:CPU``;
an idle gap of the device is named by the host event that overlaps it most.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
TOP = 10


def module_name(event_name: str) -> str:
    """``jit__fused_scan(1234)`` -> ``jit__fused_scan``."""
    return event_name.split("(", 1)[0].strip()


def union_length(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of [start, end) intervals, and the union."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def reduce_trace(path: str, layers: dict[str, list[str]]) -> dict:
    """Device metrics of the trace at ``path``, averaged over its devices:
    ``busy_s``, ``layer_ms`` (layer key -> device ms), ``module_s``
    (module -> device s), ``gaps`` (the longest idle gaps, named by host
    activity), ``span_s`` (first to last device event), ``devices``."""
    import gzip

    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    devices, host_lines = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append({ln.name: list(_events(ln)) for ln in plane.lines})
        elif plane.name == "/host:CPU":
            host_lines.extend(list(_events(ln)) for ln in plane.lines)
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane; the trace saw no chip")
    busy = 0.0
    layer_ms: dict[str, float] = defaultdict(float)
    module_s: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float, float]] = []
    lo, hi = float("inf"), 0.0
    for lines in devices:
        ops = lines.get(OPS) or lines.get(MODULES) or []
        length, merged = union_length([(s, s + d) for _, s, d in ops])
        busy += length
        if merged:
            lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        gaps.extend((b - a, a, b) for (_, a), (b, _) in zip(merged, merged[1:]))
        for name, _, d in lines.get(MODULES, []):
            mod = module_name(name)
            module_s[mod] += d * 1e-9
            for key, prefixes in layers.items():
                if any(mod.startswith(p) for p in prefixes):
                    layer_ms[key] += d * 1e-6
    n = len(devices)
    gaps.sort(reverse=True)
    return {
        "devices": n,
        "busy_s": busy * 1e-9 / n,
        "span_s": max(0.0, hi - lo) * 1e-9,
        "layer_ms": {k: v / n for k, v in layer_ms.items()},
        "module_s": {k: v / n for k, v in module_s.items()},
        "gaps": [(name_gap(host_lines, a, b), g * 1e-9) for g, a, b in gaps[:TOP]],
    }


WAITING = ("sleep", "wait", "acquire", "Condition", "select", "poll(")


def name_gap(host_lines, a: float, b: float) -> str:
    """What the host was doing while the device idled over [a, b): the
    busiest host event in the gap, leaving out waits (a sleeping generator
    or an idle drain thread says only that no work was there) and wrappers
    that span many times the gap. ``waiting`` where nothing else ran."""
    cands = []
    for events in host_lines:
        for ev, s, d in events:
            e = s + d
            if e <= a or s >= b or d > 4 * (b - a) or any(w in ev for w in WAITING):
                continue
            cands.append((min(e, b) - max(s, a), d, ev))
    if not cands:
        return "waiting"
    # the most specific event among those that cover most of the busiest
    top = max(c[0] for c in cands)
    return min((c for c in cands if c[0] >= 0.5 * top), key=lambda c: c[1])[2]


def breakdown(reduced: dict) -> dict:
    """The ``--trace 1`` result's ``breakdown``: the device programs that
    took most time, and the longest idle gaps by what the host was doing."""
    ops = sorted(reduced["module_s"].items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in reduced["gaps"]]}
