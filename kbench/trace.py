"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time,
its executables' device time, and the breakdown the driver records.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the device planes. An executable's device time is
the sum of its ``XLA Modules`` events. An idle gap is a stretch of the
traced span with no device operation; each of the longest is named by
the host event that covers most of it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> List[dict]:
    """The trace's planes as plain data: [{"name", "lines": {line name:
    [(name, start_ns, end_ns)]}}]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for e in line.events
            ]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce(planes: List[dict], top: int = 10) -> Optional[dict]:
    """None when the trace holds no device plane with operations."""
    devices = [
        p for p in planes
        if p["name"].startswith("/device:") and (
            p["lines"].get(OPS_LINE) or p["lines"].get(MODULES_LINE)
        )
    ]
    if not devices:
        return None
    hosts = [p for p in planes if p["name"].startswith("/host:")]
    lo = min(
        e[1] for p in devices + hosts for evs in p["lines"].values() for e in evs
    )
    hi = max(
        e[2] for p in devices + hosts for evs in p["lines"].values() for e in evs
    )
    busy_ns = 0
    modules: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[int, int]] = []
    for p in devices:
        op_events = p["lines"].get(OPS_LINE) or p["lines"][MODULES_LINE]
        merged = _union([(s, e) for _, s, e in op_events])
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in p["lines"].get(MODULES_LINE, []):
            modules[name] += (e - s) / 1e9
        for name, s, e in op_events:
            # A TPU op's event name is its whole HLO instruction.
            ops[name.split(" = ", 1)[0]] += (e - s) / 1e9
        edge = lo
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if hi > edge:
            gaps.append((edge, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host_events = [
        (line, name, s, e)
        for p in hosts for line, evs in p["lines"].items() for name, s, e in evs
    ]
    return {
        "busy_s": busy_ns / len(devices) / 1e9,
        "span_s": (hi - lo) / 1e9,
        "modules": dict(modules),
        "ops": dict(ops),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [
            [_what_host_did(host_events, s, e), (e - s) / 1e9]
            for s, e in gaps[:top]
        ],
    }


def _what_host_did(host_events, s: int, e: int) -> str:
    """The innermost host event covering at least half of the gap,
    else the one overlapping it most; "host idle" when none does."""
    best, best_key = "host idle", None
    for line, name, hs, he in host_events:
        ov = min(e, he) - max(s, hs)
        if ov <= 0:
            continue
        key = (ov * 2 >= e - s, -(he - hs) if ov * 2 >= e - s else ov)
        if best_key is None or key > best_key:
            best, best_key = f"{line}: {name}", key
    return best


def module_seconds(reduced: dict, needles) -> float:
    """Device seconds of the executables whose module name contains one
    of ``needles``."""
    return sum(
        sec for name, sec in reduced["modules"].items()
        if any(n in name for n in needles)
    )
