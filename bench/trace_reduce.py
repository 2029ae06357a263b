"""Reduction of a ``jax.profiler`` trace to device busy time, the device
operations that took most time, and the device's idle time by what the
host was doing.

The trace holds one plane per device (``/device:TPU:<n>``) whose "XLA Ops"
line lists every operation that ran, and whose "XLA Modules" line lists
the compiled programs they ran in; the host plane (``/host:CPU``) holds
the benchmark's annotations (``bench:<stage>`` around each workflow stage
and ``bench:window`` around the traced iterations).  Busy time is the
union of a device's operation intervals inside the window, averaged over
the devices; idle time is the rest of the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
STAGE_PREFIX = "bench:"

Interval = Tuple[int, int]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> dict:
    """Device ops, device modules and host annotations of one trace, as
    plain tuples (the part of the trace the reduction reads)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(int(e.start_ns), int(e.end_ns), e.name)
                                for e in line.events]
            if dev["ops"]:
                devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events
                            if e.name.startswith(STAGE_PREFIX))
    return {"devices": devices, "host": host}


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """busy_s, window_s, the ``top`` device ops by time (name = program /
    op) and idle seconds by host stage.  None when the trace holds no
    window or no device op."""
    windows = [(s, e) for s, e, n in trace["host"] if n == WINDOW]
    if not windows or not trace["devices"]:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    stages = sorted((s, e, n[len(STAGE_PREFIX):]) for s, e, n in trace["host"]
                    if n != WINDOW)
    busy_ns, op_ns, idle_ns = [], defaultdict(float), defaultdict(float)
    n_dev = len(trace["devices"])
    for dev in trace["devices"].values():
        ops = [(s, e, n) for s, e, n in dev["ops"] if e > lo and s < hi]
        busy = clip(union([(s, e) for s, e, _ in ops]), lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        mods = sorted(dev["modules"])
        starts = [m[0] for m in mods]
        for s, e, n in ops:
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
            op_ns[f"{_short(prog)}/{_short(n)}"] += (min(e, hi) - max(s, lo)) / n_dev
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            # split the gap where a host stage starts or ends inside it
            cuts = sorted({g0, g1} | {t for s, e, _ in stages
                                      for t in (s, e) if g0 < t < g1})
            for a, b in zip(cuts, cuts[1:]):
                idle_ns[_stage_at(stages, (a + b) / 2)] += (b - a) / n_dev
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    top_idle = sorted(idle_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n_dev,
        "device_ops": [[n, v / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in top_idle],
    }


def _short(name: str) -> str:
    """An op's or program's name without its HLO text or fingerprint:
    "%copy.84 = f32[...] copy(...)" -> "copy.84", "jit_f(123)" -> "jit_f"."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def _stage_at(stages, t: float) -> str:
    """The innermost (latest-starting) host stage covering time t."""
    name = "between stages"
    for s, e, n in stages:
        if s > t:
            break
        if e >= t:
            name = n
    return name
