#!/usr/bin/env python3
"""Device-idle time of a traced window by the program span the host was in.

The program's live spans (``repro.obs.trace.Tracer.span``) open a
``jax.profiler.TraceAnnotation`` named ``repro:<span>``, so they sit on
the trace's host plane beside the benchmark's ``bench:`` annotations.
Each idle piece of the window is charged to the innermost program span
covering it: the latest-starting one, as ``trace_reduce`` charges the
stages (on equal starts, the shorter).  Busy time and the window are
those of ``trace_reduce.reduce``, so the pieces add up to its idle time.

The spans fall into three groups: the rollout engine (``engine-step``
and its ``engine-prefill`` / ``engine-decode`` children), the context
switch and weight sync (``offload:*``, ``onload:*``, ``prefetch``,
``weight-sync``), and the rest (``iteration``, ``execute``, the
executor's task spans, or no program span): what the spans cannot name.

    python3 bench/program_idle.py <trace.xplane.pb>

reads a trace that ``bench/run.py --trace 1 --keep-trace <file>`` kept
and prints one JSON line.
"""
from __future__ import annotations

import heapq
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

PREFIX = "repro:"
NO_SPAN = "no program span"
GROUPS = ("engine", "switch", "unattributed")

Event = Tuple[int, int, str]


def load(path: str) -> List[Event]:
    """The program's spans on the host plane of one trace, as
    (start_ns, end_ns, span name) sorted by start."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((int(e.start_ns), int(e.end_ns),
                            e.name[len(PREFIX):]) for e in line.events
                           if e.name.startswith(PREFIX))
    return sorted(out)


def group(name: str) -> str:
    if name.startswith("engine-"):
        return "engine"
    if name.startswith(("offload:", "onload:")) or name in ("prefetch",
                                                           "weight-sync"):
        return "switch"
    return "unattributed"


def innermost_pieces(spans: List[Event], lo: int, hi: int) -> List[Event]:
    """[lo, hi] cut where a span starts or ends, each piece with the
    innermost span covering it (``NO_SPAN`` where none does)."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    order = sorted(spans)
    open_: list = []  # (-start, end, i): latest start first, then shortest
    pieces: List[Event] = []
    nxt = 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(order) and order[nxt][0] <= a:
            s, e, _ = order[nxt]
            heapq.heappush(open_, (-s, e, nxt))
            nxt += 1
        while open_ and open_[0][1] <= a:  # ended before this piece
            heapq.heappop(open_)
        pieces.append((a, b, order[open_[0][2]][2] if open_ else NO_SPAN))
    return pieces


def idle_by_span(trace: dict, spans: List[Event]) -> Optional[dict]:
    """Idle seconds of the window of ``trace`` (``trace_reduce.load``)
    by innermost program span and by group, averaged over the devices,
    beside ``busy_s`` and ``window_s``.  None without a window or a
    device op."""
    windows = [(s, e) for s, e, n in trace["host"] if n == trace_reduce.WINDOW]
    if not windows or not trace["devices"]:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    pieces = innermost_pieces(spans, lo, hi)
    n_dev = len(trace["devices"])
    busy_ns, idle_ns = 0, defaultdict(float)
    for dev in trace["devices"].values():
        busy = trace_reduce.clip(trace_reduce.union(
            [(s, e) for s, e, _ in dev["ops"] if e > lo and s < hi]), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        i = 0
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            while pieces[i][1] <= g0:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < g1:
                a, b, name = pieces[j]
                idle_ns[name] += (min(b, g1) - max(a, g0)) / n_dev
                j += 1
    by_group = dict.fromkeys(GROUPS, 0.0)
    for name, ns in idle_ns.items():
        by_group[group(name)] += ns / 1e9
    return {
        "busy_s": busy_ns / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "idle_by_span": {n: v / 1e9 for n, v in
                         sorted(idle_ns.items(), key=lambda kv: -kv[1])},
        "idle_by_group": by_group,
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[0], file=sys.stderr)
        print("usage: program_idle.py <trace.xplane.pb>", file=sys.stderr)
        return 2
    out = idle_by_span(trace_reduce.load(args[0]), load(args[0]))
    if out is None:
        print("no window or no device op in the trace", file=sys.stderr)
        return 1
    w = out["window_s"]
    out["idle_share"] = 100.0 * (1.0 - out["busy_s"] / w)
    out["idle_share_by_group"] = {g: 100.0 * v / w
                                  for g, v in out["idle_by_group"].items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
