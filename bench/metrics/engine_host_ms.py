"""Mean host time (ms) of one rollout engine step outside its device
calls: each ``engine-step`` span of the program (``serve/engine.py``)
minus the union of the ``engine-prefill`` and ``engine-decode`` spans
inside it, averaged over the steps.  Children are matched to their step
by time, as one engine runs its steps one after another."""
import bisect

from bench.metrics._shares import union_seconds

CHILDREN = ("engine-prefill", "engine-decode")


def read(ctx):
    spans = ctx["program_spans"]
    steps = [(s, e) for n, _, s, e in spans if n == "engine-step"]
    kids = sorted((s, e) for n, _, s, e in spans if n in CHILDREN)
    if not steps or not kids:
        return None
    starts = [s for s, _ in kids]
    host = 0.0
    for s, e in steps:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        inside = [(a, b) for a, b in kids[lo:hi] if b <= e]
        host += (e - s) - union_seconds(inside)
    return 1e3 * host / len(steps)
