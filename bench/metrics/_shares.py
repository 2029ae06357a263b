"""Shared arithmetic of the share readers."""
from __future__ import annotations


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def stage_share(ctx, stage: str):
    spans = [(s, e) for n, s, e in ctx["stage_spans"] if n == stage]
    if not spans or ctx["window_s"] <= 0:
        return None
    return 100.0 * union_seconds(spans) / ctx["window_s"]
