"""Share (%) of the window spent switching context and syncing weights:
the union of the program's ``offload:*``, ``onload:*``, ``prefetch`` and
``weight-sync`` spans (``core/switching.py``, ``comm/resharding.py``),
over the window."""
from bench.metrics._shares import union_seconds


def _is_switch(name: str) -> bool:
    return (name.startswith(("offload:", "onload:"))
            or name in ("prefetch", "weight-sync"))


def read(ctx):
    spans = [(s, e) for n, _, s, e in ctx["program_spans"] if _is_switch(n)]
    if not spans or ctx["window_s"] <= 0:
        return None
    return 100.0 * union_seconds(spans) / ctx["window_s"]
