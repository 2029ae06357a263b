"""Gigabytes moved per iteration by context switches and weight syncs:
the program's ``switch/bytes`` (``core/switching.py``) and ``sync/bytes``
(``comm/resharding.py``) counters, over the window's iterations.  The
program's metrics registry records only while a tracer is armed, which
in a run is the traced window alone, so what it holds afterwards is the
window's."""


def read(ctx):
    from repro.obs.metrics import default_registry

    snap = default_registry().snapshot()
    if "switch/bytes" not in snap or not ctx["iterations"]:
        return None
    moved = snap["switch/bytes"]["value"] + snap.get(
        "sync/bytes", {"value": 0.0})["value"]
    return moved / ctx["iterations"] / 1e9
