"""Per-layer metric readers, one file each, found by the metric's name.

Each file defines ``read(ctx) -> float | None``.  ``ctx`` holds what a
traced run collected over its window: ``window_s``; ``stage_spans``, the
benchmark's (name, t0, t1) spans around each workflow stage;
``program_spans``, the program's own (name, category, t0, t1) spans;
``trace``, the device-trace reduction of ``trace_reduce.reduce`` (or
None); ``iteration_flops``, ``iterations``, ``chips`` and ``peaks``.  A
reader that finds nothing to read returns None and the metric is left
out of the result line.
"""
