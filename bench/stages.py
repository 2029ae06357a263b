"""Benchmark-owned spans around each workflow stage.

:func:`wrap` puts every entry of the runner's ``task_fns`` inside a host
span (``time.perf_counter``) and a ``jax.profiler.TraceAnnotation``
named ``bench:<stage>``, so that the per-layer shares and the device
trace's idle gaps can name the stage.  Every stage returns numpy or
Python values, so a span ends after its device work.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple

import jax


class Stages:
    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.before: List[Callable] = []
        self.after: List[Callable] = []

    def wrap(self, task_fns: Dict[str, Callable],
             replace: Dict[str, Callable] = None) -> Dict[str, Callable]:
        replace = replace or {}
        return {name: self._wrap(name, replace.get(name, fn))
                for name, fn in task_fns.items()}

    def _wrap(self, name: str, fn: Callable) -> Callable:
        def run(worker, chunk):
            for hook in self.before:
                hook(name, worker, chunk)
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                t0 = time.perf_counter()
                out = fn(worker, chunk)
                t1 = time.perf_counter()
            self.spans.append((name, t0, t1))
            for hook in self.after:
                hook(name, worker, chunk, out)
            return out
        return run

    def in_window(self, t0: float, t1: float) -> List[Tuple[str, float, float]]:
        return [s for s in self.spans if s[1] >= t0 and s[2] <= t1]
