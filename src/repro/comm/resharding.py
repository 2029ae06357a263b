"""Cross-worker resharding: the production data plane for weight sync.

At production scale the trainer holds params FSDP+TP-sharded on its
sub-mesh while the rollout engine wants them TP-only (or differently
laid out) on ITS sub-mesh.  The paper's weight-update barrier is, in JAX
terms, a pytree `device_put` from one NamedSharding to another — XLA
emits the minimal collective schedule.  This module wraps that, plus the
byte accounting the profiler feeds to the scheduler (weight sync is part
of the context-switch cost).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


def reshard(tree: Any, shardings: Any) -> Any:
    """device_put every leaf to its destination sharding (async)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings,
        is_leaf=lambda x: isinstance(x, (NamedSharding,)))


def reshard_params(params: Any, mesh: Mesh, specs: Any) -> Any:
    shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))
    return reshard(params, shardings)


def transfer_stats(tree: Any) -> Dict[str, float]:
    """Bytes that a weight-sync of this tree moves (profiler input)."""
    total = 0
    n = 0
    for l in jax.tree_util.tree_leaves(tree):
        if hasattr(l, "nbytes"):
            total += int(l.nbytes)
            n += 1
    return {"bytes": float(total), "arrays": float(n)}


def timed_weight_sync(params: Any, dst_shardings: Any
                      ) -> Tuple[Any, float]:
    """Reshard + block, returning (new_tree, seconds) — the measured
    weight-update-barrier cost the scheduler charges between training and
    generation stages.  Traced, it is one ``weight-sync`` span."""
    tr = _trace.active()
    if tr is None:
        return _synced(params, dst_shardings)
    stats = transfer_stats(params)
    with tr.span("weight-sync", "sync", bytes=stats["bytes"],
                 arrays=int(stats["arrays"])):
        out, dt = _synced(params, dst_shardings)
    reg = _metrics.active()
    if reg is not None:
        reg.counter("sync/count").inc()
        reg.counter("sync/bytes").inc(stats["bytes"])
        reg.histogram("sync/seconds").observe(dt)
    return out, dt


def _synced(params: Any, dst_shardings: Any) -> Tuple[Any, float]:
    t0 = time.perf_counter()
    out = reshard(params, dst_shardings)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0
