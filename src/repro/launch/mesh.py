"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 forced host devices).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = 1) -> Mesh:
    """(data, model) mesh over the first ``data * model`` devices."""
    n = len(jax.devices())
    assert model * data <= n, (model, data, n)
    return _make_mesh((data, model), ("data", "model"))


def mesh_for_devices(global_ids: Sequence[int], *,
                     axis: str = "data") -> Optional[Mesh]:
    """1-D mesh over the LOCAL jax devices backing a cluster device slice
    (the mesh a worker rebuilds when ``bind_devices`` rebinds it).

    Global cluster ids fold onto local devices round-robin
    (``id % n_local``): at production scale the slice maps 1:1 onto real
    accelerators; on a CI/laptop host every id lands on the lone CPU
    device.  Duplicates are dropped — a Mesh must not repeat devices."""
    if not global_ids:
        return None
    local = jax.devices()
    picked, seen = [], set()
    for g in global_ids:
        d = local[int(g) % len(local)]
        if d.id not in seen:
            seen.add(d.id)
            picked.append(d)
    return Mesh(np.array(picked), (axis,))
