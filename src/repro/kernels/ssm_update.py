"""Mamba2 single-token SSD state-update Pallas TPU kernel (decode).

The decode-time recurrence of ``models/ssm.mamba2_decode`` for ONE token
per sequence, fused per (batch, head) tile:

  state' = exp(dt * A) * state + (dt * x) ⊗ B
  y      = state' · C + D * x

This is the serve tier's per-step hot op for SSM/hybrid cache layouts —
the state-cache analogue of paged attention: constant-size work per
request per token, no sequence dimension.

Mosaic tiles the last two dimensions of a block by (8, 128) unless the
block spans them whole, so every per-(b, h) operand travels with a unit
axis that makes its block span the tiled dimensions: vectors as (P, 1)
columns or (1, N) rows, scalars as (1, 1) blocks.  The wrapper's
reshapes are free.

Layouts:
  state: (B, H, P, N)  block (1, 1, P, N)   f32 running SSD state
  x:     (B, H, P, 1)  block (1, 1, P, 1)   post-conv head inputs
  dt:    (B, H, 1, 1)  block (1, 1, 1, 1)   post-softplus step size
  A:     (B, H, 1, 1)  block (1, 1, 1, 1)   negative decay rate
  Bm:    (B, 1, N)     block (1, 1, N)      input projection (per batch)
  Cm:    (B, N, 1)     block (1, N, 1)      readout projection
  D:     (B, H, 1, 1)  block (1, 1, 1, 1)   skip gain
  y:     (B, H, P, 1)  block (1, 1, P, 1)
  state':(B, H, P, N)  block (1, 1, P, N)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_update_kernel(state_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
                       d_ref, y_ref, new_state_ref):
    state = state_ref[0, 0].astype(jnp.float32)  # (P, N)
    x = x_ref[0, 0].astype(jnp.float32)  # (P, 1)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (1, 1)
    A = a_ref[0, 0].astype(jnp.float32)  # (1, 1)
    Bm = b_ref[0].astype(jnp.float32)  # (1, N)
    Cm = c_ref[0].astype(jnp.float32)  # (N, 1)
    Dh = d_ref[0, 0].astype(jnp.float32)  # (1, 1)

    decay = jnp.exp(dt * A)
    new_state = state * decay + (dt * x) * Bm  # (P, N)
    y = jnp.dot(new_state, Cm, preferred_element_type=jnp.float32)  # (P, 1)
    y_ref[0, 0] = (y + Dh * x).astype(y_ref.dtype)
    new_state_ref[0, 0] = new_state.astype(new_state_ref.dtype)


def ssm_state_update_bh(
    state: jax.Array,  # (B, H, P, N) f32
    x: jax.Array,  # (B, H, P)
    dt: jax.Array,  # (B, H)
    A: jax.Array,  # (B, H)
    Bm: jax.Array,  # (B, N)
    Cm: jax.Array,  # (B, N)
    D: jax.Array,  # (B, H)
    *,
    interpret: bool,
):
    """Returns (y (B, H, P) f32, new_state (B, H, P, N) f32)."""
    B, H, P, N = state.shape
    scalar = pl.BlockSpec((1, 1, 1, 1), lambda b, h: (b, h, 0, 0))
    y, new_state = pl.pallas_call(
        _ssm_update_kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, 1), lambda b, h: (b, h, 0, 0)),
            scalar,
            scalar,
            pl.BlockSpec((1, 1, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, N, 1), lambda b, h: (b, 0, 0)),
            scalar,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, P, 1), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, P, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(state, x.reshape(B, H, P, 1), dt.reshape(B, H, 1, 1),
      A.reshape(B, H, 1, 1), Bm.reshape(B, 1, N), Cm.reshape(B, N, 1),
      D.reshape(B, H, 1, 1))
    return y.reshape(B, H, P), new_state
