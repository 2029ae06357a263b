"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid: (batch, heads, num_chunks) with the chunk dimension sequential
("arbitrary") — the running inter-chunk state (P, N) lives in VMEM scratch
and is carried across chunk iterations, exactly the recurrence of
models/ssm.ssd_chunked but fused per (batch, head) tile:

  y[c] = (L ⊙ C Bᵀ) diag(dt) x  +  (exp(a_cum) C) · state
  state = exp(a_sum) · state + Σ_s exp(a_sum - a_cum_s) dt_s B_s ⊗ x_s

Mosaic tiles the last two dimensions of a block by (8, 128) unless the
block spans them whole, and has no cumsum.  So the per-chunk vectors
travel as (1, s) rows of arrays with a unit axis inserted (dt and the
within-chunk cumulative log-decay, which the wrapper computes with one
XLA cumsum), the per-head scalars as (1, 1) blocks of (B, H, 1, 1)
arrays, and the kernel transposes a row where it needs a column.

Layouts:
  x:     (B, H, nc, s, P)     block (1, 1, 1, s, P)
  dt:    (B, H, nc, 1, s)     block (1, 1, 1, 1, s)  (post-softplus)
  a_cum: (B, H, nc, 1, s)     block (1, 1, 1, 1, s)  cumsum of dt * A
  Bm:    (B, nc, s, N)        block (1, 1, s, N)     (shared across heads)
  Cm:    (B, nc, s, N)        block (1, 1, s, N)
  D:     (B, H, 1, 1)         block (1, 1, 1, 1)
  y:     (B, H, nc, s, P)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, acum_ref, b_ref, c_ref, d_ref, y_ref,
                state_scr):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (s, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (1, s)
    a_row = acum_ref[0, 0, 0]  # (1, s) log-decay cumsum
    a_col = a_row.T  # (s, 1)
    a_end = a_row[:, -1:]  # (1, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (s, N)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (s, N)
    Dh = d_ref[0, 0].astype(jnp.float32)  # (1, 1)

    # intra-chunk quadratic term
    diff = a_col - a_row  # (s, s) i-j
    ii = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    # mask before exp: avoids overflow fwd and NaN cotangents bwd
    L = jnp.exp(jnp.where(ii >= jj, diff, -1e30))
    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # (s, s)
    W = CB * L * dt  # (s, s) weight on x_j
    y = jnp.dot(W, x, preferred_element_type=jnp.float32)  # (s, P)

    # contribution of the carried state
    state = state_scr[...]  # (P, N)
    Cdec = Cm * jnp.exp(a_col)  # (s, N)
    y += jnp.dot(Cdec, state.T, preferred_element_type=jnp.float32)

    # state update
    decay_to_end = jnp.exp(a_end - a_row)  # (1, s)
    xb = x * (decay_to_end * dt).T  # (s, P)
    new_contrib = jnp.dot(xb.T, Bm, preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(a_end) + new_contrib

    y_ref[0, 0, 0, :, :] = (y + Dh * x).astype(y_ref.dtype)


def ssd_scan_bhcsp(
    x: jax.Array,  # (B, H, nc, s, P)
    dt: jax.Array,  # (B, H, nc, s)
    A: jax.Array,  # (B, H)
    Bm: jax.Array,  # (B, nc, s, N)
    Cm: jax.Array,  # (B, nc, s, N)
    D: jax.Array,  # (B, H)
    *,
    interpret: bool,
) -> jax.Array:
    B, H, nc, s, P = x.shape
    N = Bm.shape[-1]
    a_cum = jnp.cumsum(dt.astype(jnp.float32)
                       * A.astype(jnp.float32)[:, :, None, None], axis=-1)
    row = (B, H, nc, 1, s)
    return pl.pallas_call(
        _ssd_kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, s, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, s), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, s), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, s, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, s, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, s, P),
                               lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, s, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt.reshape(row), a_cum.reshape(row), Bm, Cm,
      D.reshape(B, H, 1, 1))
