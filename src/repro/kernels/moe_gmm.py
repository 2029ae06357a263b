"""Grouped (per-expert) matmul Pallas TPU kernel for MoE FFN.

Computes out[e] = buf[e] @ w[e] for every expert e over the capacity-
dispatched token buffer — the compute hot-spot of the MoE block after
dispatch.  Grid: (E, C/bc, F/bf, D/bd) with the contraction dimension
sequential and a VMEM f32 accumulator.

Layouts:
  buf: (E, C, D)   block (1, bc, bd)
  w:   (E, D, F)   block (1, bd, bf)
  out: (E, C, F)   block (1, bc, bf)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gmm_kernel(buf_ref, w_ref, o_ref, acc_scr, *, num_d_blocks: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    b = buf_ref[0].astype(jnp.float32)  # (bc, bd)
    w = w_ref[0].astype(jnp.float32)  # (bd, bf)
    acc_scr[...] += jnp.dot(b, w, preferred_element_type=jnp.float32)

    @pl.when(di == num_d_blocks - 1)
    def _finalize():
        o_ref[0, :, :] = acc_scr[...].astype(o_ref.dtype)


def decode_capacity(num_tokens: int) -> int:
    """Drop-free per-expert buffer size for ``moe_decode_gmm``: top-k
    expert indices are distinct per token, so one expert receives at most
    ``num_tokens`` assignments; round up to the MXU tile above 128."""
    if num_tokens <= 128:
        return max(num_tokens, 1)
    return ((num_tokens + 127) // 128) * 128


def moe_decode_gmm(
    x: jax.Array,  # (T, d) tokens at the decode frontier
    expert_idx: jax.Array,  # (T, k) int32 top-k expert ids
    gate_vals: jax.Array,  # (T, k) f32 normalized gate weights
    gate_w: jax.Array,  # (E, d, f)
    up_w: jax.Array,  # (E, d, f)
    down_w: jax.Array,  # (E, f, d)
    *,
    interpret: bool,
) -> jax.Array:
    """Expert-parallel decode FFN: token→expert gather into a drop-free
    per-expert buffer, three grouped GEMMs, weighted scatter-add back.

    Unlike the training path's capacity dispatch, nothing is ever
    dropped (capacity = T covers the worst case of every token routing
    to one expert), so the result equals the exact top-k combine — the
    invariant the serve tier's batch-invariance contract needs.
    Returns (T, d).
    """
    T, d = x.shape
    E = gate_w.shape[0]
    k = expert_idx.shape[1]
    C = decode_capacity(T)
    flat_e = expert_idx.reshape(T * k)
    # position of each assignment within its expert's buffer (stable,
    # token-major — the same slot math as the capacity dispatch, minus
    # the overflow bucket)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (Tk, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot
    my_pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # (Tk,)
    slot = flat_e * C + my_pos
    token_ids = jnp.repeat(jnp.arange(T), k)  # (Tk,)
    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(x[token_ids])
    buf = buf.reshape(E, C, d)
    h = jax.nn.silu(
        grouped_matmul(buf, gate_w, interpret=interpret)
    ) * grouped_matmul(buf, up_w, interpret=interpret)
    out = grouped_matmul(h.astype(x.dtype), down_w, interpret=interpret)
    gathered = out.reshape(E * C, d)[slot]  # (Tk, d)
    weighted = gathered * gate_vals.reshape(T * k, 1).astype(x.dtype)
    return jnp.zeros((T, d), x.dtype).at[token_ids].add(weighted)


def grouped_matmul(
    buf: jax.Array,  # (E, C, D)
    w: jax.Array,  # (E, D, F)
    *,
    block_c: int = 128,
    block_d: int = 512,
    block_f: int = 128,
    interpret: bool,
) -> jax.Array:
    E, C, D = buf.shape
    F = w.shape[-1]
    block_c = min(block_c, C)
    block_d = min(block_d, D)
    block_f = min(block_f, F)
    assert C % block_c == 0 and D % block_d == 0 and F % block_f == 0, (
        (C, D, F), (block_c, block_d, block_f))
    nc, nd, nf = C // block_c, D // block_d, F // block_f
    kernel = functools.partial(_gmm_kernel, num_d_blocks=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, ci, fi, di: (e, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, ci, fi, di: (e, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), buf.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(buf, w)
