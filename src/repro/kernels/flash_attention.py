"""Flash attention Pallas TPU kernel (GQA + causal + sliding window).

TPU-native adaptation of the flash-attention idea: blocked online-softmax
with the running (m, l, acc) state held in VMEM scratch across the
innermost (sequential) grid dimension, MXU-aligned block shapes, and GQA
expressed through the K/V BlockSpec index map (no K/V replication in HBM).

Grid: (batch, q_heads, num_q_blocks, num_k_blocks) — the k dimension is
"arbitrary" (sequential) so scratch accumulators persist across it.

Layouts (chosen so the last two dims are the MXU-tiled ones):
  q:  (B, H,  S, D)   block (1, 1, block_q, D)
  k:  (B, KV, S, D)   block (1, 1, block_k, D)   index: kv = h // group
  v:  (B, KV, S, D)   block (1, 1, block_k, D)
  o:  (B, H,  S, D)   block (1, 1, block_q, D)
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool, window: int,
                  num_k_blocks: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = jnp.ones(s.shape, jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]  # (bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, S, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, num_k_blocks=nk, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
