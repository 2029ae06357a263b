"""Paged-attention decode Pallas TPU kernel (vLLM-style, block tables).

One query token per sequence attends over a KV cache scattered across
fixed-size pages.  The per-request page list (*block table*) is a
scalar-prefetch operand — ``PrefetchScalarGridSpec`` makes it available
to the BlockSpec index maps, so each grid step DMAs exactly the one page
it needs from the pool; the kernel never materializes a request's
logically-contiguous KV view in HBM.

Grid: (batch, num_blocks) — the page dimension is sequential
("arbitrary") so the online-softmax accumulators of every query head
persist in VMEM scratch across pages.

Mosaic tiles the last two dimensions of a block by (8, 128) unless the
block spans them whole, which rules out a one-KV-head slice of a page.
So one grid step takes a whole page — every KV head — viewed as
(page * KV, D) rows (a free reshape of the pool), and scores all H query
heads against all of its rows in one matmul; a row belongs to KV head
``row % KV`` and query head ``h`` to KV head ``h // G``, and every other
pairing is masked out like a position past the context.

Layouts (last two dims are the tiled ones):
  q        (B, H, D)            block (1, H, D)
  k_pages  (P, page * KV, D)    block (1, page * KV, D)  page via table
  v_pages  (P, page * KV, D)    block (1, page * KV, D)
  o        (B, H, D)            block (1, H, D)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  page_size: int, kv_heads: int, group: int,
                  num_blocks: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (H, D)
    k = k_ref[0].astype(jnp.float32)  # (page * KV, D)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (H, page*KV)

    # row r of the page is token r // KV of KV head r % KV; everything
    # at or past the context length (trash-padded table entries
    # included) and every other head's rows are masked out
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    pos = j * page_size + row // kv_heads
    ok = (pos < lens_ref[b]) & (row % kv_heads == head // group)
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # a fully-masked row keeps m_new == NEG_INF; exp(s - m_new) would be
    # exp(0) = 1 there, silently averaging trash pages — force p = 0 so l
    # stays 0 and _finalize emits zeros for empty contexts
    p = jnp.where(m_new <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_bhd(
    q: jax.Array,             # (B, H, D)
    k_pages: jax.Array,       # (P, page, KV, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32
    context_lens: jax.Array,  # (B,) int32
    *,
    interpret: bool,
) -> jax.Array:
    B, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    nb = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _paged_kernel, page_size=page, kv_heads=KV, group=H // KV,
        num_blocks=nb, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_tables, context_lens)
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, tables, lens: (b, 0, 0)),
            pl.BlockSpec((1, page * KV, D),
                         lambda b, j, tables, lens: (tables[b, j], 0, 0)),
            pl.BlockSpec((1, page * KV, D),
                         lambda b, j, tables, lens: (tables[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D),
                               lambda b, j, tables, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32), q,
      k_pages.reshape(P, page * KV, D), v_pages.reshape(P, page * KV, D))
