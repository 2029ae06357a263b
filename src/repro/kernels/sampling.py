"""Fused sampling Pallas TPU kernel: temperature + top-k + top-p +
Gumbel-max categorical in one pass over the logits row.

The unfused serving path (``serve.sampling.sample_token``) materializes
up to four (B, V) intermediates per decode step — tempered logits, a
``lax.top_k`` result, a full descending sort with softmax/cumsum for the
nucleus cutoff, and the categorical's own Gumbel draw — each a separate
HBM round-trip at vocab widths of 100k+.  This kernel streams the row
once in VMEM and fuses everything:

* **temperature** — static scalar multiply.
* **top-k** — the exact k-th largest value via ``k`` iterations of
  find-max + mask-first-occurrence (k is a small static serving
  parameter; k passes over a VMEM-resident row beat a full HBM sort).
* **top-p** — the nucleus cutoff via binary search on the *order-
  preserving int32 bitcast* of the float row: ~32 fixed
  iterations, each a masked sum, no sort.  The kept set {x : mass
  strictly above x < p} matches the oracle's "smallest sorted prefix
  reaching p, cutoff token always kept" semantics including duplicate
  handling.
* **categorical** — Gumbel-max: ``argmax(filtered + gumbel)`` with the
  Gumbel noise passed IN (generated from the caller's per-request keys,
  so fused and unfused paths draw bit-identical samples).
* **behaviour logprob** — the token's logprob under the *unfiltered*
  temperature-1 policy (what the RL importance ratio references),
  computed from the same resident row.

Grid: (B / 8,) — one program per block of 8 rows, blocks fully parallel.
Mosaic tiles the last two dimensions of every block by (8, 128) unless
a block spans the whole dimension, so a block holds 8 full rows (the
f32 sublane count) and the wrapper pads the batch up to a multiple of 8;
every reduction is per row.

Layouts:
  logits (Bp, V)  block (8, V)
  gumbel (Bp, V)  block (8, V)
  token  (Bp, 1)  block (8, 1) int32
  lp     (Bp, 1)  block (8, 1) float32
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ROWS = 8  # rows per block: the f32 sublane count Mosaic tiles by


def _sort_keys(x: jax.Array) -> jax.Array:
    """Order-preserving map float32 -> int32: a < b  <=>  key(a) < key(b).

    IEEE-754 trick: non-negative floats order like their bit patterns
    read as signed ints; negative floats order in reverse, so flipping
    their 31 magnitude bits puts them below the non-negatives in order.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _first_argmax(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-row index of the first occurrence of the row maximum (matches
    jnp.argmax tie-breaking); (R, V) -> (R, 1)."""
    m = jnp.max(x, axis=-1, keepdims=True)
    big = jnp.int32(x.shape[-1])
    return jnp.min(jnp.where(x >= m, idx, big), axis=-1, keepdims=True)


def _row_sum(x: jax.Array) -> jax.Array:
    return jnp.sum(x, axis=-1, keepdims=True)


def _sampling_kernel(logits_ref, gumbel_ref, tok_ref, lp_ref, *,
                     temperature: float, top_k: int, top_p: float,
                     vocab_size: int):
    row = logits_ref[...].astype(jnp.float32)  # (R, V)
    V = row.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    if 0 < vocab_size < V:
        row = jnp.where(idx < vocab_size, row, NEG_INF)

    # behaviour logprob normalizer on the UNFILTERED temp-1 row
    m0 = jnp.max(row, axis=-1, keepdims=True)
    lse = m0 + jnp.log(_row_sum(jnp.exp(row - m0)))

    if temperature <= 0.0:
        tok = _first_argmax(row, idx)  # greedy
    else:
        x = row / temperature
        if 0 < top_k < V:
            # exact k-th largest: peel the max k times (duplicates count
            # once per occurrence, exactly like lax.top_k)
            def peel(_, carry):
                work, _ = carry
                m = jnp.max(work, axis=-1, keepdims=True)
                first = _first_argmax(work, idx)
                return jnp.where(idx == first, NEG_INF, work), m

            _, cutoff = jax.lax.fori_loop(
                0, top_k, peel, (x, jnp.zeros_like(m0)))
            x = jnp.where(x < cutoff, NEG_INF, x)
        if top_p < 1.0:
            # nucleus cutoff: binary-search the sort-key space for the
            # smallest value whose strictly-greater mass is < p
            mx = jnp.max(x, axis=-1, keepdims=True)
            ex = jnp.exp(x - mx)  # masked entries underflow to 0
            z = _row_sum(ex)
            keys = _sort_keys(x)
            # H(lo) = 1 >= p, H(hi) = 0 < p; the key of -inf is above
            # INT32_MIN, so lo cannot wrap
            lo = jnp.min(keys, axis=-1, keepdims=True) - 1
            hi = jnp.max(keys, axis=-1, keepdims=True)

            def bisect(_, carry):
                lo, hi = carry
                # floor((lo + hi) / 2) without int32 overflow
                mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
                above = _row_sum(jnp.where(keys > mid, ex, 0.0)) / z
                keep = above >= top_p
                return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

            lo, hi = jax.lax.fori_loop(0, 33, bisect, (lo, hi))
            x = jnp.where(keys < hi, NEG_INF, x)
        tok = _first_argmax(x + gumbel_ref[...].astype(jnp.float32), idx)

    tok_lp = _row_sum(jnp.where(idx == tok, row, 0.0))
    tok_ref[...] = tok.astype(jnp.int32)
    lp_ref[...] = (tok_lp - lse).astype(jnp.float32)


def fused_sample_bv(
    logits: jax.Array,  # (B, V)
    gumbel: jax.Array,  # (B, V) Gumbel(0,1) noise (ignored at temp<=0)
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    vocab_size: int = 0,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (token (B,) int32, behaviour logprob (B,) float32)."""
    B, V = logits.shape
    assert gumbel.shape == (B, V), (gumbel.shape, logits.shape)
    Bp = -(-B // ROWS) * ROWS
    logits = jnp.pad(logits.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    gumbel = jnp.pad(gumbel.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    kernel = functools.partial(
        _sampling_kernel, temperature=float(temperature), top_k=int(top_k),
        top_p=float(top_p), vocab_size=int(vocab_size))
    tok, lp = pl.pallas_call(
        kernel,
        grid=(Bp // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, V), lambda b: (b, 0)),
            pl.BlockSpec((ROWS, V), lambda b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, 1), lambda b: (b, 0)),
            pl.BlockSpec((ROWS, 1), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(logits, gumbel)
    return tok[:B, 0], lp[:B, 0]
