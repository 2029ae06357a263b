"""Sampling utilities shared by the serving engines.

Filters (top-k, nucleus/top-p) reshape only the *sampling* distribution;
the behaviour logprob returned to the RL stack is always evaluated under
the unfiltered temperature-1 policy (the same distribution the
inference worker's prefill recompute scores), so importance ratios stay
well-defined whatever decoding strategy produced the trajectory.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.models.layers import token_logprobs

NEG_INF = -1e30


def mask_padded_vocab(logits: jax.Array, vocab_size: int) -> jax.Array:
    """Embedding tables are padded for sharding; never sample the pad."""
    if vocab_size <= 0:
        return logits
    V = logits.shape[-1]
    return jnp.where(jnp.arange(V) < vocab_size, logits, NEG_INF)


def top_k_logits(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k highest logits, mask the rest to -inf.  k<=0 disables."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    vals, _ = jax.lax.top_k(logits, k)
    cutoff = vals[..., -1:]
    return jnp.where(logits < cutoff, NEG_INF, logits)


def top_p_logits(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filter: keep the smallest prefix of the sorted distribution
    whose mass reaches p (the cutoff token itself is always kept, so the
    argmax survives even for tiny p)."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut_idx = jnp.sum(cum < p, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_logits, cut_idx, axis=-1)
    return jnp.where(logits < cutoff, NEG_INF, logits)


def sample_token(
    key: jax.Array,
    logits: jax.Array,  # (..., V)
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    vocab_size: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Draw a token and return ``(token int32, behaviour logprob f32)``.

    temperature <= 0 is greedy (argmax); otherwise temperature scales the
    logits FIRST and the filters apply to the tempered distribution
    (temperature -> top-k -> top-p, the standard serving order: the
    nucleus is computed on the same distribution that is sampled).
    """
    logits = mask_padded_vocab(logits.astype(jnp.float32), vocab_size)
    if temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1)
    else:
        filtered = top_p_logits(top_k_logits(logits / temperature, top_k),
                                top_p)
        tok = jax.random.categorical(key, filtered, axis=-1)
    # behaviour logprob under the unfiltered temp-1 policy (see module doc)
    lp = token_logprobs(logits, tok)
    return tok.astype(jnp.int32), lp


def sample_tokens_fused(
    keys: jax.Array,    # (B, 2) per-row PRNG keys (same keys sample_token
    logits: jax.Array,  # (B, V)  would receive row-by-row)
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    vocab_size: int = 0,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched :func:`sample_token` through the fused Pallas kernel.

    ``jax.random.categorical`` IS Gumbel-max (``argmax(logits +
    gumbel(key))``), so drawing the Gumbel noise here from the same
    per-row keys and fusing filter+argmax in the kernel reproduces the
    unfused path draw-for-draw; parity sweeps in test_kernels.py hold
    the two together.

    ``mesh``: the multi-device mesh the caller's jitted step is
    replicated over.  A Mosaic kernel cannot be partitioned
    automatically, so there the sampler runs under ``shard_map``, every
    device sampling the whole (replicated) batch.
    """
    if mesh is not None:
        rep = PartitionSpec()
        return jax.shard_map(
            functools.partial(sample_tokens_fused, temperature=temperature,
                              top_k=top_k, top_p=top_p,
                              vocab_size=vocab_size),
            mesh=mesh, in_specs=(rep, rep), out_specs=(rep, rep),
            check_vma=False)(keys, logits)
    from repro.kernels import ops as kops

    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    if temperature <= 0.0:
        gumbel = jnp.zeros_like(logits)  # greedy: noise unused
    else:
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (V,), jnp.float32))(keys)
    return kops.fused_sample(
        logits, gumbel, temperature=temperature, top_k=top_k, top_p=top_p,
        vocab_size=vocab_size)
