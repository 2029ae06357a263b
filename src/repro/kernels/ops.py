"""Jit-friendly wrappers dispatching model layouts onto the Pallas kernels.

This module is the one place that decides how a kernel runs: compiled by
Mosaic on a TPU backend, and under the Pallas interpreter (which executes
the kernel body on the host, for correctness) on any other backend.  The
kernel entries themselves take ``interpret`` with no default, so a caller
that bypasses this module has to choose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import paged_attention as _pa
from repro.kernels import sampling as _samp
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ssm_update as _ssu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """Model layout (B, S, H, D) / (B, S, KV, D) -> (B, S, H, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens):
    """Decode-time paged attention: q (B, H, D) over a (P, page, KV, D)
    page pool addressed through per-request block tables."""
    return _pa.paged_attention_bhd(
        q, k_pages, v_pages, block_tables, context_lens,
        interpret=_interpret())


def ssd_scan(x, dt, A, Bm, Cm, D, chunk: int):
    """Model layout (see models.ssm.mamba2_block):
    x (B, L, H, P), dt (B, L, H), A (H,), Bm/Cm (B, L, N), D (H,)."""
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    assert L % chunk == 0
    nc = L // chunk
    xk = x.reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
    dtk = dt.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2)
    Bk = Bm.reshape(B, nc, chunk, N)
    Ck = Cm.reshape(B, nc, chunk, N)
    Ab = jnp.broadcast_to(A[None, :], (B, H))
    Db = jnp.broadcast_to(D[None, :], (B, H))
    y = _ssd.ssd_scan_bhcsp(xk, dtk, Ab, Bk, Ck, Db,
                            interpret=_interpret())
    # back to (B, L, H, P)
    return y.transpose(0, 2, 3, 1, 4).reshape(B, L, H, P)


def grouped_matmul(buf, w, **kw):
    return _gmm.grouped_matmul(buf, w, interpret=_interpret(), **kw)


def moe_decode(x, expert_idx, gate_vals, gate_w, up_w, down_w):
    """Expert-parallel exact top-k decode FFN (token→expert gather +
    grouped per-expert GEMMs): x (T, d), expert_idx/gate_vals (T, k),
    gate_w/up_w (E, d, f), down_w (E, f, d) -> (T, d)."""
    return _gmm.moe_decode_gmm(x, expert_idx, gate_vals, gate_w, up_w,
                               down_w, interpret=_interpret())


def ssm_state_update(state, x, dt, A, Bm, Cm, D):
    """Single-token SSD state update (models.ssm.mamba2_decode layout):
    state (B, H, P, N) f32, x (B, H, P), dt (B, H), A (H,), Bm/Cm (B, N),
    D (H,) -> (y (B, H, P) f32, new_state (B, H, P, N) f32)."""
    B, H = dt.shape
    Ab = jnp.broadcast_to(A[None, :], (B, H))
    Db = jnp.broadcast_to(D[None, :], (B, H))
    return _ssu.ssm_state_update_bh(state, x, dt, Ab, Bm, Cm, Db,
                                    interpret=_interpret())


def fused_sample(logits, gumbel, *, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, vocab_size: int = 0):
    """Fused temperature+top-k+top-p+Gumbel-max sampling over (B, V)
    logits; gumbel is the caller's per-row Gumbel(0,1) noise.  Returns
    (token (B,) int32, behaviour logprob (B,) float32)."""
    return _samp.fused_sample_bv(
        logits, gumbel, temperature=temperature, top_k=top_k, top_p=top_p,
        vocab_size=vocab_size, interpret=_interpret())
