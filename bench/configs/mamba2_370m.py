"""Plain float32 reference of the mamba2-370m configuration, and the model
FLOPs one GRPO iteration requires of it.

Each layer: RMSNorm, then the Mamba2 mixer of arXiv:2405.21060 with one
B/C group: an input projection to [z, x, B, C, dt], a causal depthwise
convolution of width ``d_conv`` over [x, B, C] and SiLU, dt = softplus(dt +
dt_bias), A = -exp(A_log), the selective state-space recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t,

gating by SiLU(z), a gated RMSNorm and the output projection; the
residual adds it back.  The recurrence is evaluated in its quadratic
(attention-like) form over the whole sequence, y_t = sum_{s<=t} (C_t . B_s)
exp(a_{s+1} + ... + a_t) dt_s x_s with a_r = dt_r A, the decay sums taken
directly over the positions between s and t.

``init_params`` draws the weights from the seed by the same recipe as the
system under test, so the reference builds its own weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def padded_vocab(c) -> int:
    return -(-c["vocab_size"] // 2048) * 2048


def _dims(c):
    d, n, P = c["d_model"], c["d_state"], c["headdim"]
    di = c["expand"] * d
    return d, di, n, P, di // P


def _dense(key, shape, std=None):
    std = 1.0 / math.sqrt(shape[0]) if std is None else std
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std


def init_params(key, c):
    d, di, n, P, nh = _dims(c)
    conv_ch = di + 2 * n
    ke, kl, _, _ = jax.random.split(key, 4)
    k_tok, _ = jax.random.split(ke)

    def layer(k):
        k1, k2, k3, _ = jax.random.split(k, 4)
        return {
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "mixer": {
                "in_proj": _dense(k1, (d, 2 * di + 2 * n + nh)),
                "conv_w": _dense(k2, (c["d_conv"], conv_ch), std=0.5),
                "conv_b": jnp.zeros((conv_ch,), jnp.float32),
                "dt_bias": jnp.zeros((nh,), jnp.float32),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
                "D": jnp.ones((nh,), jnp.float32),
                "norm": {"scale": jnp.ones((di,), jnp.float32)},
                "out_proj": _dense(k3, (di, d)),
            },
        }

    keys = jax.random.split(kl, c["n_layer"])
    return {
        "embed": {"tokens": jax.random.normal(
            k_tok, (padded_vocab(c), d), jnp.float32) * 0.02},
        "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
        "layers": jax.vmap(layer)(keys),
    }


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mixer(p, c, u):
    B_, L, _ = u.shape
    d, di, n, P, nh = _dims(c)
    width = c["d_conv"]
    proj = u @ p["in_proj"]
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * n]
    dt = proj[..., 2 * di + 2 * n:]
    # causal depthwise conv: out_t = sum_w xbc_{t-width+1+w} conv_w[w]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, w:w + L] * p["conv_w"][w] for w in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = dt * -jnp.exp(p["A_log"].astype(jnp.float32))  # (B, L, nh)
    # decay[t, s] = exp(sum of a_r over s < r <= t), for s <= t
    t_idx = jnp.arange(L)
    between = ((t_idx[None, None, :] > t_idx[None, :, None])
               & (t_idx[None, None, :] <= t_idx[:, None, None]))  # (t, s, r)
    seg = jnp.einsum("blh,tsl->bhts", a, between.astype(jnp.float32))
    causal = t_idx[:, None] >= t_idx[None, :]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("btn,bsn->bts", Cm.astype(jnp.float32), Bm.astype(jnp.float32))
    xh = xs.reshape(B_, L, nh, P).astype(jnp.float32)
    y = jnp.einsum("bts,bhts,bsh,bshp->bthp", cb, decay, dt, xh)
    y = y + xh * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B_, L, di).astype(u.dtype) * jax.nn.silu(z)
    y = _rms(y, p["norm"]["scale"], c["rms_norm_eps"])
    return y @ p["out_proj"]


def forward(params, c, tokens, *, capacity_limit: bool = False):
    """Logits (B, S, vocab_size) in float32 and a zero auxiliary loss.
    Runs in the dtype of ``params``."""
    del capacity_limit  # no experts
    eps = c["rms_norm_eps"]
    emb = params["embed"]["tokens"]
    x = emb[tokens]
    for i in range(c["n_layer"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x = x + _mixer(lp["mixer"], c, _rms(x, lp["ln1"]["scale"], eps))
    x = _rms(x, params["ln_f"]["scale"], eps)
    logits = x @ emb[: c["vocab_size"]].T
    return logits.astype(jnp.float32), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# model FLOPs (2 per multiply-add) the work of one GRPO iteration requires
# ---------------------------------------------------------------------------
def token_flops(c, context: float, seq: int = 0) -> float:
    """One position's forward.  ``seq`` > 0: the position is part of a
    whole-sequence pass of that length, which the chunked SSD algorithm
    computes in chunks of Q = min(chunk_size, seq): per position the
    causal half of the intra-chunk C.B and (C.B)x terms, the chunk-state
    contribution and the state readout.  ``seq`` == 0: one decode step,
    a state update and a readout.  ``context`` is unused: the state does
    not grow."""
    del context
    d, di, n, P, nh = _dims(c)
    proj = d * (2 * di + 2 * n + nh) + di * d
    conv = c["d_conv"] * (di + 2 * n)
    if seq:
        q = min(c["chunk_size"], seq)
        scan = q * n / 2 + nh * P * q / 2 + 2 * nh * P * n
    else:
        scan = 2 * nh * P * n
    per_layer = proj + conv + scan + nh * P  # + the D skip
    return 2.0 * (c["n_layer"] * per_layer + d * c["vocab_size"])
