"""Plain float32 reference of the granite-moe-3b-a800m configuration, and
the model FLOPs one GRPO iteration requires of it.

Pre-norm decoder: RMSNorm, grouped-query attention with rotary positions
(half-split), RMSNorm, a top-k mixture of SwiGLU experts; final RMSNorm and
the tied embedding as the output head.  Written from the configuration's
sizes alone, in straightforward ``jax.numpy``: every expert is computed for
every token and the top-k combine picks from them.

``init_params`` draws the weights from the seed by the same recipe as the
system under test (the same keys, shapes and scales), so the reference
builds its own weights and takes none from the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def padded_vocab(c) -> int:
    return -(-c["vocab_size"] // 2048) * 2048


def _dense(key, shape, fan_in=None):
    std = 1.0 / math.sqrt(shape[0] if fan_in is None else fan_in)
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std


def init_params(key, c):
    d, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    E, f = c["num_local_experts"], c["intermediate_size"]
    ke, kl, _, _ = jax.random.split(key, 4)
    k_tok, _ = jax.random.split(ke)

    def layer(k):
        k_attn, k_moe = jax.random.split(k)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        kr, kg, ku, kd, _ = jax.random.split(k_moe, 5)
        return {
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": _dense(kq, (d, H, hd)), "wk": _dense(kk, (d, KV, hd)),
                     "wv": _dense(kv, (d, KV, hd)), "wo": _dense(ko, (H, hd, d))},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "moe": {"router": _dense(kr, (d, E)), "gate": _dense(kg, (E, d, f)),
                    "up": _dense(ku, (E, d, f)), "down": _dense(kd, (E, f, d))},
        }

    keys = jax.random.split(kl, c["num_hidden_layers"])
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


def _rope(x, theta):
    """x (B, S, heads, hd): rotate the first half against the second."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _attention(p, c, x):
    B, S, _ = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = _rope(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), c["rope_theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), c["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    # query head h reads key/value head h // (H // KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def capacity(tokens: int, c) -> int:
    cap = int(tokens * c["num_experts_per_tok"] / c["num_local_experts"]
              * c["capacity_factor"])
    if cap >= 128:
        cap = -(-cap // 128) * 128
    return max(cap, 1)


def _moe(p, c, x, limit: bool):
    """Top-k mixture.  ``limit``: each expert takes at most ``capacity``
    (token, choice) pairs, first come first served in token-major order,
    and a pair beyond it adds nothing."""
    B, S, d = x.shape
    E, k = c["num_local_experts"], c["num_experts_per_tok"]
    xt = x.reshape(B * S, d)
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"].astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, k)
    gates = top / jnp.maximum(jnp.sum(top, -1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (T, k, E)
    aux = c["router_aux_loss_coef"] * E * jnp.sum(
        jnp.mean(probs, 0) * jnp.mean(jnp.sum(chosen, 1), 0))
    if limit:
        flat = chosen.reshape(-1, E)
        before = jnp.cumsum(flat, 0) - flat  # earlier pairs on the same expert
        place = jnp.sum(before * flat, -1).reshape(gates.shape)
        gates = jnp.where(place < capacity(B * S, c), gates, 0.0)
    combine = jnp.einsum("tk,tke->te", gates, chosen).astype(x.dtype)
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["gate"]))
         * jnp.einsum("td,edf->tef", xt, p["up"]))
    y = jnp.einsum("te,tef,efd->td", combine, h, p["down"])
    return y.reshape(B, S, d), aux


def forward(params, c, tokens, *, capacity_limit: bool):
    """Logits (B, S, vocab_size) in float32 and the load-balance loss.
    Runs in the dtype of ``params``."""
    eps = c["rms_norm_eps"]
    emb = params["embed"]["tokens"]
    x = emb[tokens]
    aux = jnp.zeros((), jnp.float32)
    for i in range(c["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x = x + _attention(lp["attn"], c, _rms(x, lp["ln1"]["scale"], eps))
        y, a = _moe(lp["moe"], c, _rms(x, lp["ln2"]["scale"], eps),
                    capacity_limit)
        x, aux = x + y, aux + a
    x = _rms(x, params["ln_f"]["scale"], eps)
    logits = x @ emb[: c["vocab_size"]].T
    return logits.astype(jnp.float32), aux


# ---------------------------------------------------------------------------
# model FLOPs (2 per multiply-add) the work of one GRPO iteration requires
# ---------------------------------------------------------------------------
def token_flops(c, context: float, seq: int = 0) -> float:
    """One position's forward at a mean attended context of ``context``:
    projections, attention scores and values, the router, top-k experts
    (not all of them), and the output head.  ``seq`` is unused."""
    d, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    E, k, f = c["num_local_experts"], c["num_experts_per_tok"], c["intermediate_size"]
    proj = d * H * hd * 2 + d * KV * hd * 2
    scores = 2 * H * hd * context
    experts = k * 3 * d * f + d * E
    per_layer = proj + scores + experts
    return 2.0 * (c["num_hidden_layers"] * per_layer + d * c["vocab_size"])
