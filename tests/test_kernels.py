"""Per-kernel shape/dtype sweeps asserting allclose against ref.py oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 2, 1, 128, 32),
    (2, 4, 2, 256, 64),
    (1, 8, 8, 128, 128),  # MHA
    (2, 6, 2, 384, 64),   # 3-way GQA groups
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100), (False, 0)])
def test_flash_attention_sweep(B, H, KV, S, D, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=128, block_k=128)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@settings(max_examples=8, deadline=None)
@given(
    bq=st.sampled_from([32, 64, 128]),
    bk=st.sampled_from([32, 64, 128]),
    s_mult=st.integers(2, 4),
)
def test_flash_attention_block_shape_property(bq, bk, s_mult):
    """Output must be independent of the BlockSpec tiling choice."""
    S = 128 * s_mult
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, S, 2, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))
    a = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    b = ops.flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,P,N,L,chunk", [
    (1, 2, 16, 8, 64, 16),
    (2, 4, 32, 16, 128, 32),
    (1, 1, 64, 64, 256, 64),
    (3, 2, 16, 8, 96, 32),  # batch > 1, three chunks carried
])
def test_ssd_scan_sweep(B, H, P, N, L, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, L, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, L, N)) * 0.5).astype(dtype)
    D = jnp.ones((H,), jnp.float32)
    got = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk)
    nc = L // chunk
    want = ref.ssd_scan_ref(
        x.reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4),
        dt.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2),
        jnp.broadcast_to(A, (B, H)),
        Bm.reshape(B, nc, chunk, N), Cm.reshape(B, nc, chunk, N),
        jnp.broadcast_to(D, (B, H)))
    want = want.transpose(0, 2, 3, 1, 4).reshape(B, L, H, P)
    tol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# single-token SSD state update (state-cache decode path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,P,N", [
    (1, 2, 16, 8),
    (3, 4, 32, 16),
    (2, 24, 64, 128),  # mamba2-370m head geometry
    (5, 3, 8, 16),
])
def test_ssm_state_update_sweep(B, H, P, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(ks[0], (B, H, P, N), jnp.float32)
    x = jax.random.normal(ks[1], (B, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[3], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[4], (B, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[5], (B, N)) * 0.5).astype(dtype)
    D = jnp.ones((H,), jnp.float32)
    got_y, got_s = ops.ssm_state_update(state, x, dt, A, Bm, Cm, D)
    want_y, want_s = ref.ssm_state_update_ref(state, x, dt, A, Bm, Cm, D)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F,bc,bd,bf", [
    (2, 64, 128, 64, 64, 64, 64),
    (4, 128, 256, 128, 64, 128, 64),
    (8, 256, 128, 512, 128, 128, 128),
])
def test_grouped_matmul_sweep(E, C, D, F, bc, bd, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    buf = jax.random.normal(ks[0], (E, C, D), dtype)
    w = (jax.random.normal(ks[1], (E, D, F)) * 0.05).astype(dtype)
    got = ops.grouped_matmul(buf, w, block_c=bc, block_d=bd, block_f=bf)
    want = ref.grouped_matmul_ref(buf, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype] * 10)


@settings(max_examples=6, deadline=None)
@given(e=st.integers(1, 6), scale=st.floats(0.01, 2.0))
def test_grouped_matmul_linearity_property(e, scale):
    """gmm(a·buf, w) == a · gmm(buf, w) — catches accumulator bugs."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    buf = jax.random.normal(ks[0], (e, 64, 128))
    w = jax.random.normal(ks[1], (e, 128, 64)) * 0.1
    a = ops.grouped_matmul(buf * scale, w)
    b = ops.grouped_matmul(buf, w) * scale
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# expert-parallel exact MoE decode (gather + grouped GEMMs + combine)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,E,k,d,f", [
    (1, 4, 2, 64, 32),
    (7, 8, 2, 128, 64),
    (160, 4, 2, 128, 128),  # T > 128: capacity rounds up to 256
])
def test_moe_decode_sweep(T, E, k, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (T, d), dtype)
    gate_w = (jax.random.normal(ks[1], (E, d, f)) * 0.05).astype(dtype)
    up_w = (jax.random.normal(ks[2], (E, d, f)) * 0.05).astype(dtype)
    down_w = (jax.random.normal(ks[3], (E, f, d)) * 0.05).astype(dtype)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(np.stack([rng.permutation(E)[:k]
                                for _ in range(T)]).astype(np.int32))
    gv = jnp.asarray(rng.dirichlet(np.ones(k), size=T).astype(np.float32))
    got = ops.moe_decode(x, idx, gv, gate_w, up_w, down_w)
    want = ref.moe_decode_ref(x, idx, gv, gate_w, up_w, down_w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype] * 10)


def test_moe_decode_is_capacity_free():
    """Every token's full top-k contributes even when all tokens pick
    the same expert — the drop regime capacity dispatch cannot serve."""
    T, E, k, d, f = 9, 4, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (T, d))
    gate_w = jax.random.normal(ks[1], (E, d, f)) * 0.05
    up_w = jax.random.normal(ks[2], (E, d, f)) * 0.05
    down_w = jax.random.normal(ks[3], (E, f, d)) * 0.05
    # adversarial skew: every token routes to experts {0, 1}
    idx = jnp.tile(jnp.asarray([[0, 1]], jnp.int32), (T, 1))
    gv = jnp.tile(jnp.asarray([[0.7, 0.3]], jnp.float32), (T, 1))
    got = ops.moe_decode(x, idx, gv, gate_w, up_w, down_w)
    want = ref.moe_decode_ref(x, idx, gv, gate_w, up_w, down_w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# paged attention (decode over block tables)
# ---------------------------------------------------------------------------
def _paged_inputs(key, B, H, KV, D, P, page, nb, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k_pages = jax.random.normal(ks[1], (P, page, KV, D), dtype)
    v_pages = jax.random.normal(ks[2], (P, page, KV, D), dtype)
    # distinct non-trash pages per request (page 0 is the trash page)
    rng = np.random.default_rng(int(jax.random.randint(ks[0], (), 0, 1 << 30)))
    tables = np.stack([rng.permutation(np.arange(1, P))[:nb]
                       for _ in range(B)]).astype(np.int32)
    return q, k_pages, v_pages, jnp.asarray(tables)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,D,page,nb", [
    (1, 2, 1, 32, 8, 2),
    (3, 4, 2, 16, 8, 4),    # GQA groups of 2
    (2, 8, 8, 64, 16, 3),   # MHA
    (4, 6, 2, 32, 4, 5),    # 3-way GQA groups
    (3, 24, 8, 64, 16, 2),  # granite-moe heads: 8 KV heads x 3
])
def test_paged_attention_sweep(B, H, KV, D, page, nb, dtype):
    P = nb * B + 1
    q, kp, vp, tables = _paged_inputs(
        jax.random.PRNGKey(0), B, H, KV, D, P, page, nb, dtype)
    # ragged context lengths incl. partial pages and a single-token ctx
    lens = jnp.asarray(
        [1 + (i * 7) % (nb * page) for i in range(B)], jnp.int32)
    got = ops.paged_attention(q, kp, vp, tables, lens)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@settings(max_examples=8, deadline=None)
@given(
    page=st.sampled_from([2, 4, 8, 16]),
    ctx=st.integers(1, 31),
    seed=st.integers(0, 100),
)
def test_paged_attention_block_size_property(page, ctx, seed):
    """Output must be independent of the page-size tiling choice."""
    B, H, KV, D = 2, 4, 2, 16
    total = 32
    nb = total // page
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    # one logically-contiguous KV stream laid out under two page sizes
    kflat = jax.random.normal(ks[1], (B, total, KV, D))
    vflat = jax.random.normal(ks[2], (B, total, KV, D))
    lens = jnp.asarray([ctx, total - ctx + 1], jnp.int32)

    def run(page_size):
        nb_ = total // page_size
        P = B * nb_ + 1
        kp = jnp.zeros((P, page_size, KV, D))
        vp = jnp.zeros((P, page_size, KV, D))
        tables = np.zeros((B, nb_), np.int32)
        pid = 1
        for b in range(B):
            for j in range(nb_):
                kp = kp.at[pid].set(
                    kflat[b, j * page_size:(j + 1) * page_size])
                vp = vp.at[pid].set(
                    vflat[b, j * page_size:(j + 1) * page_size])
                tables[b, j] = pid
                pid += 1
        return np.asarray(ops.paged_attention(
            q, kp, vp, jnp.asarray(tables), lens))

    np.testing.assert_allclose(run(page), run(total), atol=1e-5, rtol=1e-5)


def test_paged_attention_ignores_trash_page_contents():
    """Positions past the context length (incl. trash-padded table rows)
    must not influence the output."""
    B, H, KV, D, page, nb = 2, 4, 2, 16, 4, 4
    P = 16
    q, kp, vp, tables = _paged_inputs(
        jax.random.PRNGKey(3), B, H, KV, D, P, page, nb)
    lens = jnp.asarray([3, 9], jnp.int32)
    base = np.asarray(ops.paged_attention(q, kp, vp, tables, lens))
    # poison the trash page and every slot past the context length
    kp2 = kp.at[0].set(1e3)
    vp2 = vp.at[0].set(1e3)
    got = np.asarray(ops.paged_attention(q, kp2, vp2, tables, lens))
    np.testing.assert_allclose(base, got, atol=1e-6)


def test_paged_attention_empty_context_returns_zeros():
    """context_len == 0 (inactive slot) must yield zeros, not a softmax
    over the masked scores (i.e. the mean of the trash pages)."""
    B, H, KV, D, page, nb = 2, 4, 2, 16, 4, 2
    q, kp, vp, tables = _paged_inputs(
        jax.random.PRNGKey(4), B, H, KV, D, 16, page, nb)
    vp = vp.at[:].set(7.0)  # make averaging-garbage obvious
    lens = jnp.asarray([0, 5], jnp.int32)
    got = np.asarray(ops.paged_attention(q, kp, vp, tables, lens))
    want = np.asarray(ref.paged_attention_ref(q, kp, vp, tables, lens))
    np.testing.assert_allclose(got[0], 0.0, atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# fused sampling (temperature -> top-k -> top-p -> Gumbel-max)
# ---------------------------------------------------------------------------
def _sampling_inputs(seed, B, V):
    kl, kk = jax.random.split(jax.random.PRNGKey(seed))
    logits = 4.0 * jax.random.normal(kl, (B, V), jnp.float32)
    keys = jax.vmap(lambda i: jax.random.fold_in(kk, i))(jnp.arange(B))
    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (V,), jnp.float32))(keys)
    return logits, gumbel, keys


@pytest.mark.parametrize("B,V", [(1, 64), (4, 128), (3, 250),
                                 (9, 300)])  # pads to two 8-row blocks
@pytest.mark.parametrize("temperature,top_k,top_p,vocab_size", [
    (0.0, 0, 1.0, 0),     # greedy
    (1.0, 0, 1.0, 0),     # plain categorical
    (0.7, 5, 1.0, 0),     # top-k only
    (1.0, 0, 0.9, 0),     # nucleus only
    (0.8, 12, 0.7, 40),   # all filters + padded vocab mask
    (1.3, 0, 0.95, 40),
])
def test_fused_sample_sweep(B, V, temperature, top_k, top_p, vocab_size):
    """Token draws are bit-exact vs the oracle (same Gumbel noise in,
    same filters, same argmax tie-breaking); logprobs allclose."""
    logits, gumbel, _ = _sampling_inputs(B * 7 + V, B, V)
    tok, lp = ops.fused_sample(
        logits, gumbel, temperature=temperature, top_k=top_k,
        top_p=top_p, vocab_size=vocab_size)
    want_tok, want_lp = ref.fused_sample_ref(
        logits, gumbel, temperature=temperature, top_k=top_k,
        top_p=top_p, vocab_size=vocab_size)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(want_tok))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("temperature,top_k,top_p", [
    (0.0, 0, 1.0),
    (1.0, 0, 1.0),
    (0.7, 8, 1.0),
    (1.0, 0, 0.85),
    (0.9, 6, 0.8),
])
def test_fused_sample_matches_unfused_serving_path(temperature, top_k,
                                                   top_p):
    """Draw-for-draw parity with the engine's unfused sample_token under
    the same per-request PRNG keys (jax.random.categorical IS Gumbel-max,
    so feeding the kernel Gumbel noise from the same keys must reproduce
    every draw)."""
    import functools

    from repro.serve.sampling import sample_token, sample_tokens_fused

    B, V = 5, 96
    logits, _, keys = _sampling_inputs(11, B, V)
    want_tok, want_lp = jax.vmap(functools.partial(
        sample_token, temperature=temperature, top_k=top_k, top_p=top_p,
        vocab_size=77))(keys, logits)
    got_tok, got_lp = sample_tokens_fused(
        keys, logits, temperature=temperature, top_k=top_k, top_p=top_p,
        vocab_size=77)
    np.testing.assert_array_equal(np.asarray(got_tok),
                                  np.asarray(want_tok))
    np.testing.assert_allclose(np.asarray(got_lp), np.asarray(want_lp),
                               atol=2e-5, rtol=2e-5)


def test_fused_sample_greedy_ties_break_like_argmax():
    logits = (jnp.zeros((2, 64), jnp.float32)
              .at[0, 7].set(3.0).at[0, 20].set(3.0)  # tie: first wins
              .at[1, 0].set(1.0))
    gumbel = jnp.zeros_like(logits)
    tok, lp = ops.fused_sample(logits, gumbel, temperature=0.0)
    assert np.asarray(tok).tolist() == [7, 0]
    want = np.asarray(jax.nn.log_softmax(logits)[jnp.arange(2), tok])
    np.testing.assert_allclose(np.asarray(lp), want, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 2 ** 16),
    temperature=st.sampled_from([0.0, 0.5, 1.0, 1.7]),
    top_k=st.integers(0, 16),
    top_p=st.sampled_from([0.6, 0.8, 0.95, 1.0]),
)
def test_fused_sample_filter_property(seed, temperature, top_k, top_p):
    """Any filter combination: the fused draw equals the oracle draw."""
    B, V = 2, 80
    logits, gumbel, _ = _sampling_inputs(seed, B, V)
    got = ops.fused_sample(logits, gumbel, temperature=temperature,
                           top_k=top_k, top_p=top_p)
    want = ref.fused_sample_ref(logits, gumbel, temperature=temperature,
                                top_k=top_k, top_p=top_p)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=2e-5, rtol=2e-5)
