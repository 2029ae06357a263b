"""Serve subsystem: paged KV allocator, continuous-batching scheduler,
sampling filters, and PagedEngine parity/lifecycle contracts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.profiler import engine_cost_model, fit_tail_factor
from repro.models import init_model
from repro.models.layers import token_logprobs
from repro.serve import (Engine, OutOfPages, PageAccountingError,
                         PagedEngine, PageAllocator, PrefixCache)
from repro.serve.paging import TRASH_PAGE, pad_block_table
from repro.serve.sampling import sample_token, top_k_logits, top_p_logits
from repro.serve.scheduler import ContinuousScheduler
from repro.train.data import PromptDataset


def dense_cfg():
    return get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=128)


@pytest.fixture(scope="module")
def cfg():
    return dense_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return init_model(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------
def test_allocator_never_hands_out_trash_page():
    a = PageAllocator(num_pages=8, page_size=4)
    got = a.allocate(7)
    assert TRASH_PAGE not in got
    assert sorted(got) == list(range(1, 8))


def test_allocator_free_list_reuse_and_exhaustion():
    a = PageAllocator(num_pages=6, page_size=4)
    first = a.allocate(3)
    assert a.num_free == 2
    a.free(first)
    assert a.num_free == 5
    again = a.allocate(5)
    assert set(first) <= set(again)  # freed pages are recycled
    with pytest.raises(OutOfPages):
        a.allocate(1)


def test_allocator_double_free_raises_typed_error():
    a = PageAllocator(num_pages=4, page_size=2)
    pages = a.allocate(1)
    a.free(pages)
    # the page must NOT re-enter the free list twice (two requests would
    # be handed the same page); the typed error makes the bug loud
    with pytest.raises(PageAccountingError):
        a.free(pages)
    assert a.num_free == 3  # freed exactly once


def test_allocator_refcount_sharing_lifecycle():
    a = PageAllocator(num_pages=4, page_size=2)
    (p,) = a.allocate(1)
    a.incref([p])           # a sharer adopts the page
    assert a.refcount(p) == 2
    a.free([p])             # first owner drops out
    assert a.refcount(p) == 1 and a.num_free == 2
    a.free([p])             # last reference: physically freed
    assert a.refcount(p) == 0 and a.num_free == 3
    with pytest.raises(PageAccountingError):
        a.incref([p])       # incref of an unallocated page is a bug


def test_pages_needed_is_ceil_div():
    a = PageAllocator(num_pages=4, page_size=8)
    assert a.pages_needed(1) == 1
    assert a.pages_needed(8) == 1
    assert a.pages_needed(9) == 2


def test_pad_block_table_pads_with_trash():
    assert pad_block_table([3, 5], 4) == [3, 5, TRASH_PAGE, TRASH_PAGE]


# ---------------------------------------------------------------------------
# continuous-batching scheduler
# ---------------------------------------------------------------------------
def _sched(max_batch=2, num_pages=9, page_size=4, max_seq=16):
    alloc = PageAllocator(num_pages=num_pages, page_size=page_size)
    return ContinuousScheduler(max_batch=max_batch, allocator=alloc,
                               max_seq_len=max_seq)


def test_scheduler_admits_fifo_up_to_slots():
    s = _sched(max_batch=2)
    r1 = s.submit([1, 2, 3], 4)
    r2 = s.submit([1, 2], 4)
    r3 = s.submit([9], 4)
    joined = s.admit()
    assert [r.rid for r in joined] == [r1.rid, r2.rid]
    assert r3.state == "queued" and s.num_active == 2


def test_scheduler_backfills_freed_slot_and_pages():
    s = _sched(max_batch=1, num_pages=3, page_size=4)
    r1 = s.submit([1, 2, 3], 2)
    r2 = s.submit([4, 5], 2)
    (a,) = s.admit()
    assert a is r1 and s.allocator.num_free == 1
    assert not s.admit()  # no slot free
    s.finish(r1)  # evict: pages back on the free list immediately
    assert s.allocator.num_free == 2 and r1.pages == []
    (b,) = s.admit()
    assert b is r2 and r2.slot == 0  # freed slot reused


def test_scheduler_blocks_admission_on_page_budget():
    # 2 slots but pages for only one prompt at a time
    s = _sched(max_batch=2, num_pages=3, page_size=2, max_seq=8)
    s.submit([1, 2, 3], 2)  # needs ceil(4/2)=2 pages
    s.submit([1, 2, 3], 2)
    joined = s.admit()
    assert len(joined) == 1  # second must wait for pages, not slots


def test_scheduler_ensure_page_grows_block_table():
    s = _sched(max_batch=1, num_pages=9, page_size=2, max_seq=16)
    r = s.submit([1, 2, 3], 8)
    s.admit()
    npages = len(r.pages)
    r.num_cached = npages * 2  # simulate filling every allocated slot
    s.ensure_page_for(r)
    assert len(r.pages) == npages + 1


# ---------------------------------------------------------------------------
# sampling: top-k / top-p
# ---------------------------------------------------------------------------
def test_top_k_keeps_exactly_k():
    logits = jnp.asarray([0.1, 2.0, -1.0, 3.0, 0.5])
    out = top_k_logits(logits, 2)
    kept = np.asarray(out) > -1e29
    assert kept.tolist() == [False, True, False, True, False]


def test_top_k_disabled_for_nonpositive_or_full_k():
    logits = jnp.asarray([0.1, 2.0, -1.0])
    np.testing.assert_array_equal(np.asarray(top_k_logits(logits, 0)),
                                  np.asarray(logits))
    np.testing.assert_array_equal(np.asarray(top_k_logits(logits, 3)),
                                  np.asarray(logits))


def test_top_p_nucleus_mass_property():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (64,))
    p = 0.7
    out = np.asarray(top_p_logits(logits, p))
    probs = np.asarray(jax.nn.softmax(logits))
    kept = out > -1e29
    # kept set is the smallest prefix of the sorted distribution >= p
    order = np.argsort(-probs)
    cum = np.cumsum(probs[order])
    n_expected = int(np.searchsorted(cum, p)) + 1
    assert kept.sum() == n_expected
    assert probs[kept].sum() >= p - 1e-6


def test_top_p_always_keeps_argmax():
    logits = jnp.asarray([0.0, 5.0, 1.0, -2.0])
    out = np.asarray(top_p_logits(logits, 1e-6))
    assert out[1] > -1e29 and (out[[0, 2, 3]] < -1e29).all()


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 16), seed=st.integers(0, 50))
def test_sample_token_respects_top_k(k, seed):
    key = jax.random.PRNGKey(seed)
    logits = jax.random.normal(key, (32,)) * 3
    allowed = set(np.argsort(-np.asarray(logits))[:k].tolist())
    tok, _ = sample_token(jax.random.fold_in(key, 1), logits,
                          temperature=1.0, top_k=k)
    assert int(tok) in allowed


def test_sample_token_greedy_and_behaviour_logprob():
    logits = jnp.asarray([0.0, 4.0, 1.0, 2.0])
    tok, lp = sample_token(jax.random.PRNGKey(0), logits, temperature=0.0)
    assert int(tok) == 1
    # behaviour logprob is under the UNFILTERED temp-1 policy
    want = float(token_logprobs(logits[None], jnp.asarray([1]))[0])
    assert lp == pytest.approx(want, abs=1e-6)


def test_sample_token_masks_padded_vocab():
    logits = jnp.asarray([0.0, 1.0, 50.0, 60.0])  # ids 2,3 are padding
    for s in range(8):
        tok, _ = sample_token(jax.random.PRNGKey(s), logits,
                              temperature=1.0, vocab_size=2)
        assert int(tok) < 2


# ---------------------------------------------------------------------------
# PagedEngine vs legacy Engine
# ---------------------------------------------------------------------------
def test_paged_matches_legacy_token_for_token_at_temp0(cfg, params):
    ds = PromptDataset(6, prompt_len=6, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    legacy = Engine(cfg, max_new_tokens=8, temperature=0.0)
    want = legacy.generate(params, jnp.asarray(prompts),
                           key=jax.random.PRNGKey(1))
    # fewer slots than requests -> exercises queueing + backfill
    paged = PagedEngine(cfg, max_batch=4, page_size=4, max_new_tokens=8,
                        temperature=0.0)
    got = paged.generate(params, prompts, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(want.tokens),
                                  np.asarray(got.tokens))
    np.testing.assert_array_equal(np.asarray(want.lengths),
                                  np.asarray(got.lengths))
    np.testing.assert_allclose(np.asarray(want.logprobs),
                               np.asarray(got.logprobs), atol=1e-4)
    # after the drain the only live pages are the prefix cache's; a flush
    # returns every page to the free list
    assert paged.allocator.num_allocated == paged.prefix_cache.num_pages
    paged.release_prefix_cache()
    assert paged.allocator.num_allocated == 0


@pytest.mark.parametrize("page_size", [2, 4, 16])
def test_paged_engine_parity_across_page_sizes(cfg, params, page_size):
    ds = PromptDataset(4, prompt_len=5, seed=3)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    legacy = Engine(cfg, max_new_tokens=6, temperature=0.0)
    want = np.asarray(legacy.generate(params, jnp.asarray(prompts)).tokens)
    paged = PagedEngine(cfg, max_batch=2, page_size=page_size,
                        max_new_tokens=6, temperature=0.0)
    got = np.asarray(paged.generate(params, prompts).tokens)
    np.testing.assert_array_equal(want, got)


def test_paged_engine_kernel_backed_parity(cfg, params):
    ds = PromptDataset(3, prompt_len=5, seed=2)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    ref_eng = PagedEngine(cfg, max_batch=3, page_size=4, max_new_tokens=5,
                          temperature=0.0)
    kern_eng = PagedEngine(cfg, max_batch=3, page_size=4, max_new_tokens=5,
                           temperature=0.0, use_kernel=True)
    a = ref_eng.generate(params, prompts)
    b = kern_eng.generate(params, prompts)
    np.testing.assert_array_equal(np.asarray(a.tokens), np.asarray(b.tokens))
    np.testing.assert_allclose(np.asarray(a.logprobs),
                               np.asarray(b.logprobs), atol=1e-4)


def test_paged_engine_scheduling_invariant_sampling(cfg, params):
    """Per-request RNG: results must not depend on slot count/batching."""
    ds = PromptDataset(5, prompt_len=5, seed=1)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    outs = []
    for max_batch in (2, 5):
        eng = PagedEngine(cfg, max_batch=max_batch, page_size=4,
                          max_new_tokens=6, temperature=1.0)
        outs.append(eng.generate(params, prompts,
                                 key=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(np.asarray(outs[0].tokens),
                                  np.asarray(outs[1].tokens))
    np.testing.assert_allclose(np.asarray(outs[0].logprobs),
                               np.asarray(outs[1].logprobs), atol=1e-5)


def test_paged_engine_logprobs_match_prefill_recompute(cfg, params):
    """Same contract the legacy engine honours: behaviour logprobs from
    generation equal the inference worker's recompute."""
    from repro.train import make_prefill_step

    ds = PromptDataset(4, prompt_len=6, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    eng = PagedEngine(cfg, max_batch=4, page_size=4, max_new_tokens=6,
                      temperature=1.0)
    res = eng.generate(params, prompts, key=jax.random.PRNGKey(5))
    pf = jax.jit(make_prefill_step(cfg))
    recomputed = pf(params, {"tokens": jnp.asarray(res.tokens)})
    S = prompts.shape[1]
    gen_lp = np.asarray(res.logprobs)[:, S:]
    rec_lp = np.asarray(recomputed)[:, S:]
    mask = np.asarray(res.tokens)[:, S:] != 0
    np.testing.assert_allclose(gen_lp[mask], rec_lp[mask], atol=2e-3)


def test_paged_engine_ragged_lengths_and_page_recycling(cfg, params):
    """Skewed per-request budgets: short requests leave early, pages are
    recycled, and the engine takes far fewer slot-steps than static
    padding would."""
    eng = PagedEngine(cfg, max_batch=4, page_size=4, max_new_tokens=32,
                      temperature=0.0, max_seq_len=4 + 32, num_pages=4 * 9 + 1,
                      eos_token=-1)  # never sampled: budget-driven stop
    ds = PromptDataset(8, prompt_len=4, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    budgets = [2, 2, 2, 2, 2, 2, 2, 24]
    reqs = [eng.submit(prompts[i], max_new_tokens=budgets[i], seed=i)
            for i in range(8)]
    eng.set_params(params)
    done = eng.run()
    assert len(done) == 8
    for r, b in zip(reqs, budgets):
        assert len(r.generated) == b
    eng.release_prefix_cache()
    assert eng.allocator.num_allocated == 0
    # static padding would cost 8 requests x (4 + 24) slot-steps in two
    # full batches; continuous batching re-forms the batch every step
    static_steps = 2 * (4 + 24)
    assert eng.decode_steps < static_steps


# ---------------------------------------------------------------------------
# in-flight weight sync
# ---------------------------------------------------------------------------
def test_paged_engine_inflight_weight_update_version_tags(cfg, params):
    params_v1 = jax.tree_util.tree_map(lambda x: x * 1.05, params)
    eng = PagedEngine(cfg, max_batch=2, page_size=4, max_new_tokens=6,
                      temperature=0.0, eos_token=-1)
    ds = PromptDataset(4, prompt_len=5, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    eng.set_params(params, version=0)
    reqs = [eng.submit(prompts[i], seed=i) for i in range(4)]
    # run a few steps under v0, then swap in flight
    for _ in range(3):
        eng.step()
    eng.update_weights(params_v1, version=1)
    eng.run()
    assert eng.weight_version == 1 and eng.weight_swaps == 1
    # requests admitted before the swap keep their admission tag (what
    # the staleness correction references) but record the newer weights
    early = [r for r in reqs if r.weight_version == 0]
    late = [r for r in reqs if r.weight_version == 1]
    assert early and late  # 2 slots x 4 requests straddle the swap
    assert all(r.last_weight_version == 1 for r in late)
    assert all(r.last_weight_version >= r.weight_version for r in reqs)


def test_paged_engine_requests_after_swap_match_new_params(cfg, params):
    """A request admitted after an in-flight swap must generate exactly
    what a fresh engine holding only the new weights generates."""
    params_v1 = jax.tree_util.tree_map(lambda x: x * 1.1, params)
    ds = PromptDataset(2, prompt_len=5, seed=4)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])

    eng = PagedEngine(cfg, max_batch=1, page_size=4, max_new_tokens=5,
                      temperature=0.0)
    eng.set_params(params, version=0)
    first = eng.submit(prompts[0], seed=0)
    eng.update_weights(params_v1, version=1)  # lands before any step
    second = eng.submit(prompts[1], seed=1)
    eng.run()
    assert first.weight_version == 1 and second.weight_version == 1

    fresh = PagedEngine(cfg, max_batch=1, page_size=4, max_new_tokens=5,
                        temperature=0.0)
    fresh.set_params(params_v1, version=1)
    ref2 = fresh.submit(prompts[1], seed=1)
    fresh.run()
    assert second.generated == ref2.generated


def test_rollout_worker_paged_engine_roundtrip(cfg, params):
    from repro.rl.workers import RolloutWorker

    w = RolloutWorker("rollout/t", cfg=cfg, max_new_tokens=4,
                      temperature=1.0, engine="paged", max_batch=4,
                      page_size=4)
    assert isinstance(w.engine, PagedEngine)
    w.update_weights(params, version=3)
    ds = PromptDataset(4, prompt_len=5, seed=0)
    out = w.generate({"prompt_tokens": np.asarray(
        ds.next_batch()["prompt_tokens"])})
    assert out["tokens"].shape[1] == 5 + 4
    assert (out["weight_versions"] == 3).all()
    recs = w.request_records()
    assert len(recs) == 4 and all(t >= 0 for _, t in recs)
    w.shutdown()


# ---------------------------------------------------------------------------
# prefix cache: radix trie over page-aligned token blocks
# ---------------------------------------------------------------------------
def test_prefix_cache_insert_lookup_roundtrip():
    a = PageAllocator(num_pages=16, page_size=4)
    c = PrefixCache(page_size=4)
    toks = list(range(10))  # 2 full pages + a 2-token partial leaf
    pages = a.allocate(3)
    c.insert(toks, pages, a)
    assert c.num_pages == 3
    # the trie holds one reference per indexed page (owner + cache)
    assert all(a.refcount(p) == 2 for p in pages)
    m = c.lookup(toks)
    assert [n.page for n in m.nodes] == pages[:2]
    assert m.partial is not None and m.partial.page == pages[2]
    assert m.partial_rows == 2
    # a prompt diverging after the full pages matches only those
    m2 = c.lookup(toks[:8] + [99, 98])
    assert [n.page for n in m2.nodes] == pages[:2]
    assert m2.partial is None and m2.partial_rows == 0


def test_prefix_cache_cow_candidate_from_full_page_head():
    """A prompt sharing only the leading rows of a cached FULL page gets
    that page as a copy-on-write donor, not as an adopted page."""
    a = PageAllocator(num_pages=8, page_size=4)
    c = PrefixCache(page_size=4)
    pages = a.allocate(1)
    c.insert([0, 1, 2, 3], pages, a)
    m = c.lookup([0, 1, 2, 99, 100])
    assert m.nodes == [] and m.partial is not None
    assert m.partial.page == pages[0] and m.partial_rows == 3


def test_prefix_cache_evicts_lru_leaves_first():
    a = PageAllocator(num_pages=16, page_size=2)
    c = PrefixCache(page_size=2)
    pa = a.allocate(2)
    pb = a.allocate(1)
    c.insert([0, 1, 2, 3], pa, a)
    c.insert([9, 9], pb, a)
    a.free(pa + pb)  # owners finished: only the cache's refs remain
    assert a.num_allocated == 3
    c.lookup([0, 1, 2, 3])  # touch chain A -> chain B becomes LRU
    assert c.evict(1, a) == 1
    assert c.num_pages == 2 and a.refcount(pb[0]) == 0
    # next eviction takes chain A's leaf; the parent is not a leaf yet
    assert c.evict(1, a) == 1
    assert a.refcount(pa[1]) == 0 and a.refcount(pa[0]) == 1
    # the parent became a leaf; asking for more than exists is bounded
    assert c.evict(5, a) == 1
    assert c.num_pages == 0 and a.num_allocated == 0


def test_prefix_cache_eviction_refuses_shared_and_writing_pages():
    a = PageAllocator(num_pages=8, page_size=2)
    c = PrefixCache(page_size=2)
    mine = a.allocate(1)
    c.insert([5, 6], mine, a)  # rc 2: running request + cache
    assert c.evict(1, a) == 0  # pinned by the running request
    theirs = a.allocate(1)
    c.insert([7, 8], theirs, a, writer=42)
    a.free(mine + theirs)  # both owners drop their refs
    # the page still being prefilled (writer attached) is not evictable
    assert c.evict(2, a) == 1
    assert a.refcount(theirs[0]) == 1 and a.refcount(mine[0]) == 0
    c.release_writer(42)
    assert c.evict(2, a) == 1
    assert c.num_pages == 0 and a.num_allocated == 0


def test_prefix_cache_flush_releases_everything():
    a = PageAllocator(num_pages=8, page_size=2)
    c = PrefixCache(page_size=2)
    pgs = a.allocate(3)
    c.insert([0, 1, 2, 3, 4], pgs, a, writer=7)
    a.free(pgs)
    assert a.num_allocated == 3
    assert c.flush(a) == 3
    assert c.num_pages == 0 and a.num_allocated == 0
    m = c.lookup([0, 1, 2, 3])
    assert not m.nodes and m.partial is None


# ---------------------------------------------------------------------------
# prefix sharing through the engine
# ---------------------------------------------------------------------------
def test_grpo_group_allocates_shared_prompt_pages_once(cfg, params):
    """A GRPO group's N identical prompts must prefill the prompt KV
    once: followers adopt the leader's pages through the radix cache.
    Asserted via allocator accounting, not timing."""
    ds = PromptDataset(1, prompt_len=16, seed=0)
    prompt = np.asarray(ds.next_batch()["prompt_tokens"])[0]
    group = np.stack([prompt] * 8)

    def run(sharing):
        eng = PagedEngine(cfg, max_batch=8, page_size=8, max_new_tokens=4,
                          temperature=0.0, prefix_sharing=sharing)
        out = eng.generate(params, group, key=jax.random.PRNGKey(0))
        return eng, np.asarray(out.tokens)

    shared_eng, shared_toks = run(True)
    private_eng, private_toks = run(False)
    np.testing.assert_array_equal(shared_toks, private_toks)
    # shared: 2 prompt pages allocated once + 1 decode page per request;
    # private: 3 pages x 8 requests
    assert shared_eng.allocator.pages_allocated_total == 10
    assert private_eng.allocator.pages_allocated_total == 24
    assert shared_eng.scheduler.stats.prefix_hit_tokens > 0
    assert shared_eng.scheduler.stats.prefix_shared_pages == 14  # 7 x 2


def test_prefix_cache_copy_on_write_divergent_tail(cfg, params):
    """Two prompts sharing a partial page: the second copies the shared
    rows into its own page (never mutating the cached one) and still
    generates exactly what a cold engine does."""
    ds = PromptDataset(1, prompt_len=6, seed=8)
    base = [int(t) for t in np.asarray(ds.next_batch()["prompt_tokens"])[0]]
    p2 = base[:5] + [(base[5] + 1) % 32]  # diverges inside page 2

    eng = PagedEngine(cfg, max_batch=1, page_size=4, max_new_tokens=4,
                      temperature=0.0)
    eng.set_params(params)
    eng.submit(base, seed=0)
    eng.run()
    r2 = eng.submit(p2, seed=1)
    eng.run()
    assert eng.scheduler.stats.cow_pages >= 1

    cold = PagedEngine(cfg, max_batch=1, page_size=4, max_new_tokens=4,
                       temperature=0.0, prefix_sharing=False)
    cold.set_params(params)
    c2 = cold.submit(p2, seed=1)
    cold.run()
    assert r2.generated == c2.generated
    np.testing.assert_allclose(r2.logprobs, c2.logprobs, atol=1e-5)


def test_preempt_resume_with_shared_prefix_pages(cfg, params):
    """Preempting a request that holds shared (ref-counted) pages must
    decref rather than blind-free: the survivors keep decoding from the
    shared prefix, the victim replays deterministically on resume, and
    the pool drains to exactly the cache-held pages."""
    ds = PromptDataset(1, prompt_len=8, seed=9)
    prompt = np.asarray(ds.next_batch()["prompt_tokens"])[0]
    group = np.stack([prompt] * 3)

    def run(num_pages):
        eng = PagedEngine(cfg, max_batch=3, page_size=4, max_seq_len=32,
                          max_new_tokens=20, temperature=1.0,
                          num_pages=num_pages, eos_token=-1)
        out = eng.generate(params, group, key=jax.random.PRNGKey(11))
        eng.release_prefix_cache()
        assert eng.allocator.num_allocated == 0
        return eng, np.asarray(out.tokens)

    tight_eng, tight = run(num_pages=12)   # 11 usable << 20-page peak
    roomy_eng, roomy = run(num_pages=None)
    assert tight_eng.scheduler.stats.preempted > 0
    assert roomy_eng.scheduler.stats.preempted == 0
    np.testing.assert_array_equal(tight, roomy)


def test_chunked_prefill_parity_and_deferral_accounting(cfg, params):
    """A tiny per-step prefill budget must spread prompt ingestion over
    steps — counting the deferred tokens — without changing a single
    sampled token or logprob."""
    ds = PromptDataset(3, prompt_len=24, seed=5)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])

    def run(chunk):
        eng = PagedEngine(cfg, max_batch=3, page_size=4, max_new_tokens=5,
                          temperature=1.0, prefill_chunk=chunk)
        return eng, eng.generate(params, prompts, key=jax.random.PRNGKey(2))

    small_eng, small = run(8)
    big_eng, big = run(256)
    np.testing.assert_array_equal(np.asarray(small.tokens),
                                  np.asarray(big.tokens))
    np.testing.assert_allclose(np.asarray(small.logprobs),
                               np.asarray(big.logprobs), atol=1e-5)
    assert small_eng.scheduler.stats.chunk_deferred_tokens > 0
    assert big_eng.scheduler.stats.chunk_deferred_tokens == 0


@pytest.mark.parametrize("chunk", [4, 32])
def test_chunked_prefill_matches_decode_only_prefill(cfg, params, chunk):
    """Prompts written into the page pool by prefill chunks, then decoded,
    sample the tokens and logprobs of prompts fed one position per decode
    step (``prefill_chunk=0``): both paths write and read the same rows."""
    ds = PromptDataset(3, prompt_len=13, seed=4)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])

    def run(c):
        eng = PagedEngine(cfg, max_batch=2, page_size=4, max_new_tokens=6,
                          temperature=1.0, prefill_chunk=c)
        return eng.generate(params, prompts, key=jax.random.PRNGKey(3))

    chunked, decode_only = run(chunk), run(0)
    np.testing.assert_array_equal(np.asarray(chunked.tokens),
                                  np.asarray(decode_only.tokens))
    np.testing.assert_allclose(np.asarray(chunked.logprobs),
                               np.asarray(decode_only.logprobs), atol=1e-5)


def test_serve_metrics_surface_under_tracing(cfg, params):
    """The serve-tier counters/gauges only record while tracing is
    armed, and land in the default registry under serve/ and engine/."""
    from repro.obs import default_registry, tracing

    ds = PromptDataset(1, prompt_len=16, seed=0)
    prompt = np.asarray(ds.next_batch()["prompt_tokens"])[0]
    group = np.stack([prompt] * 4)
    eng = PagedEngine(cfg, max_batch=4, page_size=8, max_new_tokens=3,
                      temperature=0.0)
    default_registry().clear()
    try:
        with tracing():
            eng.generate(params, group, key=jax.random.PRNGKey(0))
        snap = default_registry().snapshot()
        assert snap["serve/prefix_hit_tokens"]["value"] > 0
        assert snap["serve/radix_pages"]["max"] > 0
        assert snap["engine/page_util"]["max"] > 0
    finally:
        default_registry().clear()


def test_engine_step_span_holds_prefill_and_decode_spans(cfg, params):
    """Traced, each engine step is one span; its chunked prefills and
    its batch decode are child spans inside it, and the step's args say
    what it advanced."""
    from repro.obs import tracing

    ds = PromptDataset(3, prompt_len=6, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    eng = PagedEngine(cfg, max_batch=2, page_size=4, max_new_tokens=3,
                      temperature=0.0, prefill_chunk=4,
                      prefix_sharing=False)
    with tracing() as tr:
        eng.generate(params, prompts, key=jax.random.PRNGKey(0))
    spans = tr.spans("engine")
    steps = [s for s in spans if s.name == "engine-step"]
    kids = [s for s in spans if s.name in ("engine-prefill", "engine-decode")]
    assert len(steps) == eng.decode_steps
    assert {s.name for s in kids} == {"engine-prefill", "engine-decode"}
    for k in kids:
        assert sum(s.t0 <= k.t0 and k.t1 <= s.t1 for s in steps) == 1, k
    assert sum(s.name == "engine-decode" for s in kids) == sum(
        s.args["decode"] + s.args["prefill"] > 0 for s in steps)
    assert sum(s.args["chunked"] for s in steps) == sum(
        s.args["tokens"] for s in kids if s.name == "engine-prefill")


# ---------------------------------------------------------------------------
# profiler: measured tail factor
# ---------------------------------------------------------------------------
def test_fit_tail_factor_known_values():
    assert fit_tail_factor([1.0, 1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert fit_tail_factor([1.0, 1.0, 6.0]) == pytest.approx(6.0 / (8 / 3))
    assert fit_tail_factor([]) == 1.0


def test_engine_cost_model_fits_measured_tail(cfg, params):
    eng = PagedEngine(cfg, max_batch=4, page_size=4, max_new_tokens=16,
                      temperature=0.0, eos_token=-1)
    ds = PromptDataset(4, prompt_len=4, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])
    eng.set_params(params)
    # warm-up request so the jitted step compiles outside the measurement
    eng.submit(prompts[0], max_new_tokens=1, seed=99)
    eng.run()
    eng.pop_request_records()
    for i, budget in enumerate([2, 2, 2, 12]):
        eng.submit(prompts[i], max_new_tokens=budget, seed=i)
    eng.run()
    recs = eng.pop_request_records()
    cm = engine_cost_model("rollout", recs)
    # the skewed budgets must surface as a measured long tail
    assert cm.tail_factor > 1.2
    assert cm.slope_time >= 0.0
    # the log is consumed
    assert eng.pop_request_records() == []


def test_paged_engine_preempts_on_page_exhaustion(cfg, params):
    """A pool too small for the whole batch must trigger recompute
    preemption (youngest request yields), not crash — and the output must
    be identical to an uncontended run (deterministic per-request RNG +
    teacher-forced replay)."""
    ds = PromptDataset(4, prompt_len=6, seed=0)
    prompts = np.asarray(ds.next_batch()["prompt_tokens"])

    def run(num_pages):
        eng = PagedEngine(cfg, max_batch=4, page_size=4, max_seq_len=32,
                          max_new_tokens=24, temperature=1.0,
                          num_pages=num_pages, eos_token=-1)
        eng.set_params(params)
        reqs = [eng.submit(prompts[i], seed=i) for i in range(4)]
        eng.run()
        eng.release_prefix_cache()
        assert eng.allocator.num_allocated == 0
        return eng, [r.generated for r in reqs]

    tight_eng, tight_out = run(num_pages=10)   # 9 usable pages < 4 seqs
    roomy_eng, roomy_out = run(num_pages=None)  # full-occupancy pool
    assert tight_eng.scheduler.stats.preempted > 0
    assert roomy_eng.scheduler.stats.preempted == 0
    assert tight_out == roomy_out
    for out in tight_out:
        assert len(out) == 24
