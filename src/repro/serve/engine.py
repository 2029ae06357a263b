"""Rollout/serving engines: static batch (legacy) and paged continuous.

Two engines implement the "rollout worker" compute of the M2Flow runtime
(the paper's SGLang/vLLM role):

* :class:`Engine` — the original fixed-shape engine: one ``lax.scan``
  over ``max_new_tokens`` with a per-sequence `done` mask.  Every request
  is padded to the longest response, so devices idle behind the long
  tail (paper Fig. 2).
* :class:`PagedEngine` — continuous batching over a paged KV cache: the
  decode batch is re-formed every step (finished requests immediately
  free their pages, queued prompts backfill), attention reads the cache
  through per-request block tables (optionally via the Pallas
  paged-attention kernel), and trainer weight updates apply *in flight*
  at step boundaries with per-request version tags preserved for the
  staleness correction.

Both return per-token *behaviour logprobs* so the trainer can form
importance ratios without a separate inference pass when the collocated
mode is chosen (one-forward-pass trick, §5.3).
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.attention import NEG_INF
from repro.serve import layouts as layouts_mod
from repro.serve.paging import (
    OutOfPages,
    PageAllocator,
    PrefixCache,
    pad_block_table,
)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.serve.sampling import sample_token
from repro.serve.scheduler import RUNNING, ContinuousScheduler, Request


class GenerationResult(NamedTuple):
    tokens: jax.Array  # (B, S_total) prompt + generated (PAD after EOS)
    logprobs: jax.Array  # (B, S_total) behaviour logprob per token (0 on prompt)
    lengths: jax.Array  # (B,) total valid length
    done: jax.Array  # (B,) bool — hit EOS before max tokens
    # weight version each request was admitted under (all zeros on the
    # legacy engine; the paged engine tags every request so the staleness
    # correction can reference the actual behaviour-policy version)
    weight_versions: Optional[np.ndarray] = None


def _sample(key, logits: jax.Array, temperature: float, vocab_size: int,
            top_k: int = 0, top_p: float = 1.0):
    """Categorical sample with padded-vocab masking; temp<=0 = greedy."""
    return sample_token(key, logits, temperature=temperature, top_k=top_k,
                        top_p=top_p, vocab_size=vocab_size)


class Engine:
    """Owns jitted prefill/decode functions for one model config."""

    def __init__(self, cfg: ModelConfig, *, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token: int = 2,
                 pad_token: int = 0):
        self.cfg = cfg
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos = eos_token
        self.pad = pad_token
        self._generate = jax.jit(self._generate_impl, static_argnames=("B", "S"))
        self._act = None  # lazily jitted closed-loop action path

    # ------------------------------------------------------------------
    def _generate_impl(self, params, prompt_tokens, prompt_lens, key, *,
                       B: int, S: int):
        cfg = self.cfg
        total = S + self.max_new_tokens
        state = M.init_decode_state(cfg, B, total)

        # ---- prefill the (left-padded) prompt ----
        logits, state = M.prefill(params, cfg, prompt_tokens, state)
        last = logits[:, 0]  # (B, V)

        out_tokens = jnp.concatenate(
            [prompt_tokens,
             jnp.full((B, self.max_new_tokens), self.pad, jnp.int32)], axis=1)
        out_lp = jnp.zeros((B, total), jnp.float32)

        def step(carry, i):
            state, last, toks, lps, done, key = carry
            key, sub = jax.random.split(key)
            tok, lp = _sample(sub, last, self.temperature, cfg.vocab_size,
                              top_k=self.top_k, top_p=self.top_p)
            tok = jnp.where(done, self.pad, tok)
            lp = jnp.where(done, 0.0, lp)
            pos = S + i
            toks = jax.lax.dynamic_update_slice(toks, tok[:, None], (0, pos))
            lps = jax.lax.dynamic_update_slice(lps, lp[:, None], (0, pos))
            newdone = done | (tok == self.eos)
            logits, state = M.decode_step(params, cfg, tok[:, None], state, pos)
            return (state, logits[:, 0], toks, lps, newdone, key), None

        done0 = jnp.zeros((B,), bool)
        (state, last, out_tokens, out_lp, done, _), _ = jax.lax.scan(
            step, (state, last, out_tokens, out_lp, done0, key),
            jnp.arange(self.max_new_tokens))
        lengths = S + jnp.sum(
            (out_tokens[:, S:] != self.pad).astype(jnp.int32), axis=1)
        return GenerationResult(out_tokens, out_lp, lengths, done)

    # ------------------------------------------------------------------
    def generate(self, params, prompt_tokens, prompt_lens=None,
                 key=None) -> GenerationResult:
        """prompt_tokens: (B, S) int32 left-padded prompts."""
        B, S = prompt_tokens.shape
        if key is None:
            key = jax.random.PRNGKey(0)
        if prompt_lens is None:
            prompt_lens = jnp.full((B,), S, jnp.int32)
        return self._generate(params, prompt_tokens, prompt_lens, key, B=B, S=S)

    # ------------------------------------------------------------------
    # per-step constrained action sampling (the embodied cycle's path)
    # ------------------------------------------------------------------
    def _act_impl(self, params, prompt_tokens, env_keys, *, lo: int,
                  hi: int):
        logits, _ = M.forward(params, self.cfg, prompt_tokens)
        last = logits[:, -1].astype(jnp.float32)
        idx = jnp.arange(last.shape[-1])
        last = jnp.where((idx >= lo) & (idx < hi), last, NEG_INF)
        # one key PER ROW: sampling is invariant to how the env batch is
        # chunked (the hybrid cycle realization splits it), so collocated
        # and hybrid execution draw identical actions
        toks = jax.vmap(jax.random.categorical)(env_keys, last)
        lse = jax.nn.logsumexp(last, axis=-1)
        lps = jnp.take_along_axis(last, toks[:, None], axis=-1)[:, 0] - lse
        return toks.astype(jnp.int32), lps.astype(jnp.float32)

    def act(self, params, prompt_tokens, env_keys, *, action_lo: int,
            action_hi: int):
        """One closed-loop policy step: a single prefill forward, logits
        masked to the action-token window ``[action_lo, action_hi)``,
        per-row categorical sampling under explicit per-env keys.
        Returns (action_tokens (B,), behaviour logprobs (B,))."""
        if self._act is None:
            self._act = jax.jit(self._act_impl,
                                static_argnames=("lo", "hi"))
        return self._act(params, jnp.asarray(prompt_tokens), env_keys,
                         lo=action_lo, hi=action_hi)


# ===========================================================================
# Continuous-batching engine over per-architecture cache layouts
# ===========================================================================
class PagedEngine:
    """Continuous-batching rollout engine with a paged KV cache.

    The engine advances *all* active requests by one token per
    :meth:`step` — mixed prefill/decode (Orca-style iteration-level
    scheduling): a request still consuming its prompt is teacher-forced,
    one past it feeds back its sampled token.  The jitted step runs over
    ``max_batch`` fixed slots (inactive slots write to the reserved trash
    page and are ignored on the host), so one compilation serves every
    batch composition the scheduler produces.

    Weight sync: :meth:`update_weights` enqueues a versioned update that
    is applied at the next step boundary *without draining the engine* —
    running requests keep their pages and simply continue under the new
    weights; each request records the version it was admitted under
    (``weight_version``, what the staleness correction references) and
    the newest version that produced any of its tokens
    (``last_weight_version``).

    Sampling is per-request deterministic: token ``i`` of request ``r``
    is drawn from ``fold_in(PRNGKey(r.seed), position)``, so results do
    not depend on how requests were batched together.
    """

    def __init__(self, cfg: ModelConfig, *, max_batch: int = 8,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 max_new_tokens: int = 32, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, eos_token: int = 2,
                 pad_token: int = 0, use_kernel: bool = False,
                 prefix_sharing: bool = True, prefill_chunk: int = 32,
                 use_sampling_kernel: Optional[bool] = None,
                 dtype=jnp.float32):
        layout_cls = layouts_mod.layout_class(cfg)
        if layout_cls is None:
            raise NotImplementedError(
                "PagedEngine does not window the paged cache yet"
                if cfg.sliding_window else
                f"PagedEngine has no cache layout for kind={cfg.kind}")
        self.cfg = cfg
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos = eos_token
        self.pad = pad_token
        self.use_kernel = use_kernel
        # fused sampling: like use_kernel, the Pallas path only pays off
        # compiled; default ON on TPU, OFF under CPU interpret mode
        if use_sampling_kernel is None:
            use_sampling_kernel = jax.default_backend() == "tpu"
        self.use_sampling_kernel = use_sampling_kernel
        # per-step prompt-token budget for chunked prefill (0 = legacy
        # token-by-token prefill through the decode step)
        self.prefill_chunk = (int(prefill_chunk)
                              if layout_cls.supports_chunked_prefill else 0)
        if layout_cls.uses_pages:
            self.max_blocks = -(-self.max_seq_len // page_size)
            # default pool: every slot holds a full sequence (+ trash page)
            if num_pages is None:
                num_pages = max_batch * self.max_blocks + 1
            # the pool must at least hold ONE full sequence, or the oldest
            # request could never finish even with everyone else preempted
            assert num_pages - 1 >= self.max_blocks, (num_pages,
                                                      self.max_blocks)
        else:
            # constant-size layouts keep the allocator as an inert stub
            # (page_size still parameterizes host bookkeeping); requests
            # cost zero pages, so the pool size is irrelevant
            self.max_blocks = 1
            if num_pages is None:
                num_pages = 2
        self.allocator = PageAllocator(num_pages=num_pages,
                                       page_size=page_size)
        # the radix trie (full-page adoption + partial-page COW) only
        # attaches to layouts that can honour it; state layouts do their
        # own exact-full-prompt snapshot reuse instead
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(page_size)
            if prefix_sharing and layout_cls.supports_partial_cow else None)
        self.layout = layout_cls(
            cfg, max_batch=max_batch, page_size=page_size,
            num_pages=num_pages, max_blocks=self.max_blocks,
            max_seq_len=self.max_seq_len, temperature=temperature,
            top_k=top_k, top_p=top_p, use_kernel=use_kernel,
            use_sampling_kernel=self.use_sampling_kernel, dtype=dtype,
            prefix_cache=self.prefix_cache, prefix_sharing=prefix_sharing)
        self.scheduler = ContinuousScheduler(
            max_batch=max_batch, allocator=self.allocator,
            max_seq_len=self.max_seq_len, prefix_cache=self.prefix_cache,
            cost_model=self.layout.cost_model(),
            preempt_keeps_progress=self.layout.preempt_keeps_progress)
        # -- weights + in-flight sync --------------------------------------
        self.params: Any = None
        self.weight_version: int = 0
        self._pending: deque = deque()  # (version, params), newest wins
        self._sync_lock = threading.Lock()
        self.weight_swaps = 0
        # -- bookkeeping ----------------------------------------------------
        # bounded: records feed the profiler's tail fit; without a
        # consumer the log must not grow for the life of the worker
        self.finished_log: deque = deque(maxlen=4096)
        self.decode_steps = 0

    @property
    def cache(self):
        """The layout's device cache (a :class:`PagedKVCache` for KV
        layouts, a stacked :class:`repro.models.model.DecodeState` for
        state layouts)."""
        return self.layout.cache

    # ------------------------------------------------------------------
    # weights
    # ------------------------------------------------------------------
    def set_params(self, params: Any, version: Optional[int] = None) -> None:
        """Apply immediately (initial load / synchronous callers)."""
        self.params = params
        if version is not None:
            self.weight_version = version

    def update_weights(self, params: Any,
                       version: Optional[int] = None) -> None:
        """Enqueue an in-flight update; applied at the next step boundary.
        Thread-safe — the trainer may call this while the engine loop is
        mid-generation."""
        with self._sync_lock:
            if version is None:
                # auto-version past any still-pending update, or two
                # back-to-back enqueues would share one tag for
                # different parameter sets
                base = self._pending[-1][0] if self._pending \
                    else self.weight_version
                version = base + 1
            self._pending.append((version, params))

    def rebind_devices(self, sharding) -> None:
        """Re-place the engine's device-resident state — page pools,
        applied params, pending updates — onto ``sharding``.  Called when
        the execution plan rebinds the rollout worker's device slice: the
        KV pool must live where the weights live, or the jitted step sees
        inputs committed to incompatible device sets."""
        def put(tree):
            return jax.tree_util.tree_map(
                lambda x: (jax.device_put(x, sharding)
                           if isinstance(x, jax.Array) else x), tree)

        with self._sync_lock:
            self.layout.rebind(sharding)
            if self.params is not None:
                self.params = put(self.params)
            self._pending = deque(
                (v, put(p)) for v, p in self._pending)

    def _apply_pending(self) -> None:
        # params/weight_version are written under the lock: update_weights
        # reads weight_version to auto-assign the next version, so an
        # unlocked write could hand the same tag to two parameter sets
        with self._sync_lock:
            if not self._pending:
                return
            version, params = self._pending[-1]  # newest update wins
            skipped = len(self._pending) - 1
            self._pending.clear()
            self.params = params
            self.weight_version = version
            self.weight_swaps += 1 + skipped
        # cached prefixes were computed under the OLD weights: a request
        # admitted after the swap must not adopt stale KV.  Running
        # requests keep their pages (in-flight sync semantics); only the
        # cache's own references are dropped.
        if self.prefix_cache is not None:
            self.prefix_cache.flush(self.allocator)
        self.layout.on_weight_swap()
        tr = _trace.active()
        if tr is not None:
            tr.instant("weight-swap", "engine", version=version,
                       skipped=skipped)
            reg = _metrics.active()
            if reg is not None:
                reg.counter("engine/weight_swaps").inc(1 + skipped)

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               seed: int = 0) -> Request:
        return self.scheduler.submit(
            list(int(t) for t in prompt),
            max_new_tokens if max_new_tokens is not None
            else self.max_new_tokens,
            seed=seed, weight_version=self.weight_version)

    # ------------------------------------------------------------------
    # host-side engine loop
    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit, advance every active request, join/evict.  Returns the
        number of requests advanced (chunk-prefilled or decoded).

        Per step: pending COW copies run first, then each request (rid
        order) fast-forwards ``num_cached`` through shared pages as far
        as their computed watermarks allow, requests blocked behind an
        in-flight writer of their shared prefix sit the step out, the
        remaining prompt work is chunk-prefilled under the
        ``prefill_chunk`` token budget, and everyone at the sampling
        frontier decodes one token in the fixed-shape batch.

        Traced, the step is one ``engine-step`` span holding an
        ``engine-prefill`` span per prefill chunk and an
        ``engine-decode`` span from the batch step's dispatch until its
        sampled tokens reach the host: the rest of the step is host
        bookkeeping."""
        tr = _trace.active()
        if tr is None:
            return self._step(None, None)
        with tr.span("engine-step", "engine") as span_args:
            return self._step(tr, span_args)

    def _step(self, tr: Optional[_trace.Tracer],
              span_args: Optional[Dict[str, Any]]) -> int:
        reg = _metrics.active()
        self._apply_pending()  # before the check: update_weights() alone
        # is a valid way to deliver the initial weights
        assert self.params is not None, "engine weights not initialized"
        joined = self.scheduler.admit(weight_version=self.weight_version)
        for q in joined:
            # layout-private admission work: slot reset / snapshot
            # restore / exact-prefix-match reuse (state layouts)
            skipped = self.layout.on_admit(q)
            if skipped:
                self.scheduler.stats.prefix_hit_tokens += skipped
                if reg is not None:
                    reg.counter("serve/prefix_hit_tokens").inc(skipped)
        self._perform_cow_copies()
        self._grow_pages_or_preempt()
        reqs = self.scheduler.active_requests()
        if tr is not None:
            util = (self.allocator.num_allocated
                    / max(self.allocator.num_pages, 1))
            tr.counter("engine/page_util", util)
            if reg is not None:
                reg.gauge("engine/page_util").set(util)
                if self.prefix_cache is not None:
                    reg.gauge("serve/radix_pages").set(
                        self.prefix_cache.num_pages)
        if not reqs:
            if tr is not None:
                span_args.update(advanced=0, prefill=0, decode=0, chunked=0)
            return 0
        budget = self.prefill_chunk
        chunked_tokens = 0
        chunk_only = 0  # advanced by chunk but not yet at the frontier
        deferred = 0
        decode_reqs: List[Request] = []
        waiting: List[Request] = []
        for r in sorted(reqs, key=lambda q: q.rid):
            skipped = self._fast_forward(r)
            if skipped and reg is not None:
                reg.counter("serve/prefix_hit_tokens").inc(skipped)
            if self._waiting_on_writer(r):
                # the shared page under our cursor is still being filled
                # by its writer; wait instead of duplicating its prefill
                waiting.append(r)
                continue
            if self.prefill_chunk > 0 and r.num_cached < r.total_len - 1:
                need = r.total_len - 1 - r.num_cached
                grant = min(need, budget)
                if grant > 0:
                    if tr is None:
                        self._prefill_chunk_step(r, grant)
                    else:
                        with tr.span("engine-prefill", "engine", rid=r.rid,
                                     tokens=grant):
                            self._prefill_chunk_step(r, grant)
                    budget -= grant
                    chunked_tokens += grant
                    # a chunk may complete up to a watermark another
                    # sharer extended meanwhile
                    self._fast_forward(r)
                if r.num_cached < r.total_len - 1:
                    deferred += r.total_len - 1 - r.num_cached
                    chunk_only += 1 if grant > 0 else 0
                    continue  # still mid-prompt: no frontier this step
            decode_reqs.append(r)
        if not decode_reqs and chunked_tokens == 0 and waiting:
            # safety valve: never let the whole step idle on writers
            # (cannot happen under the acyclic wait order, but a stalled
            # step here would be an infinite loop in run())
            decode_reqs = waiting
        if decode_reqs:
            B = self.max_batch
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.zeros((B, self.max_blocks), np.int32)  # trash page
            seeds = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            for r in decode_reqs:
                pos = r.num_cached
                if pos < r.prompt_len:
                    tokens[r.slot] = r.prompt[pos]
                else:
                    tokens[r.slot] = r.generated[pos - r.prompt_len]
                positions[r.slot] = pos
                if r.pages:
                    tables[r.slot] = pad_block_table(r.pages,
                                                     self.max_blocks)
                seeds[r.slot] = r.seed
                active[r.slot] = True
            if tr is None:
                tok_np, lp_np = self._decode(tokens, positions, tables,
                                             seeds, active)
            else:
                with tr.span("engine-decode", "engine",
                             batch=len(decode_reqs)):
                    tok_np, lp_np = self._decode(tokens, positions, tables,
                                                 seeds, active)
            for r in decode_reqs:
                pos = r.num_cached
                r.num_cached += 1
                r.last_weight_version = self.weight_version
                if r.pages:
                    page = self.page_size
                    self.allocator.note_computed(r.pages[pos // page],
                                                 pos % page + 1)
                self.layout.note_progress(r)
                # sample only at the frontier: during prompt prefill AND
                # during post-preemption replay of already-generated
                # tokens the step is teacher-forced and its sampled token
                # is discarded
                if pos == r.total_len - 1 and pos >= r.prompt_len - 1:
                    t = int(tok_np[r.slot])
                    r.generated.append(t)
                    r.logprobs.append(float(lp_np[r.slot]))
                    if t == self.eos or len(r.generated) >= r.max_new_tokens:
                        r.hit_eos = t == self.eos
                        # only index KV produced wholly under the current
                        # weights — spans of a mid-flight swap are stale
                        idx = r.weight_version == self.weight_version
                        self.layout.on_finish(r, index_in_cache=idx)
                        self.scheduler.finish(r, index_in_cache=idx)
        if deferred:
            self.scheduler.stats.chunk_deferred_tokens += deferred
            if reg is not None:
                reg.counter("serve/prefill_chunk_deferred").inc(deferred)
        self.decode_steps += 1
        self.scheduler.stats.steps += 1
        advanced = len(decode_reqs) + chunk_only
        if tr is not None:
            # num_cached already advanced: a slot still inside its prompt
            # was a prefill (teacher-forced) step, the rest decoded
            prefill = sum(1 for r in decode_reqs
                          if r.num_cached < r.prompt_len)
            span_args.update(advanced=advanced, prefill=prefill,
                             decode=len(decode_reqs) - prefill,
                             chunked=chunked_tokens)
        return advanced

    def _decode(self, tokens, positions, tables, seeds, active):
        """One batch step of every slot; returns the sampled tokens and
        their logprobs on the host."""
        tok, lp = self.layout.step(self.params, tokens, positions, tables,
                                   seeds, active)
        return np.asarray(tok), np.asarray(lp)

    # ------------------------------------------------------------------
    # prefix sharing + chunked prefill plumbing
    # ------------------------------------------------------------------
    def _fast_forward(self, r: Request) -> int:
        """Advance ``num_cached`` through the shared-prefix region as far
        as the adopted pages' computed watermarks allow (never past the
        sampling frontier).  Returns the number of positions skipped —
        prompt tokens this request will never prefill."""
        if r.shared_len <= r.num_cached:
            return 0
        page = self.page_size
        ceiling = min(r.shared_len, r.total_len - 1)
        skipped = 0
        while r.num_cached < ceiling:
            pidx = r.num_cached // page
            avail = pidx * page + self.allocator.computed_rows(
                r.pages[pidx])
            if avail <= r.num_cached:
                break
            new = min(avail, ceiling)
            skipped += new - r.num_cached
            r.num_cached = new
        if skipped:
            self.scheduler.stats.prefix_hit_tokens += skipped
        return skipped

    def _waiting_on_writer(self, r: Request) -> bool:
        """True when the shared page under the request's cursor is still
        being prefilled by another running request (the trie writer):
        the follower waits for watermarks to advance instead of
        recomputing rows the writer will produce anyway."""
        if r.num_cached >= min(r.shared_len, r.total_len - 1):
            return False
        pidx = r.num_cached // self.page_size
        if pidx >= len(r.shared_nodes):
            return False  # COW tail: those rows are ours to compute
        writer = r.shared_nodes[pidx].writer
        return writer is not None and writer != r.rid

    def _perform_cow_copies(self) -> None:
        """Run the device copies the scheduler planned at admission: the
        computed rows of a shared partial page land in the request's
        private page, the watermark follows, and the pinned source is
        released (decref)."""
        for r in self.scheduler.active_requests():
            if r.pending_cow is None:
                continue
            src, dst, rows = r.pending_cow
            self.layout.cow(src, dst)
            self.allocator.note_computed(dst, rows)
            self.allocator.free([src])  # release the admission pin
            r.pending_cow = None
            reg = _metrics.active()
            if reg is not None:
                reg.counter("serve/cow_pages").inc()

    def _prefill_chunk_step(self, r: Request, grant: int) -> None:
        """Cache ``grant`` positions of request ``r`` starting at
        ``num_cached`` in one jitted forward (prompt tokens, or generated
        tokens during post-preemption replay) and advance the watermarks
        so sharers can fast-forward behind us."""
        start = r.num_cached
        end = start + grant
        C = self.prefill_chunk
        toks = np.zeros((C,), np.int32)
        poss = np.zeros((C,), np.int32)
        for i, pos in enumerate(range(start, end)):
            toks[i] = (r.prompt[pos] if pos < r.prompt_len
                       else r.generated[pos - r.prompt_len])
            poss[i] = pos
        self.layout.prefill_chunk_step(self.params, toks, poss, grant, r)
        r.num_cached = end
        r.last_weight_version = self.weight_version
        if r.pages:
            page = self.page_size
            for pidx in range(start // page, (end - 1) // page + 1):
                self.allocator.note_computed(
                    r.pages[pidx], min(end - pidx * page, page))
        self.layout.note_progress(r)

    def release_prefix_cache(self) -> int:
        """Drop every cache-held page reference (tests, memory pressure,
        or an explicit reset between workloads).  Running requests keep
        theirs.  Returns the number of trie nodes dropped."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.flush(self.allocator)

    def _grow_pages_or_preempt(self) -> None:
        """Back every active request's next slot with a page.  When the
        pool runs dry, preempt the YOUNGEST active request (freeing all
        its pages; it re-queues at the head and recomputes on resume) so
        the oldest requests always make progress — admission guarantees
        a lone request fits, so this cannot livelock."""
        for r in sorted(self.scheduler.active_requests(),
                        key=lambda r: r.rid):
            if r.state != RUNNING:  # preempted earlier in this loop
                continue
            while True:
                try:
                    self.scheduler.ensure_page_for(r)
                    break
                except OutOfPages:
                    victims = [v for v in self.scheduler.active_requests()
                               if v.rid > r.rid]
                    victim = max(victims, key=lambda v: v.rid) if victims \
                        else r  # r itself is youngest: it yields
                    self.preempt_request(victim)
                    if victim is r:
                        break

    def preempt_request(self, victim: Request) -> None:
        """Preempt one running request: the layout snapshots or forgets
        its cache state (per its preemption policy), then the scheduler
        requeues it at the head."""
        self.layout.on_preempt(victim)
        self.scheduler.preempt(victim)
        tr = _trace.active()
        if tr is not None:
            tr.instant("preempt", "engine", rid=victim.rid)
            reg = _metrics.active()
            if reg is not None:
                reg.counter("engine/preemptions").inc()

    def run(self) -> List[Request]:
        """Drive until the queue and the running set are both empty."""
        while self.scheduler.has_work:
            self.step()
        done, self.scheduler.finished = self.scheduler.finished, []
        self.finished_log.extend(done)
        return done

    # ------------------------------------------------------------------
    # batch-compatible front end (drop-in for Engine.generate)
    # ------------------------------------------------------------------
    def generate(self, params, prompt_tokens, prompt_lens=None,
                 key=None) -> GenerationResult:
        """prompt_tokens: (B, S) int32; returns the legacy layout padded
        to ``S + max_new_tokens`` so downstream RL code is unchanged."""
        if params is not None:
            self.set_params(params, self.weight_version)
        if key is None:
            key = jax.random.PRNGKey(0)
        base_seed = int(jax.random.randint(key, (), 0, 2**31 - 1))
        prompts = np.asarray(prompt_tokens)
        B, S = prompts.shape
        # mask keeps base_seed + i inside int32 (the jitted step's seeds)
        reqs = [self.submit(prompts[i], seed=(base_seed + i) & 0x7FFFFFFF)
                for i in range(B)]
        self.run()
        return self._collect(reqs, S)

    def _collect(self, reqs: List[Request], S: int) -> GenerationResult:
        B = len(reqs)
        total = S + self.max_new_tokens
        tokens = np.full((B, total), self.pad, np.int32)
        logprobs = np.zeros((B, total), np.float32)
        lengths = np.zeros((B,), np.int32)
        done = np.zeros((B,), bool)
        versions = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            tokens[i, :S] = r.prompt
            n = len(r.generated)
            tokens[i, S:S + n] = r.generated
            logprobs[i, S:S + n] = r.logprobs
            lengths[i] = S + n
            done[i] = r.hit_eos
            versions[i] = r.weight_version
        return GenerationResult(
            tokens=jnp.asarray(tokens), logprobs=jnp.asarray(logprobs),
            lengths=jnp.asarray(lengths), done=jnp.asarray(done),
            weight_versions=versions)

    # ------------------------------------------------------------------
    # measurement (feeds the profiler's fitted tail factor)
    # ------------------------------------------------------------------
    def pop_request_records(self) -> List[Tuple[int, float]]:
        """(generated_tokens, service_seconds) per finished request;
        clears the log."""
        recs = [(len(r.generated), r.service_time())
                for r in self.finished_log]
        self.finished_log.clear()
        return recs
