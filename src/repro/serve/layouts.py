"""Per-architecture cache layouts behind one serve-tier interface.

The continuous-batching engine (:class:`repro.serve.engine.PagedEngine`)
is host-side scheduling — admission, chunk budgets, preemption, weight
sync — over a device cache whose *shape* depends on the architecture:

* :class:`PagedKVLayout` — the classic vLLM layout: a lane-dense
  (L, P, page, KV * hd) page pool addressed through per-request block
  tables.  Pages grow with every decoded token, preemption recomputes,
  and the radix prefix trie can share full pages and copy-on-write
  partial ones.
* :class:`MoEPagedKVLayout` — same KV pool; the FFN half of each layer
  routes through the exact top-k expert combine (optionally the grouped
  per-expert decode GEMM kernel, ``kernels.ops.moe_decode``).
* :class:`StateCacheLayout` — SSM/hybrid stacks: one constant-size
  recurrent state (Mamba2 SSD state + conv window, plus the hybrid
  shared-attention KV ring) per slot.  No page growth during decode,
  preemption *snapshots* the state instead of recomputing, and prefix
  reuse happens only on an exact full-prompt match — SSD state is
  position-dependent, so partial-prefix copy-on-write is structurally
  impossible here (constructing this layout with a
  :class:`~repro.serve.paging.PrefixCache` raises :class:`LayoutError`).

The engine asks the layout for its scheduler cost model
(:class:`~repro.serve.scheduler.KVPageCost` vs
:class:`~repro.serve.scheduler.NullPageCost`), so admission/page-budget
math, chunked prefill, and preemption run unchanged across layouts.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DENSE, HYBRID, MOE, SSM, ModelConfig
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models.attention import NEG_INF, KVCache, qkv_project, sdpa
from repro.models.layers import apply_rope, embed, mlp, rmsnorm, unembed
from repro.models.ssm import SSMState
from repro.serve.paging import (
    TRASH_PAGE,
    PagedKVCache,
    PrefixCache,
    init_paged_cache,
    pad_block_table,
)
from repro.serve.sampling import sample_token, sample_tokens_fused
from repro.serve.scheduler import KVPageCost, NullPageCost, Request


class LayoutError(TypeError):
    """A cache layout was constructed with machinery it cannot honour
    (e.g. a state-cache layout with a partial-page COW prefix trie)."""


class CacheLayout:
    """Device-cache strategy for one model architecture.

    Subclasses own the jitted step/prefill compute and the cache buffers;
    the engine owns the host loop and calls through this interface.  The
    class attributes are the *policy* the engine and scheduler read:

    - ``uses_pages``: requests consume pool pages (block tables, page
      watermarks, COW) vs a constant-size per-slot cache.
    - ``supports_partial_cow``: a radix :class:`PrefixCache` (full-page
      adoption + partial-page copy-on-write) may be attached.
    - ``preempt_keeps_progress``: preemption snapshots per-request cache
      state, so ``num_cached`` survives requeueing.
    """

    name = "abstract"
    uses_pages = True
    supports_partial_cow = True
    supports_chunked_prefill = True
    preempt_keeps_progress = False

    def __init__(self, cfg: ModelConfig, *, max_batch: int, page_size: int,
                 num_pages: int, max_blocks: int, max_seq_len: int,
                 temperature: float, top_k: int, top_p: float,
                 use_kernel: bool, use_sampling_kernel: bool, dtype,
                 prefix_cache: Optional[PrefixCache] = None,
                 prefix_sharing: bool = True):
        self.cfg = cfg
        self.max_batch = max_batch
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_blocks = max_blocks
        self.max_seq_len = max_seq_len
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.use_kernel = use_kernel
        self.use_sampling_kernel = use_sampling_kernel
        self.dtype = dtype

    # -- scheduler integration ---------------------------------------------
    def cost_model(self):
        return (KVPageCost(self.page_size) if self.uses_pages
                else NullPageCost())

    # -- jitted compute (implemented by subclasses) ------------------------
    def step(self, params, tokens, positions, tables, seeds, active):
        """Advance every slot one token; returns (tokens, logprobs)."""
        raise NotImplementedError

    def prefill_chunk_step(self, params, tokens, positions, n_valid,
                           req: Request) -> None:
        """Cache ``n_valid`` positions of one request in a single call."""
        raise NotImplementedError

    def cow(self, src: int, dst: int) -> None:
        """Copy-on-write a whole page (paged-KV layouts only)."""
        raise NotImplementedError

    # -- lifecycle hooks (default: no-ops) ---------------------------------
    def on_admit(self, req: Request) -> int:
        """Called for each newly-admitted request; returns the number of
        prompt positions satisfied from a layout-private cache."""
        return 0

    def on_preempt(self, req: Request) -> None:
        """Called just before the scheduler requeues a running request."""

    def on_finish(self, req: Request, *, index_in_cache: bool) -> None:
        """Called just before the scheduler evicts a finished request."""

    def on_weight_swap(self) -> None:
        """Called after an in-flight weight update lands: any
        layout-private cache of old-weight activations must drop."""

    def note_progress(self, req: Request) -> None:
        """Called after ``req.num_cached`` advances (decode or chunk)."""

    def rebind(self, sharding) -> None:
        """Re-place the layout's device buffers onto ``sharding``."""
        raise NotImplementedError

    # -- shared sampling tail ----------------------------------------------
    # the params' mesh when it spans several devices (the fused sampler
    # then runs under shard_map); set on the host before each jitted
    # step and read while tracing, which input shardings key
    _mesh = None

    def _note_mesh(self, params) -> None:
        mesh = getattr(jax.tree_util.tree_leaves(params)[0].sharding,
                       "mesh", None)
        self._mesh = mesh if mesh is not None and mesh.size > 1 else None

    def _sample_batch(self, logits, seeds, positions):
        """Per-request deterministic sampling: token at ``position`` of a
        request seeded ``seed`` is drawn from fold_in(PRNGKey(seed), pos)
        — invariant to batching, chunking, and preemption."""
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
        )(seeds, positions)
        if self.use_sampling_kernel:
            return sample_tokens_fused(
                keys, logits, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p,
                vocab_size=self.cfg.vocab_size, mesh=self._mesh)
        return jax.vmap(functools.partial(
            sample_token, temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, vocab_size=self.cfg.vocab_size))(keys, logits)


# ===========================================================================
# Paged KV (dense attention stacks) — the original layout, extracted
# ===========================================================================
def _paged_sdpa(q, k_pages, v_pages, li, block_tables, context_lens,
                kv_heads):
    """Pure-JAX paged attention over layer ``li`` of the lane-dense pool
    (gather through the block table + sdpa); the XLA analogue of
    kernels/paged_attention.py, exact same math."""
    B, nb = block_tables.shape
    page = k_pages.shape[2]
    k = k_pages[li, block_tables].reshape(B, nb * page, kv_heads, -1)
    v = v_pages[li, block_tables].reshape(B, nb * page, kv_heads, -1)
    pos = jnp.arange(nb * page)
    mask = jnp.where(pos[None, :] < context_lens[:, None], 0.0,
                     NEG_INF)[:, None, None, :]  # (B, 1, 1, S)
    return sdpa(q, k, v, mask)  # (B, 1, H, hd)


class PagedKVLayout(CacheLayout):
    """vLLM-style paged KV pool + block tables; dense attention stacks."""

    name = "paged-kv"
    uses_pages = True
    supports_partial_cow = True
    preempt_keeps_progress = False

    def __init__(self, cfg: ModelConfig, **kw):
        super().__init__(cfg, **kw)
        self.cache: PagedKVCache = init_paged_cache(
            cfg.num_layers, self.num_pages, self.page_size,
            cfg.num_kv_heads, cfg.resolved_head_dim, self.dtype)
        # donate the page pools: XLA aliases input to output so the
        # per-step .at[].set() updates the cache in place instead of
        # copying the whole pool every token
        self._step_fn = jax.jit(self._step_impl, donate_argnums=(1, 2))
        self._prefill_fn = jax.jit(self._prefill_impl, donate_argnums=(1, 2))
        self._cow_fn = jax.jit(self._cow_impl, donate_argnums=(0, 1))
        if kw.get("prefix_cache") is not None:
            # compile the copy-on-write kernel now (trash page onto
            # itself is a semantic no-op) so the first real COW during a
            # measured run doesn't eat a compilation
            self.cache = PagedKVCache(*self._cow_fn(
                self.cache.k, self.cache.v,
                jnp.asarray(TRASH_PAGE, jnp.int32),
                jnp.asarray(TRASH_PAGE, jnp.int32)))

    # -- per-layer FFN hook (MoE subclass overrides) ------------------------
    def _ffn(self, lp, h):
        return mlp(lp["mlp"], h)

    # -- jitted impls -------------------------------------------------------
    def _step_impl(self, params, k_pages, v_pages, tokens, positions,
                   block_tables, seeds):
        """One token for every slot.  All shapes fixed by construction:
        tokens/positions/seeds (max_batch,), block_tables
        (max_batch, max_blocks), cache (L, P, page, KV * hd).

        The pool rides the layer scan as carry, written and read by layer
        index: scanned as ``xs``/``ys`` instead, each layer's pool would be
        sliced out of the donated input and updated into a separate
        stacked output, a copy of the whole pool per step."""
        cfg = self.cfg
        B = tokens.shape[0]
        KV = cfg.num_kv_heads
        x = embed(params["embed"], tokens[:, None])  # (B, 1, d)
        posb = positions[:, None]
        page = self.page_size
        page_idx = jnp.take_along_axis(
            block_tables, (positions // page)[:, None], axis=1)[:, 0]
        offset = positions % page
        ctx = positions + 1  # valid tokens after this step's write

        def layer_body(carry, xs):
            x, k_pages, v_pages = carry
            lp, li = xs
            h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = qkv_project(lp["attn"], cfg, h)  # (B, 1, H|KV, hd)
            q = apply_rope(q, posb, cfg.rope_theta)
            k = apply_rope(k, posb, cfg.rope_theta)
            # scatter this step's K/V into each request's current page
            # (inactive slots target the trash page)
            k_pages = k_pages.at[li, page_idx, offset].set(
                k.reshape(B, -1).astype(k_pages.dtype))
            v_pages = v_pages.at[li, page_idx, offset].set(
                v.reshape(B, -1).astype(v_pages.dtype))
            if self.use_kernel:
                from repro.kernels import ops as kops

                # the kernel takes one layer's (P, page, KV, hd) pool:
                # this slices a copy of the layer out of the stack
                kl = k_pages[li].reshape(*k_pages.shape[1:3], KV, -1)
                vl = v_pages[li].reshape(*v_pages.shape[1:3], KV, -1)
                out = kops.paged_attention(
                    q[:, 0], kl, vl, block_tables, ctx)[:, None]
            else:
                out = _paged_sdpa(q, k_pages, v_pages, li, block_tables,
                                  ctx, KV)
            x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
            x = x + self._ffn(lp, rmsnorm(lp["ln2"], x, cfg.norm_eps))
            return (x, k_pages, v_pages), None

        (x, k_pages, v_pages), _ = jax.lax.scan(
            layer_body, (x, k_pages, v_pages),
            (params["layers"], jnp.arange(cfg.num_layers)))
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = unembed(params["embed"], x)[:, 0]  # (B, V)
        tok, lp = self._sample_batch(logits, seeds, positions)
        return tok, lp, k_pages, v_pages

    def _prefill_impl(self, params, k_pages, v_pages, tokens, positions,
                      block_table, n_valid):
        """Write KV for up to ``prefill_chunk`` prompt positions of ONE
        request in a single forward.  No logits come back: every chunked
        position is strictly before the sampling frontier, which always
        goes through :meth:`_step_impl`.  Shapes fixed by construction:
        tokens/positions (C,), block_table (max_blocks,), n_valid ()."""
        cfg = self.cfg
        C = tokens.shape[0]
        KV = cfg.num_kv_heads
        page = self.page_size
        S = self.max_blocks * page
        valid = jnp.arange(C) < n_valid
        x = embed(params["embed"], tokens[None, :])  # (1, C, d)
        posb = positions[None, :]
        # padded rows scatter into the trash page, like inactive slots
        page_idx = jnp.where(valid, block_table[positions // page],
                             TRASH_PAGE)
        offset = positions % page
        kpos = jnp.arange(S)
        # causal over the request's own logical context: everything at or
        # before a row's position is already cached (earlier steps) or is
        # written by this very chunk's scatter before the gather below
        mask = jnp.where(kpos[None, :] <= positions[:, None], 0.0,
                         NEG_INF)[None, None]  # (1, 1, C, S)

        def layer_body(carry, xs):
            x, k_pages, v_pages = carry
            lp, li = xs
            h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = qkv_project(lp["attn"], cfg, h)  # (1, C, H|KV, hd)
            q = apply_rope(q, posb, cfg.rope_theta)
            k = apply_rope(k, posb, cfg.rope_theta)
            k_pages = k_pages.at[li, page_idx, offset].set(
                k.reshape(C, -1).astype(k_pages.dtype))
            v_pages = v_pages.at[li, page_idx, offset].set(
                v.reshape(C, -1).astype(v_pages.dtype))
            kc = k_pages[li, block_table].reshape(1, S, KV, -1)
            vc = v_pages[li, block_table].reshape(1, S, KV, -1)
            out = sdpa(q, kc, vc, mask)  # (1, C, H, hd)
            x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
            x = x + self._ffn(lp, rmsnorm(lp["ln2"], x, cfg.norm_eps))
            return (x, k_pages, v_pages), None

        (_, k_pages, v_pages), _ = jax.lax.scan(
            layer_body, (x, k_pages, v_pages),
            (params["layers"], jnp.arange(cfg.num_layers)))
        return k_pages, v_pages

    @staticmethod
    def _cow_impl(k_pages, v_pages, src, dst):
        """Copy page ``src`` into page ``dst`` on every layer — the
        copy-on-write that lets a request extend a shared partial page
        privately.  The whole page is copied (not just the adopted rows):
        rows past the destination's computed watermark are never read
        before the owner overwrites them, and a row count would otherwise
        have to be a static arg that recompiles per distinct value."""
        k_pages = k_pages.at[:, dst].set(k_pages[:, src])
        v_pages = v_pages.at[:, dst].set(v_pages[:, src])
        return k_pages, v_pages

    # -- host-facing API ----------------------------------------------------
    def step(self, params, tokens, positions, tables, seeds, active):
        self._note_mesh(params)
        tok, lp, kc, vc = self._step_fn(
            params, self.cache.k, self.cache.v, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(tables),
            jnp.asarray(seeds))
        self.cache = PagedKVCache(k=kc, v=vc)
        return tok, lp

    def prefill_chunk_step(self, params, tokens, positions, n_valid,
                           req: Request) -> None:
        table = jnp.asarray(
            pad_block_table(req.pages, self.max_blocks), jnp.int32)
        kc, vc = self._prefill_fn(
            params, self.cache.k, self.cache.v, jnp.asarray(tokens),
            jnp.asarray(positions), table,
            jnp.asarray(n_valid, jnp.int32))
        self.cache = PagedKVCache(k=kc, v=vc)

    def cow(self, src: int, dst: int) -> None:
        kc, vc = self._cow_fn(
            self.cache.k, self.cache.v,
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
        self.cache = PagedKVCache(k=kc, v=vc)

    def rebind(self, sharding) -> None:
        self.cache = PagedKVCache(
            k=jax.device_put(self.cache.k, sharding),
            v=jax.device_put(self.cache.v, sharding))


class MoEPagedKVLayout(PagedKVLayout):
    """Paged KV pool with the FFN half routed through the exact top-k
    expert combine.  Capacity-based dispatch (the training path) is
    batch-size dependent — a token's drops depend on who else is in the
    decode batch — which would break both temp-0 static parity and the
    scheduling-invariance contract, so serving always uses the exact
    per-token combine; ``use_kernel`` swaps in the grouped per-expert
    decode GEMM (token→expert gather layout, ``kernels.ops.moe_decode``)."""

    name = "paged-kv-moe"

    def _ffn(self, lp, h):
        return moe_mod.moe_decode_exact(lp["moe"], self.cfg, h,
                                        use_kernel=self.use_kernel)


# ===========================================================================
# Constant-size state cache (SSM / hybrid stacks)
# ===========================================================================
def _batch_axes(cfg: ModelConfig) -> M.DecodeState:
    """Pytree (matching DecodeState) of each leaf's slot/batch axis."""
    if cfg.kind == SSM:
        return M.DecodeState(kv=(), ssm=SSMState(ssm=1, conv=1),
                             cross_kv=(), shared_kv=())
    if cfg.kind == HYBRID:
        return M.DecodeState(
            kv=(), ssm=SSMState(ssm=2, conv=2), cross_kv=(),
            shared_kv=KVCache(k=1, v=1, positions=1))
    raise LayoutError(
        f"state cache layout has no slot axes for kind={cfg.kind}")


class StateCacheLayout(CacheLayout):
    """Constant-size recurrent state per request slot (SSM / hybrid).

    The cache is the model's own stacked :class:`DecodeState` over
    ``max_batch`` slots: Mamba2 SSD state + conv window per layer, plus
    the shared-attention KV ring for hybrid stacks.  Decode needs no page
    growth (``NullPageCost``), preemption snapshots the victim's slot
    state (progress survives requeueing), and prefix reuse is an exact
    full-prompt match against an LRU snapshot cache — SSD state at
    position ``i`` depends on every token before it, so adopting part of
    a cached prefix is meaningless.  Partial-page COW is structurally
    impossible: constructing this layout with a radix
    :class:`PrefixCache` raises :class:`LayoutError`.
    """

    name = "state"
    uses_pages = False
    supports_partial_cow = False
    # a recurrent step is sequential whether it happens in a per-request
    # chunk scan or the decode batch — but the decode batch runs every
    # slot's step in ONE vmapped call, so prefilling through it is
    # max_batch-way parallel while a chunk scan is serial per request.
    # Chunked prefill would only slow the state cache down.
    supports_chunked_prefill = False
    preempt_keeps_progress = True

    def __init__(self, cfg: ModelConfig, **kw):
        if kw.get("prefix_cache") is not None:
            raise LayoutError(
                "state cache layouts cannot take a partial-page COW "
                "prefix cache: recurrent state is position-dependent, so "
                "prefix reuse is exact-full-prompt-match only")
        super().__init__(cfg, **kw)
        self._axes = _batch_axes(cfg)
        self.cache: M.DecodeState = M.init_decode_state(
            cfg, self.max_batch, self.max_seq_len, self.dtype)
        # one zeroed slot row, used to reset a slot for a fresh request
        self._zero_row = self._take_slot(self.cache, 0)
        # rid -> slot-state snapshot taken at preemption
        self._suspended: Dict[int, Any] = {}
        # exact-full-prompt snapshot cache: tuple(tokens) -> state that
        # has consumed tokens[:-1]; LRU-bounded, flushed on weight swap
        self.exact_prefix_capacity = (
            32 if kw.get("prefix_sharing", True) else 0)
        self._exact: "OrderedDict[Tuple[int, ...], Any]" = OrderedDict()
        self.exact_prefix_hits = 0
        self._step_fn = jax.jit(self._step_impl, donate_argnums=(1,))

    # -- slot/state pytree plumbing ----------------------------------------
    def _take_slot(self, state, slot):
        return jax.tree_util.tree_map(
            lambda x, a: jax.lax.dynamic_index_in_dim(
                x, slot, axis=a, keepdims=False), state, self._axes)

    def _put_slot(self, state, row, slot):
        return jax.tree_util.tree_map(
            lambda x, r, a: jax.lax.dynamic_update_index_in_dim(
                x, r.astype(x.dtype), slot, axis=a),
            state, row, self._axes)

    def _row_decode(self, params, tok, pos, st_row):
        """One decode step of one slot: expand the slot row back to a
        B=1 state, reuse the model's own (static-engine-identical)
        ``decode_step``, squeeze back to a row."""
        st1 = jax.tree_util.tree_map(
            lambda x, a: jnp.expand_dims(x, a), st_row, self._axes)
        logits, new_st = M.decode_step(
            params, self.cfg, jnp.reshape(tok, (1, 1)), st1, pos,
            use_kernel=self.use_kernel)
        new_row = jax.tree_util.tree_map(
            lambda x, a: jnp.squeeze(x, a), new_st, self._axes)
        return logits[0, 0], new_row

    # -- jitted impls -------------------------------------------------------
    def _step_impl(self, params, state, tokens, positions, seeds, active):
        def row(tok, pos, act, st_row):
            logits, new_row = self._row_decode(params, tok, pos, st_row)
            # inactive slots (no request, or a request sitting the step
            # out) keep their state — the analogue of the trash page
            new_row = jax.tree_util.tree_map(
                lambda n, o: jnp.where(act, n, o.astype(n.dtype)),
                new_row, st_row)
            return logits, new_row

        logits, state = jax.vmap(
            row, in_axes=(0, 0, 0, self._axes),
            out_axes=(0, self._axes))(tokens, positions, active, state)
        tok, lp = self._sample_batch(logits, seeds, positions)
        return tok, lp, state

    # -- host-facing API ----------------------------------------------------
    def step(self, params, tokens, positions, tables, seeds, active):
        self._note_mesh(params)
        tok, lp, self.cache = self._step_fn(
            params, self.cache, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(seeds),
            jnp.asarray(active))
        return tok, lp

    # -- lifecycle ----------------------------------------------------------
    def _snapshot(self, slot: int):
        return self._take_slot(self.cache, slot)

    def _store_exact(self, key: Tuple[int, ...], slot: int) -> None:
        if not self.exact_prefix_capacity:
            return
        self._exact[key] = self._snapshot(slot)
        self._exact.move_to_end(key)
        while len(self._exact) > self.exact_prefix_capacity:
            self._exact.popitem(last=False)

    def on_admit(self, req: Request) -> int:
        snap = self._suspended.pop(req.rid, None)
        if snap is not None:
            # resumed after preemption: restore the snapshot; num_cached
            # survived requeueing, so decode continues at the frontier
            self.cache = self._put_slot(self.cache, snap, req.slot)
            return 0
        if req.generated or req.num_cached:
            # mid-flight request without a snapshot cannot happen (the
            # scheduler only requeues via preempt); a fresh slot it is
            self.cache = self._put_slot(self.cache, self._zero_row,
                                        req.slot)
            req.num_cached = 0
            return 0
        hit = self._exact.get(tuple(req.prompt))
        if hit is not None:
            self._exact.move_to_end(tuple(req.prompt))
            self.cache = self._put_slot(self.cache, hit, req.slot)
            req.num_cached = req.prompt_len - 1
            self.exact_prefix_hits += 1
            return req.num_cached
        self.cache = self._put_slot(self.cache, self._zero_row, req.slot)
        return 0

    def on_preempt(self, req: Request) -> None:
        self._suspended[req.rid] = self._snapshot(req.slot)

    def on_finish(self, req: Request, *, index_in_cache: bool) -> None:
        self._suspended.pop(req.rid, None)
        if index_in_cache and req.generated:
            # at finish the slot state has consumed prompt+generated[:-1]
            # (the final sampled token is never fed back), exactly the
            # invariant the exact-match cache stores
            self._store_exact(tuple(req.prompt + req.generated), req.slot)

    def on_weight_swap(self) -> None:
        # snapshots of *running* requests survive (in-flight semantics);
        # the exact-prefix cache holds old-weight state for FUTURE
        # requests and must drop, mirroring the radix-trie flush
        self._exact.clear()

    def note_progress(self, req: Request) -> None:
        if (not req.generated and self.exact_prefix_capacity
                and req.num_cached == req.prompt_len - 1):
            key = tuple(req.prompt)
            if key not in self._exact:
                self._store_exact(key, req.slot)

    def rebind(self, sharding) -> None:
        def put(tree):
            return jax.tree_util.tree_map(
                lambda x: (jax.device_put(x, sharding)
                           if isinstance(x, jax.Array) else x), tree)

        self.cache = put(self.cache)
        self._zero_row = put(self._zero_row)
        self._suspended = {k: put(v) for k, v in self._suspended.items()}
        self._exact = OrderedDict(
            (k, put(v)) for k, v in self._exact.items())


# ===========================================================================
# Registry
# ===========================================================================
def layout_class(cfg: ModelConfig):
    """The layout class serving ``cfg``, or None when uncovered (the
    rollout worker then falls back to the static engine)."""
    if cfg.kind == DENSE and not cfg.sliding_window:
        return PagedKVLayout
    if cfg.kind == MOE and not cfg.sliding_window:
        return MoEPagedKVLayout
    if cfg.kind in (SSM, HYBRID):
        return StateCacheLayout
    return None


def covers(cfg: ModelConfig) -> bool:
    """True when the paged engine has a cache layout for ``cfg``."""
    return layout_class(cfg) is not None


def make_layout(cfg: ModelConfig, **kw) -> CacheLayout:
    cls = layout_class(cfg)
    if cls is None:
        if cfg.sliding_window and cfg.kind in (DENSE, MOE):
            raise NotImplementedError(
                "PagedEngine does not window the paged cache yet")
        raise NotImplementedError(
            f"PagedEngine has no cache layout for kind={cfg.kind}")
    return cls(cfg, **kw)
