"""flowlint Pass 3 — Pallas kernel and RNG-determinism lint.

The kernel wrappers in ``kernels/ops.py`` enforce their invariants with
runtime asserts — which on the 500k-token config means discovering a bad
``block_q`` half an hour into a run.  This pass re-derives each wrapper's
shape math as a declarative :class:`KernelInvocation` (grid, operand
shapes, BlockSpec block shapes and index maps, declared divisibility
constraints) and evaluates it at the config-zoo shapes
(``configs/shapes.py``) in microseconds:

  * K101 — degenerate grid (a dimension of zero or negative extent);
  * K102 — a declared divisibility constraint fails (the runtime assert);
  * K103 — a block shape exceeding its operand dimension;
  * K104 — an index map addressing out of bounds at some grid corner
    (page tables modeled at their worst-case entry);
  * K105 — a page table too short to cover the declared context length;
  * K106 — GQA head counts that do not divide (``H % KV != 0``);
  * K107 — a public kernel entry in ``ops.py`` with no lint spec at all;
  * K108 — a block shape Mosaic refuses: the last two dimensions of every
    block must be multiples of (8, 128) or span the operand's dimension.

The RNG half checks the determinism contract PR 5's closed loop relies
on: per-(round, step, env) ``fold_in`` keying must be injective over its
coordinate domain.  Nested fold chains are injective by construction;
any *combined* keying (e.g. folding ``step + env_id``) is enumerated
over the bounded domain and collisions are reported as R101.
"""
from __future__ import annotations

import inspect
import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from repro.analysis.findings import Finding
from repro.configs.shapes import SHAPES

PASS = "kernel"


def _f(code: str, severity: str, subject: str, message: str,
       hint: str = "", pass_name: str = PASS) -> Finding:
    return Finding(code, severity, subject, message, hint, pass_name)


# ---------------------------------------------------------------------------
# Kernel invocation IR
# ---------------------------------------------------------------------------
@dataclass
class BlockMap:
    """One operand's BlockSpec as the analyzer sees it."""
    name: str
    operand_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    # grid ids -> block indices (the BlockSpec index_map, re-expressed)
    index_map: Callable[..., Tuple[int, ...]]


@dataclass
class Divisibility:
    """A declared constraint the wrapper asserts at runtime."""
    label: str
    value: int
    divisor: int
    code: str = "K102"  # K106 for the GQA head-count constraint


@dataclass
class KernelInvocation:
    kernel: str      # entry name in kernels/ops.py
    shape_name: str  # config-zoo shape this was evaluated at
    grid: Tuple[int, ...]
    operands: List[BlockMap] = field(default_factory=list)
    constraints: List[Divisibility] = field(default_factory=list)
    # (label, covered, needed): covered < needed -> K105
    coverage: Optional[Tuple[str, int, int]] = None

    @property
    def subject(self) -> str:
        return f"{self.kernel}@{self.shape_name}"


# ---------------------------------------------------------------------------
# Spec builders — each mirrors the shape math of one ops.py wrapper
# ---------------------------------------------------------------------------
def flash_invocation(shape_name: str, *, B: int, H: int, S: int, D: int,
                     KV: int, block_q: int = 128, block_k: int = 128,
                     clamp: bool = True) -> KernelInvocation:
    """Mirrors ``flash_attention_bhsd``: blocks clamp to ``min(block, S)``
    then S must divide by both; K/V are addressed at ``h // (H // KV)``."""
    if clamp:
        block_q, block_k = min(block_q, S), min(block_k, S)
    group = max(H // KV, 1) if KV > 0 else 1
    nq = max(S // block_q, 1) if block_q > 0 else 0
    nk = max(S // block_k, 1) if block_k > 0 else 0
    return KernelInvocation(
        kernel="flash_attention", shape_name=shape_name,
        grid=(B, H, nq, nk),
        operands=[
            BlockMap("q", (B, H, S, D), (1, 1, block_q, D),
                     lambda b, h, qi, ki: (b, h, qi, 0)),
            BlockMap("k", (B, KV, S, D), (1, 1, block_k, D),
                     lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            BlockMap("v", (B, KV, S, D), (1, 1, block_k, D),
                     lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            BlockMap("o", (B, H, S, D), (1, 1, block_q, D),
                     lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        constraints=[
            Divisibility("H % num_kv_heads", H, KV, code="K106"),
            Divisibility("S % block_q", S, block_q),
            Divisibility("S % block_k", S, block_k),
        ])


def paged_invocation(shape_name: str, *, B: int, H: int, D: int, P: int,
                     page: int, KV: int, nb: int, max_context: int,
                     table_max: Optional[int] = None) -> KernelInvocation:
    """Mirrors ``paged_attention_bhd``.  ``table_max`` models the largest
    page id a block table can hold (defaults to the pool's last page,
    P - 1 — the allocator's worst case)."""
    tmax = (P - 1) if table_max is None else table_max
    return KernelInvocation(
        kernel="paged_attention", shape_name=shape_name,
        grid=(B, nb),
        operands=[
            BlockMap("q", (B, H, D), (1, H, D), lambda b, j: (b, 0, 0)),
            BlockMap("k_pages", (P, page * KV, D), (1, page * KV, D),
                     lambda b, j, t=tmax: (t, 0, 0)),
            BlockMap("v_pages", (P, page * KV, D), (1, page * KV, D),
                     lambda b, j, t=tmax: (t, 0, 0)),
            BlockMap("o", (B, H, D), (1, H, D), lambda b, j: (b, 0, 0)),
        ],
        constraints=[
            Divisibility("H % num_kv_heads", H, KV, code="K106"),
        ],
        coverage=("block_table pages * page_size vs max context",
                  nb * page, max_context))


def ssd_invocation(shape_name: str, *, B: int, L: int, H: int, P: int,
                   N: int, chunk: int) -> KernelInvocation:
    """Mirrors ``ssd_scan`` -> ``ssd_scan_bhcsp``: L splits into
    L // chunk chunks carried sequentially."""
    nc = max(L // chunk, 1) if chunk > 0 else 0
    return KernelInvocation(
        kernel="ssd_scan", shape_name=shape_name,
        grid=(B, H, nc),
        operands=[
            BlockMap("x", (B, H, nc, chunk, P), (1, 1, 1, chunk, P),
                     lambda b, h, ci: (b, h, ci, 0, 0)),
            BlockMap("dt", (B, H, nc, 1, chunk), (1, 1, 1, 1, chunk),
                     lambda b, h, ci: (b, h, ci, 0, 0)),
            BlockMap("a_cum", (B, H, nc, 1, chunk), (1, 1, 1, 1, chunk),
                     lambda b, h, ci: (b, h, ci, 0, 0)),
            BlockMap("Bm", (B, nc, chunk, N), (1, 1, chunk, N),
                     lambda b, h, ci: (b, ci, 0, 0)),
            BlockMap("Cm", (B, nc, chunk, N), (1, 1, chunk, N),
                     lambda b, h, ci: (b, ci, 0, 0)),
            BlockMap("D", (B, H, 1, 1), (1, 1, 1, 1),
                     lambda b, h, ci: (b, h, 0, 0)),
            BlockMap("y", (B, H, nc, chunk, P), (1, 1, 1, chunk, P),
                     lambda b, h, ci: (b, h, ci, 0, 0)),
        ],
        constraints=[Divisibility("L % chunk", L, chunk)])


def gmm_invocation(shape_name: str, *, E: int, C: int, D: int, F: int,
                   block_c: int = 128, block_d: int = 512,
                   block_f: int = 128, clamp: bool = True
                   ) -> KernelInvocation:
    """Mirrors ``grouped_matmul``: per-expert (C, D) @ (D, F) tiles."""
    if clamp:
        block_c, block_d = min(block_c, C), min(block_d, D)
        block_f = min(block_f, F)
    nc = max(C // block_c, 1) if block_c > 0 else 0
    nd = max(D // block_d, 1) if block_d > 0 else 0
    nf = max(F // block_f, 1) if block_f > 0 else 0
    return KernelInvocation(
        kernel="grouped_matmul", shape_name=shape_name,
        grid=(E, nc, nf, nd),
        operands=[
            BlockMap("buf", (E, C, D), (1, block_c, block_d),
                     lambda e, ci, fi, di: (e, ci, di)),
            BlockMap("w", (E, D, F), (1, block_d, block_f),
                     lambda e, ci, fi, di: (e, di, fi)),
            BlockMap("out", (E, C, F), (1, block_c, block_f),
                     lambda e, ci, fi, di: (e, ci, fi)),
        ],
        constraints=[
            Divisibility("C % block_c", C, block_c),
            Divisibility("D % block_d", D, block_d),
            Divisibility("F % block_f", F, block_f),
        ])


def ssm_update_invocation(shape_name: str, *, B: int, H: int, P: int,
                          N: int) -> KernelInvocation:
    """Mirrors ``ssm_state_update`` -> ``ssm_state_update_bh``: grid
    (B, H), one full (P, N) state tile per program (the state cache's
    constant-size decode step — no blocking, no divisibility)."""
    return KernelInvocation(
        kernel="ssm_state_update", shape_name=shape_name,
        grid=(B, H),
        operands=[
            BlockMap("state", (B, H, P, N), (1, 1, P, N),
                     lambda b, h: (b, h, 0, 0)),
            BlockMap("x", (B, H, P, 1), (1, 1, P, 1),
                     lambda b, h: (b, h, 0, 0)),
            BlockMap("dt", (B, H, 1, 1), (1, 1, 1, 1),
                     lambda b, h: (b, h, 0, 0)),
            BlockMap("A", (B, H, 1, 1), (1, 1, 1, 1),
                     lambda b, h: (b, h, 0, 0)),
            BlockMap("Bm", (B, 1, N), (1, 1, N), lambda b, h: (b, 0, 0)),
            BlockMap("Cm", (B, N, 1), (1, N, 1), lambda b, h: (b, 0, 0)),
            BlockMap("D", (B, H, 1, 1), (1, 1, 1, 1),
                     lambda b, h: (b, h, 0, 0)),
            BlockMap("y", (B, H, P, 1), (1, 1, P, 1),
                     lambda b, h: (b, h, 0, 0)),
            BlockMap("new_state", (B, H, P, N), (1, 1, P, N),
                     lambda b, h: (b, h, 0, 0)),
        ])


def _decode_capacity(num_tokens: int) -> int:
    """Keep in sync with ``kernels.moe_gmm.decode_capacity``: top-k
    indices are distinct per token, so one expert receives at most T
    assignments; pad to a 128 multiple above 128 for MXU tiling."""
    if num_tokens <= 128:
        return max(num_tokens, 1)
    return -(-num_tokens // 128) * 128


def moe_decode_invocation(shape_name: str, *, T: int, E: int, d: int,
                          f: int) -> List[KernelInvocation]:
    """Mirrors ``moe_decode`` -> ``moe_decode_gmm``: tokens gather into
    an (E, C, d) buffer with C = decode_capacity(T), then grouped GEMMs
    — gate/up at (E, C, d) @ (E, d, f) and down at (E, C, f) @ (E, f, d)
    — each with ``grouped_matmul``'s clamped tile sizes."""
    up = gmm_invocation(shape_name, E=E, C=_decode_capacity(T), D=d, F=f)
    down = gmm_invocation(shape_name, E=E, C=_decode_capacity(T), D=f, F=d)
    for inv in (up, down):
        inv.kernel = "moe_decode"
    return [up, down]


def sampling_invocation(shape_name: str, *, B: int, V: int
                        ) -> KernelInvocation:
    """Mirrors ``fused_sample`` -> ``fused_sample_bv``: the batch pads to
    a multiple of 8 rows, one (8, V) logits/gumbel block per program,
    (8, 1) token/logprob outs."""
    Bp = -(-B // 8) * 8
    return KernelInvocation(
        kernel="fused_sample", shape_name=shape_name,
        grid=(Bp // 8,),
        operands=[
            BlockMap("logits", (Bp, V), (8, V), lambda b: (b, 0)),
            BlockMap("gumbel", (Bp, V), (8, V), lambda b: (b, 0)),
            BlockMap("token", (Bp, 1), (8, 1), lambda b: (b, 0)),
            BlockMap("lp", (Bp, 1), (8, 1), lambda b: (b, 0)),
        ])


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------
def check_invocation(inv: KernelInvocation) -> List[Finding]:
    out: List[Finding] = []
    subject = inv.subject

    for i, n in enumerate(inv.grid):
        if n <= 0:
            out.append(_f(
                "K101", "error", subject,
                f"grid dimension {i} has extent {n}",
                "every grid axis needs at least one program instance"))
    for c in inv.constraints:
        if c.divisor <= 0 or c.value % c.divisor:
            if c.code == "K106":
                msg = (f"GQA requires {c.label} == 0, got "
                       f"{c.value} % {c.divisor}")
                hint = ("query heads must be an integer multiple of KV "
                        "heads — the K/V index map computes h // (H//KV)")
            else:
                msg = (f"{c.label} != 0 ({c.value} % {c.divisor}) — the "
                       f"wrapper's runtime assert would fire")
                hint = ("pick a block/chunk size dividing the operand "
                        "dimension at this config-zoo shape")
            out.append(_f(c.code, "error", subject, msg, hint))
    for op in inv.operands:
        for d, (blk, dim) in enumerate(zip(op.block_shape,
                                           op.operand_shape)):
            if blk > dim:
                out.append(_f(
                    "K103", "error", f"{subject}:{op.name}",
                    f"block shape {op.block_shape} exceeds operand "
                    f"shape {op.operand_shape} in dim {d} "
                    f"({blk} > {dim})",
                    "clamp the block to min(block, dim) like the "
                    "wrappers do"))
    out.extend(_check_tiling(inv))
    if not any(f.code in ("K101", "K103") for f in out):
        out.extend(_check_index_maps(inv))
    if inv.coverage is not None:
        label, covered, needed = inv.coverage
        if covered < needed:
            out.append(_f(
                "K105", "error", subject,
                f"{label}: {covered} < {needed} — decode steps past "
                f"position {covered} address past the block table",
                "size the table at ceil(max_seq_len / page_size) pages "
                "(PagedEngine.max_blocks does this)"))
    return out


# Mosaic tiles the last two dimensions of a VMEM block by (sublanes,
# lanes) = (8, 128) for 32-bit data, unless the block spans the dimension.
_TILE = (8, 128)


def _check_tiling(inv: KernelInvocation) -> List[Finding]:
    """K108 — the block-shape rule the TPU compiler enforces, which the
    Pallas interpreter does not."""
    out: List[Finding] = []
    for op in inv.operands:
        dims = list(zip(op.block_shape, op.operand_shape))[-2:]
        for (blk, dim), tile in zip(dims, _TILE[-len(dims):]):
            if blk % tile and blk < dim:  # blk > dim is K103
                out.append(_f(
                    "K108", "error", f"{inv.subject}:{op.name}",
                    f"block shape {op.block_shape} over operand shape "
                    f"{op.operand_shape}: Mosaic needs the last two block "
                    f"dims divisible by {_TILE} or equal to the operand's",
                    "block whole rows (e.g. 8 at a time), span the "
                    "dimension, or insert a unit axis so the block spans "
                    "it"))
                break
    return out


def _check_index_maps(inv: KernelInvocation) -> List[Finding]:
    """Evaluate each index map at every grid corner and check the block
    it selects stays inside the operand.  Corner evaluation is exact
    here because every index map in the repo is monotone in each grid
    id (affine, floor-div, or a table lookup modeled at its max)."""
    out: List[Finding] = []
    corners = list(itertools.product(*([0, n - 1] if n > 1 else [0]
                                       for n in inv.grid)))
    for op in inv.operands:
        for ids in corners:
            idx = op.index_map(*ids)
            oob = next((
                (d, i * blk, i * blk + blk)
                for d, (i, blk, dim) in enumerate(zip(idx, op.block_shape,
                                                      op.operand_shape))
                if i * blk < 0 or i * blk + blk > dim), None)
            if oob is None:
                continue
            d, lo, hi = oob
            out.append(_f(
                "K104", "error", f"{inv.subject}:{op.name}",
                f"index map at grid point {ids} selects "
                f"[{lo}:{hi}) in dim {d} of operand shape "
                f"{op.operand_shape} (out of bounds)",
                "the index map must keep idx*block + block "
                "within the operand at every grid point"))
            break  # first offending corner per operand is enough
    return out


def default_invocations() -> List[KernelInvocation]:
    """The clean registry: every ops.py kernel at every config-zoo shape
    it serves, with representative 7B-class model dimensions (heads and
    widths match the qwen-family configs; SSD dims match mamba2)."""
    H, KV, D = 28, 4, 128            # dense/GQA attention dims
    ssd_H, ssd_P, ssd_N = 24, 64, 128  # mamba2 heads / head_dim / state
    page = 16                        # PagedEngine default page_size
    vocab = 151_936                  # qwen-family padded vocab width
    out: List[KernelInvocation] = []
    for name, sc in SHAPES.items():
        S, B = sc.seq_len, sc.global_batch
        if sc.phase == "decode":
            nb = -(-S // page)
            out.append(paged_invocation(
                name, B=B, H=H, D=D, P=B * nb + 1, page=page, KV=KV,
                nb=nb, max_context=S))
            # the fused sampler runs back-to-back with paged attention
            # on every decode step, same batch extent
            out.append(sampling_invocation(name, B=B, V=vocab))
            # per-arch decode paths through the state / MoE cache
            # layouts: constant-size SSD state update (mamba2 dims) and
            # the expert-parallel exact MoE FFN (granite-moe dims:
            # 40 experts, d_model 1536, expert d_ff 512, T = B tokens)
            out.append(ssm_update_invocation(
                name, B=B, H=ssd_H, P=ssd_P, N=ssd_N))
            out.extend(moe_decode_invocation(
                name, T=B, E=40, d=1536, f=512))
        else:
            out.append(flash_invocation(
                name, B=min(B, 8), H=H, S=S, D=D, KV=KV))
            out.append(ssd_invocation(
                name, B=min(B, 8), L=S, H=ssd_H, P=ssd_P, N=ssd_N,
                chunk=128))
    # MoE FFN hot-spot at the train shape: 8 experts, top-2, capacity
    # ceil(4096 * 2 / 8 * 1.25) = 1280 dispatched tokens per expert
    out.append(gmm_invocation("train_4k", E=8, C=1280, D=2048, F=5632))
    return out


def check_registry_coverage(
        invocations: Sequence[KernelInvocation]) -> List[Finding]:
    """K107 — every public kernel entry in ``kernels/ops.py`` must have
    at least one lint spec, or new kernels silently escape Pass 3."""
    from repro.kernels import ops as _ops
    covered = {inv.kernel for inv in invocations}
    out: List[Finding] = []
    for name, fn in inspect.getmembers(_ops, inspect.isfunction):
        if name.startswith("_") or fn.__module__ != _ops.__name__:
            continue
        if name not in covered:
            out.append(_f(
                "K107", "warning", name,
                f"kernel entry ops.{name} has no KernelInvocation spec "
                f"— Pass 3 cannot check it",
                "add a spec builder mirroring the wrapper's shape math "
                "to analysis.kernel_checks"))
    return out


def check_kernels(
        invocations: Optional[Sequence[KernelInvocation]] = None
) -> List[Finding]:
    invs = list(default_invocations() if invocations is None
                else invocations)
    out: List[Finding] = []
    for inv in invs:
        out.extend(check_invocation(inv))
    out.extend(check_registry_coverage(invs))
    return out


# ---------------------------------------------------------------------------
# RNG determinism lint
# ---------------------------------------------------------------------------
@dataclass
class RNGKeySpec:
    """One PRNG keying scheme.  ``combine`` is either the string
    ``"nested"`` (a chain of ``fold_in`` calls, one coordinate each —
    injective by construction, the scheme ``RolloutWorker.act`` and the
    paged sampler use) or a callable collapsing the coordinates into a
    single fold value (checked for collisions by enumeration)."""
    name: str
    coords: Tuple[str, ...]
    domain: Dict[str, range]
    combine: Union[str, Callable[..., Any]] = "nested"


def default_rng_specs() -> List[RNGKeySpec]:
    return [
        # workers.RolloutWorker.act: fold_in(fold_in(fold_in(base,
        # rollout_round), cycle_step), env_id)
        RNGKeySpec("rollout_act", ("rollout_round", "cycle_step", "env_id"),
                   {"rollout_round": range(4), "cycle_step": range(64),
                    "env_id": range(64)}),
        # serve.engine: token i of request r from
        # fold_in(PRNGKey(r.seed), position)
        RNGKeySpec("paged_sampler", ("seed", "position"),
                   {"seed": range(16), "position": range(256)}),
    ]


_MAX_ENUM = 1_000_000


def check_rng(specs: Optional[Sequence[RNGKeySpec]] = None
              ) -> List[Finding]:
    out: List[Finding] = []
    for spec in (default_rng_specs() if specs is None else specs):
        subject = spec.name
        missing = [c for c in spec.coords if c not in spec.domain]
        if missing:
            out.append(_f(
                "R101", "warning", subject,
                f"no enumeration domain declared for coordinate(s) "
                f"{missing} — collision check skipped",
                "declare a bounded range per coordinate",
                pass_name="rng"))
            continue
        if spec.combine == "nested":
            # fold_in chains are injective per coordinate: the identity
            # IS the coordinate tuple, which is unique by construction
            continue
        total = 1
        for c in spec.coords:
            total *= max(len(spec.domain[c]), 1)
        if total > _MAX_ENUM:
            out.append(_f(
                "R101", "warning", subject,
                f"domain too large to enumerate ({total} points)",
                "shrink the declared domain to a representative bound",
                pass_name="rng"))
            continue
        seen: Dict[Any, Tuple[int, ...]] = {}
        for point in itertools.product(
                *(spec.domain[c] for c in spec.coords)):
            ident = spec.combine(*point)
            if ident in seen:
                a = dict(zip(spec.coords, seen[ident]))
                b = dict(zip(spec.coords, point))
                out.append(_f(
                    "R101", "error", subject,
                    f"fold_in coordinate collision: {a} and {b} both "
                    f"key to {ident!r} — two logically distinct draws "
                    f"share a PRNG stream, breaking the bit-identical "
                    f"chunking guarantee",
                    "nest the fold_in per coordinate instead of "
                    "combining coordinates arithmetically",
                    pass_name="rng"))
                break
            seen[ident] = point
    return out
