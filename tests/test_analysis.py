"""flowlint tests: clean on every shipped workflow/example target, and
every defect class caught by a seeded mutation of a clean artifact.

The mutation tests follow one pattern: take the real graph/plan/topology
a target produces (verified clean), inject exactly one defect with
``dataclasses.replace`` (schedule nodes are frozen) or a dict edit, and
assert the lint reports that class — and nothing unrelated."""
import dataclasses
import threading
import time

import pytest

from repro.analysis import analyze, analyze_target
from repro.analysis.concurrency import (
    ChannelDecl,
    ChannelTopology,
    LockOrderRecorder,
    LockSite,
    build_topology,
    check_topology,
)
from repro.analysis.findings import (
    Finding,
    FlowLintError,
    filter_findings,
    format_findings,
    max_severity,
)
from repro.analysis.kernel_checks import (
    BlockMap,
    KernelInvocation,
    RNGKeySpec,
    check_invocation,
    check_kernels,
    check_registry_coverage,
    check_rng,
    flash_invocation,
    gmm_invocation,
    paged_invocation,
    ssd_invocation,
)
from repro.analysis.plan_checks import check_cost_models, check_graph, check_plan
from repro.analysis.targets import (
    all_targets,
    async_grpo_target,
    embodied_target,
    grpo_target,
    plan_for,
)
from repro.core.channel import DeviceLock, set_lock_observer
from repro.core.controller import Controller
from repro.core.flowgraph import FlowGraph, cycle_node_name
from repro.core.pipeline import CycleSpec
from repro.core.placement import Cluster
from repro.core.scheduler import Async, Leaf, Pipelined, leaves


def codes(findings):
    return {f.code for f in findings}


def _rewrite(node, fn):
    """Rebuild a (frozen) schedule tree with ``fn`` applied to each node."""
    node = fn(node)
    if isinstance(node, Leaf):
        return node
    return dataclasses.replace(node, s=_rewrite(node.s, fn),
                               t=_rewrite(node.t, fn))


def _mutate_plan(plan, **changes):
    return dataclasses.replace(plan, **changes)


# ---------------------------------------------------------------------------
# clean targets: zero findings on every workflow family and example graph
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("target", all_targets(), ids=lambda t: t.name)
def test_target_is_clean(target):
    findings = analyze_target(target)
    assert findings == [], format_findings(findings)


def test_kernel_registry_is_clean():
    assert check_kernels() == []
    assert check_rng() == []


# ---------------------------------------------------------------------------
# Pass 1 — graph defects
# ---------------------------------------------------------------------------
def _two_cycle():
    g = FlowGraph()
    g.add_worker("a")
    g.add_worker("b")
    g.add_edge("a", "b")
    g.add_edge("b", "a")
    return g


def test_p101_cycle_without_spec():
    fs = check_graph(_two_cycle(), {})
    assert codes(fs) == {"P101"}
    assert fs[0].severity == "error"


def test_p102_spec_order_mismatch():
    specs = {cycle_node_name(("a", "b")): CycleSpec(order=("a",), steps=2)}
    fs = check_graph(_two_cycle(), specs)
    assert codes(fs) == {"P102"}


def test_p103_orphan_node():
    g = grpo_target().graph
    g.add_worker("stray")
    fs = check_graph(g, {})
    assert codes(fs) == {"P103"}
    assert max_severity(fs) == "warning"


def test_p104_disconnected_subworkflows():
    g = FlowGraph()
    for n in ("a", "b", "c", "d"):
        g.add_worker(n)
    g.add_edge("a", "b")
    g.add_edge("c", "d")
    fs = check_graph(g, {})
    assert codes(fs) == {"P104"}


def test_p105_missing_cost_models():
    g = grpo_target().graph
    fs = check_cost_models(g, {})
    assert codes(fs) == {"P105"}
    assert len(fs) == len(g.nodes)


# ---------------------------------------------------------------------------
# Pass 1 — plan defects (seeded mutations of real plans)
# ---------------------------------------------------------------------------
def _grpo_plan(mode="disaggregated"):
    t = grpo_target(mode)
    return t, plan_for(t)


def test_p201_unknown_worker_in_placement():
    t, plan = _grpo_plan()
    plan.placement["ghost"] = [6, 7]
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg)
    assert codes(fs) == {"P201"}


def test_p202_empty_device_slice():
    t, plan = _grpo_plan()
    plan.placement["rollout"] = []
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg)
    assert codes(fs) == {"P202"}


def test_p203_device_out_of_range():
    t, plan = _grpo_plan()
    plan.placement["actor"] = [6, 99]
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg)
    assert codes(fs) == {"P203"}


def test_p204_device_on_failed_host():
    class OneDeadCluster(Cluster):
        def device_alive(self, global_id):
            return global_id != 7

    t, plan = _grpo_plan()
    fs = check_plan(plan, graph=t.graph,
                    cluster=OneDeadCluster(num_nodes=1, devices_per_node=8),
                    cfg=t.scheduler_cfg)
    assert codes(fs) == {"P204"}


def test_p205_pipelined_sides_share_devices():
    t, plan = _grpo_plan()
    plan.placement["inference"] = list(plan.placement["rollout"])
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg)
    assert codes(fs) == {"P205"}


def test_p206_empty_device_split():
    t, plan = _grpo_plan()
    first = [n for n in [plan.schedule] if isinstance(n, Pipelined)][0]
    sched = dataclasses.replace(first, n_s=0)
    fs = check_plan(_mutate_plan(plan, schedule=sched), graph=t.graph,
                    cluster=t.cluster, cfg=t.scheduler_cfg)
    assert codes(fs) == {"P206"}


def test_p207_sync_edge_unknown_endpoint():
    t, plan = _grpo_plan()
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg, sync_edges=(("actor", "ghost"),))
    assert codes(fs) == {"P207"}


def test_p208_sync_endpoint_without_devices():
    t, plan = _grpo_plan()
    plan.placement["rollout"] = []
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg, sync_edges=(("actor", "rollout"),))
    assert "P208" in codes(fs)
    # the empty slice itself also (correctly) reports P202 — nothing else
    assert codes(fs) <= {"P208", "P202"}


def test_p209_granularity_misaligned_with_chunk_multiple():
    t, plan = _grpo_plan()  # chunk_multiple = 8 (the GRPO group size)
    sched = _rewrite(plan.schedule,
                     lambda n: dataclasses.replace(n, granularity=12)
                     if isinstance(n, Pipelined) else n)
    fs = check_plan(_mutate_plan(plan, schedule=sched), graph=t.graph,
                    cluster=t.cluster, cfg=t.scheduler_cfg)
    assert codes(fs) == {"P209"}


def test_p210_negative_async_depth():
    t = async_grpo_target()
    plan = plan_for(t)
    sched = _rewrite(plan.schedule,
                     lambda n: dataclasses.replace(n, depth=-1)
                     if isinstance(n, Async) else n)
    fs = check_plan(_mutate_plan(plan, schedule=sched), graph=t.graph,
                    cluster=t.cluster, cfg=t.scheduler_cfg)
    assert codes(fs) == {"P210"}


def test_p211_cycle_leaf_without_members():
    t = embodied_target()
    plan = plan_for(t)
    cyc = cycle_node_name(("policy_gen", "simulator"))
    # give the collapsed node its own slice so only the members entry is
    # missing (not the placement)
    plan.placement[cyc] = [0, 1, 2, 3]
    fs = check_plan(_mutate_plan(plan, members={}), cluster=t.cluster,
                    cfg=t.scheduler_cfg)
    assert codes(fs) == {"P211"}


def test_p212_cycle_leaf_without_spec():
    t = embodied_target()
    plan = plan_for(t)
    fs = check_plan(plan, graph=t.graph, cluster=t.cluster,
                    cfg=t.scheduler_cfg,
                    cycle_specs={"bogus": object()})
    assert codes(fs) == {"P212"}


def test_p213_hybrid_member_devices_mismatch():
    t = embodied_target("hybrid")
    plan = plan_for(t)
    sched = _rewrite(plan.schedule,
                     lambda n: dataclasses.replace(n, member_devices=(4,))
                     if isinstance(n, Leaf) and n.cycle_mode == "hybrid"
                     else n)
    fs = check_plan(_mutate_plan(plan, schedule=sched), graph=t.graph,
                    cluster=t.cluster, cfg=t.scheduler_cfg,
                    cycle_specs=t.cycle_specs)
    assert codes(fs) == {"P213"}


def test_p214_hybrid_zero_chunks():
    t = embodied_target("hybrid")
    plan = plan_for(t)
    sched = _rewrite(plan.schedule,
                     lambda n: dataclasses.replace(n, cycle_chunks=0)
                     if isinstance(n, Leaf) and n.cycle_mode == "hybrid"
                     else n)
    fs = check_plan(_mutate_plan(plan, schedule=sched), graph=t.graph,
                    cluster=t.cluster, cfg=t.scheduler_cfg,
                    cycle_specs=t.cycle_specs)
    assert codes(fs) == {"P214"}


# ---------------------------------------------------------------------------
# Pass 2 — concurrency defects
# ---------------------------------------------------------------------------
def _hybrid_topology():
    t = embodied_target("hybrid")
    plan = plan_for(t)
    return t, build_topology(t.graph, plan, t.cycle_specs)


def test_hybrid_ring_topology_is_clean_and_primed():
    _, topo = _hybrid_topology()
    ring0 = topo.channels[
        f"ring:{cycle_node_name(('policy_gen', 'simulator'))}:0"]
    assert ring0.primed >= 1
    assert check_topology(topo) == []


def test_c101_unprimed_ring_deadlock():
    _, topo = _hybrid_topology()
    for ch in topo.channels.values():
        ch.primed = 0
    fs = check_topology(topo)
    assert codes(fs) == {"C101"}


def test_c102_bounded_ring_cannot_hold_inflight():
    _, topo = _hybrid_topology()
    for ch in topo.channels.values():
        if ch.name.startswith("ring:"):
            ch.capacity = 1
    ring0 = [c for c in topo.channels.values()
             if c.name.startswith("ring:") and c.name.endswith(":0")][0]
    ring0.primed = 10  # more carries than buffers + hands can hold
    fs = check_topology(topo)
    assert codes(fs) == {"C102"}


def test_c103_async_queue_never_admits_put():
    topo = ChannelTopology()
    topo.add_channel(ChannelDecl("aq", kind="async", capacity=0,
                                 staleness_bound=-1, gate_offset=-1))
    topo.put("rollout", "aq")
    topo.get("actor", "aq")
    fs = check_topology(topo)
    assert codes(fs) == {"C103"}
    assert len(fs) == 3  # bound, capacity and gate each reported


def test_c104_gate_wider_than_staleness_bound():
    topo = ChannelTopology()
    topo.add_channel(ChannelDecl("aq", kind="async", capacity=4,
                                 staleness_bound=1, gate_offset=3))
    topo.put("rollout", "aq")
    topo.get("actor", "aq")
    fs = check_topology(topo)
    assert codes(fs) == {"C104"}
    assert max_severity(fs) == "warning"


def test_c105_orphan_channel_blocks_getter_forever():
    topo = ChannelTopology()
    topo.add_channel(ChannelDecl("dangling"))
    topo.get("actor", "dangling")
    fs = check_topology(topo)
    assert codes(fs) == {"C105"}


def test_c106_rank_inversion_on_shared_devices():
    topo = ChannelTopology()
    topo.ranks = {"producer": 1, "consumer": 0}  # inverted
    topo.edges = [("producer", "consumer")]
    topo.devices = {"producer": {0, 1}, "consumer": {1, 2}}
    fs = check_topology(topo)
    assert codes(fs) == {"C106"}


def test_c106_silent_on_disjoint_devices():
    topo = ChannelTopology()
    topo.ranks = {"producer": 1, "consumer": 0}
    topo.edges = [("producer", "consumer")]
    topo.devices = {"producer": {0, 1}, "consumer": {2, 3}}
    assert check_topology(topo) == []


def test_c107_lock_order_inversion():
    topo = ChannelTopology()
    topo.lock_sites = [LockSite("w1", ("L1", "L2")),
                       LockSite("w2", ("L2", "L1"))]
    fs = check_topology(topo)
    assert codes(fs) == {"C107"}


def test_c108_uninterruptible_get():
    topo = ChannelTopology()
    topo.add_channel(ChannelDecl("leaky", closed_on_failure=False))
    topo.put("rollout", "leaky")
    topo.get("actor", "leaky")
    fs = check_topology(topo)
    assert codes(fs) == {"C108"}
    assert max_severity(fs) == "warning"
    # a timeout makes the same get interruptible
    topo.ports[-1].timeout = 5.0
    assert check_topology(topo) == []


def test_async_plan_topology_models_the_staleness_gate():
    t = async_grpo_target()
    plan = plan_for(t)
    topo = build_topology(t.graph, plan, {})
    aqs = [c for c in topo.channels.values() if c.kind == "async"]
    assert len(aqs) == 1
    assert aqs[0].capacity == max(aqs[0].staleness_bound, 1)
    assert check_topology(topo) == []


# ---------------------------------------------------------------------------
# Pass 3 — kernel and RNG defects
# ---------------------------------------------------------------------------
def test_k101_degenerate_grid():
    inv = KernelInvocation(kernel="toy", shape_name="t", grid=(4, 0))
    assert codes(check_invocation(inv)) == {"K101"}
    # a zero batch at the flash wrapper degenerates both the grid and
    # the block/operand relation
    fs = check_invocation(
        flash_invocation("t", B=0, H=28, S=4096, D=128, KV=4))
    assert "K101" in codes(fs) and codes(fs) <= {"K101", "K103"}


def test_k102_block_divisibility():
    inv = flash_invocation("t", B=2, H=28, S=100, D=128, KV=4,
                           block_q=64, block_k=64, clamp=False)
    fs = check_invocation(inv)
    assert codes(fs) == {"K102"}
    assert len(fs) == 2  # block_q and block_k both fail


def test_k102_ssd_chunk_divisibility():
    inv = ssd_invocation("t", B=2, L=1000, H=24, P=64, N=128, chunk=128)
    assert codes(check_invocation(inv)) == {"K102"}


def test_k103_block_exceeds_operand():
    inv = KernelInvocation(
        kernel="toy", shape_name="t", grid=(1,),
        operands=[BlockMap("a", (4,), (8,), lambda i: (0,))])
    assert codes(check_invocation(inv)) == {"K103"}


def test_k104_index_map_out_of_bounds():
    # a block table holding a page id one past the pool
    inv = paged_invocation("t", B=2, H=28, D=128, P=64, page=16, KV=4,
                           nb=8, max_context=128, table_max=64)
    fs = check_invocation(inv)
    assert codes(fs) == {"K104"}
    assert {f.subject.split(":")[-1] for f in fs} == {"k_pages", "v_pages"}


def test_k105_page_table_too_short():
    inv = paged_invocation("t", B=2, H=28, D=128, P=64, page=16, KV=4,
                           nb=4, max_context=128)
    assert codes(check_invocation(inv)) == {"K105"}


def test_k106_gqa_head_mismatch():
    inv = flash_invocation("t", B=2, H=30, S=4096, D=128, KV=4)
    fs = check_invocation(inv)
    # the non-dividing head count is the root cause; the K/V index map
    # consequently walks past the KV axis at the last head (K104)
    assert "K106" in codes(fs)
    assert codes(fs) <= {"K106", "K104"}


# (operand shape, block shape) of the four kernel layouts Mosaic refused
# before they were reblocked
_UNTILED = {
    "paged_kv_head_slice": ((65, 16, 8, 64), (1, 16, 1, 64)),
    "ssd_dt_row": ((2, 32, 4, 128), (1, 1, 1, 128)),
    "ssm_update_x_row": ((8, 32, 64), (1, 1, 64)),
    "sampling_one_row": ((8, 49155), (1, 49155)),
}


@pytest.mark.parametrize("case", sorted(_UNTILED))
def test_k108_block_off_the_mosaic_tiling(case):
    shape, block = _UNTILED[case]

    def inv(blk):
        return KernelInvocation(
            kernel="toy", shape_name="t", grid=(1,),
            operands=[BlockMap("a", shape, blk,
                               lambda i: (0,) * len(shape))])

    assert codes(check_invocation(inv(block))) == {"K108"}
    # the same operand blocked whole is accepted
    assert codes(check_invocation(inv(shape))) == set()


def test_k107_uncovered_kernel_entry():
    fs = check_registry_coverage(
        [flash_invocation("t", B=2, H=28, S=4096, D=128, KV=4)])
    assert codes(fs) == {"K107"}
    assert {"paged_attention", "ssd_scan",
            "grouped_matmul"} <= {f.subject for f in fs}


def test_gmm_spec_clean_at_train_shape():
    inv = gmm_invocation("train_4k", E=8, C=1280, D=2048, F=5632)
    assert check_invocation(inv) == []


def test_r101_combined_fold_collision():
    spec = RNGKeySpec("bad_combined", ("step", "env"),
                      {"step": range(8), "env": range(8)},
                      combine=lambda s, e: s + e)
    fs = check_rng([spec])
    assert codes(fs) == {"R101"}
    assert max_severity(fs) == "error"


def test_r101_missing_domain_is_a_warning():
    spec = RNGKeySpec("no_domain", ("step",), {}, combine=lambda s: s)
    fs = check_rng([spec])
    assert codes(fs) == {"R101"}
    assert max_severity(fs) == "warning"


def test_nested_fold_chain_is_clean():
    spec = RNGKeySpec("nested_ok", ("a", "b"),
                      {"a": range(8), "b": range(8)}, combine="nested")
    assert check_rng([spec]) == []


# ---------------------------------------------------------------------------
# analyze() facade + severity filtering
# ---------------------------------------------------------------------------
def test_analyze_graph_and_min_severity():
    g = grpo_target().graph
    g.add_worker("stray")  # P103 is a warning
    assert codes(analyze(graph=g)) == {"P103"}
    assert analyze(graph=g, min_severity="error") == []


def test_findings_format_and_filter():
    f = Finding("P999", "error", "x", "boom", hint="fix it",
                pass_name="plan")
    assert "P999" in f.format() and "fix it" in f.format()
    assert filter_findings([f], "warning") == [f]
    assert "clean" in format_findings([])


# ---------------------------------------------------------------------------
# strict mode: a corrupted plan is rejected before any worker executes
# ---------------------------------------------------------------------------
def test_strict_rejects_corrupted_plan_before_execution():
    t = grpo_target()
    ctl = Controller(t.cluster, profiles=t.cost_models,
                     scheduler_cfg=t.scheduler_cfg, strict=True)
    plan = ctl.plan(t.graph, total_batch=t.total_batch)
    plan.placement["rollout"] = [99]  # device outside the cluster
    calls = []
    task_fns = {n: (lambda w, c, n=n: calls.append(n) or c)
                for n in t.graph.nodes}
    with pytest.raises(FlowLintError) as ei:
        ctl.execute(plan, {}, task_fns, {"x": 0})
    assert any(f.code == "P203" for f in ei.value.findings)
    assert calls == []  # rejected before bind_placement / any task ran


def test_strict_accepts_clean_plan():
    t = grpo_target()
    ctl = Controller(t.cluster, profiles=t.cost_models,
                     scheduler_cfg=t.scheduler_cfg, strict=True)
    plan = ctl.plan(t.graph, total_batch=t.total_batch)
    ctl._lint(plan, None)  # no raise


def test_non_strict_controller_skips_lint():
    t = grpo_target()
    ctl = Controller(t.cluster, profiles=t.cost_models,
                     scheduler_cfg=t.scheduler_cfg)
    assert ctl.strict is False


# ---------------------------------------------------------------------------
# runtime hygiene: LockOrderRecorder vs a real DeviceLock
# ---------------------------------------------------------------------------
def test_lock_recorder_validates_priority_grants():
    rec = LockOrderRecorder()
    prev = set_lock_observer(rec)
    try:
        lock = DeviceLock("L")
        lock.set_priority("prod", 0, (0, 1))
        lock.set_priority("cons", 1, (0, 1))
        assert lock.acquire("warm")  # park both rivals in the wait set
        done = []

        def contend(w):
            lock.acquire(w)
            done.append(w)
            lock.release(w)

        threads = [threading.Thread(target=contend, args=(w,))
                   for w in ("cons", "prod")]
        for th in threads:
            th.start()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            with lock._cv:
                if len(lock._waiting) == 2:
                    break
            time.sleep(0.005)
        lock.release("warm")
        for th in threads:
            th.join(timeout=5.0)
        assert sorted(done) == ["cons", "prod"]
        # rank 0 producer must be granted before the rank 1 consumer
        assert rec.grants("L") == ["warm", "prod", "cons"]
        assert rec.violations() == []
    finally:
        set_lock_observer(prev)


def test_lock_recorder_flags_inverted_grant():
    rec = LockOrderRecorder()
    rec.record("wait", "L", "cons", 1)
    rec.record("wait", "L", "prod", 0)
    rec.record("grant", "L", "cons", 1)
    assert rec.violations()  # granted over a waiting lower rank


def test_lock_recorder_ignores_timed_out_waiter():
    rec = LockOrderRecorder()
    rec.record("wait", "L", "cons", 1)
    rec.record("wait", "L", "prod", 0)
    rec.record("leave", "L", "prod", 0)  # prod's acquire timed out
    rec.record("grant", "L", "cons", 1)
    assert rec.violations() == []


def test_device_lock_timeout_emits_leave():
    rec = LockOrderRecorder()
    prev = set_lock_observer(rec)
    try:
        lock = DeviceLock("L")
        assert lock.acquire("holder")
        assert lock.acquire("rival", timeout=0.05) is False
        lock.release("holder")
        kinds = [(k, w) for k, _, w, _ in rec.events]
        assert ("leave", "rival") in kinds
        assert rec.violations() == []
    finally:
        set_lock_observer(prev)
