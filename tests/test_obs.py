"""Observability subsystem: tracer correctness, deterministic export,
channel block-time accounting, plan-vs-actual reports, drift feedback,
and the tracing-overhead bound."""
from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from repro.core.channel import Channel
from repro.core.faults import HeartbeatMonitor
from repro.core.pipeline import ExecutionFlowManager
from repro.core.profiler import CostModel
from repro.core.scheduler import Leaf, Pipelined, Temporal
from repro.core.simulator import Simulator
from repro.obs import (
    MetricsRegistry,
    Tracer,
    default_registry,
    format_snapshot,
    set_registry,
    tracing,
)
from repro.obs import trace as trace_mod
from repro.obs.report import (
    apply_drift,
    complement,
    intersect,
    merge_intervals,
    plan_vs_actual,
    replay_sim,
    subtract,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing disarmed and a fresh
    registry — tracing must stay default-off outside tests that arm it."""
    assert trace_mod.active() is None
    prev = set_registry(MetricsRegistry())
    yield
    trace_mod.uninstall()
    set_registry(prev)


# ---------------------------------------------------------------------------
# tracer basics
# ---------------------------------------------------------------------------
def test_spans_nest_properly():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", "phase"):
        clk.advance(1.0)
        with tr.span("inner", "task"):
            clk.advance(2.0)
        clk.advance(0.5)
    spans = {s.name: s for s in tr.spans()}
    outer, inner = spans["outer"], spans["inner"]
    # proper nesting: inner fully contained in outer, same thread lane
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    assert inner.dur == pytest.approx(2.0)
    assert outer.dur == pytest.approx(3.5)
    assert outer.tid == inner.tid


def test_decorator_and_context_attributes():
    """set_context stamps live spans; args the body adds are recorded."""
    clk = FakeClock()
    tr = Tracer(clock=clk)

    def work():
        with tr.span("work", "task", chunk=0) as args:
            clk.advance(1.0)
            args["bytes"] = 7

    tr.set_context(iteration=3)
    work()
    tr.set_context(iteration=None)
    work()
    a, b = tr.spans("task")
    assert a.args == {"iteration": 3, "chunk": 0, "bytes": 7}
    assert b.args == {"chunk": 0, "bytes": 7}
    assert a.dur == pytest.approx(1.0)


def test_span_is_recorded_when_its_body_raises():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with pytest.raises(ValueError):
        with tr.span("boom", "task") as args:
            clk.advance(0.5)
            args["seen"] = True
            raise ValueError("x")
    (s,) = tr.spans("task")
    assert s.name == "boom" and s.dur == pytest.approx(0.5)
    assert s.args == {"seen": True}


def test_thread_lanes_and_names():
    tr = Tracer()

    def worker():
        with tr.span("w", "task"):
            pass

    th = threading.Thread(target=worker, name="pipe-prod-test")
    with tr.span("m", "task"):
        th.start()
        th.join()
    spans = {s.name: s for s in tr.spans()}
    assert spans["m"].tid != spans["w"].tid
    names = [e["args"]["name"] for e in tr.to_chrome()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "pipe-prod-test" in names


def test_export_is_deterministic():
    def build():
        clk = FakeClock()
        tr = Tracer(clock=clk)
        with tr.span("a", "task", worker="a"):
            clk.advance(1.0)
        tr.instant("mark", "event")
        tr.counter("depth", 3)
        clk.advance(0.25)
        with tr.span("b", "task", worker="b"):
            clk.advance(0.5)
        return json.dumps(tr.to_chrome(), sort_keys=True)

    assert build() == build()


def test_export_chrome_schema(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("t", "task"):
        clk.advance(1e-3)
    tr.export(str(tmp_path / "t.json"))
    doc = json.loads((tmp_path / "t.json").read_text())
    evs = doc["traceEvents"]
    x = [e for e in evs if e["ph"] == "X"]
    assert len(x) == 1 and x[0]["dur"] == pytest.approx(1000.0)
    assert any(e["ph"] == "M" for e in evs)


def test_tracing_default_off_and_scoped():
    assert trace_mod.active() is None
    with tracing() as tr:
        assert trace_mod.active() is tr
    assert trace_mod.active() is None


# ---------------------------------------------------------------------------
# live spans on the profiler's clock
# ---------------------------------------------------------------------------
def _profiled_program_events(log_dir, fn):
    """(name, start_ns, end_ns) of the ``repro:`` events on the host plane
    of a ``jax.profiler`` trace of ``fn()``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    return sorted((e.name, int(e.start_ns), int(e.end_ns))
                  for line in host.lines for e in line.events
                  if e.name.startswith(trace_mod.ANNOTATION_PREFIX))


def _jitted_task():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((32, 32), jnp.float32)
    f(x).block_until_ready()  # compiled outside the trace

    def task(w, chunk):
        f(x).block_until_ready()
        return chunk
    return task


def _run_one_task(task):
    workers = {"a": _DevWorker([0])}
    ExecutionFlowManager(workers, {"a": task}).run(
        Leaf("a", 1, 4), {"x": np.zeros((4, 2), np.float32)})


def test_live_spans_appear_on_the_profiler_host_plane(tmp_path):
    task = _jitted_task()

    def run():
        with tracing() as tr:
            with tr.span("outer", "phase"):
                _run_one_task(task)

    events = _profiled_program_events(tmp_path, run)
    assert [n for n, _, _ in events] == ["repro:a", "repro:outer"]
    (_, a0, a1), (_, o0, o1) = events
    # the executor's task span nests inside the span around it
    assert o0 <= a0 < a1 <= o1


def test_no_annotation_while_tracing_is_disarmed(tmp_path):
    task = _jitted_task()
    assert _profiled_program_events(tmp_path, lambda: _run_one_task(task)) == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_metrics_registry_types_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(5)
    reg.gauge("g").set(2)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"]["value"] == 3
    assert snap["g"]["value"] == 2 and snap["g"]["max"] == 5
    assert snap["h"]["count"] == 4 and snap["h"]["max"] == 4.0
    assert snap["h"]["mean"] == pytest.approx(2.5)
    with pytest.raises(TypeError):
        reg.gauge("c")
    lines = format_snapshot(snap, prefix="g")
    assert len(lines) == 1 and lines[0].startswith("g")


def test_metrics_gated_on_tracing():
    from repro.obs import metrics as metrics_mod
    assert metrics_mod.active() is None
    with tracing():
        assert metrics_mod.active() is default_registry()
    assert metrics_mod.active() is None


# ---------------------------------------------------------------------------
# channel block-time accounting vs a hand-built two-worker pipeline
# ---------------------------------------------------------------------------
def test_channel_block_gauges_match_hand_built_pipeline():
    delay = 0.02
    with tracing() as tr:
        ch = Channel("hand-pipe", capacity=1)

        def consumer():
            for _ in range(3):
                time.sleep(delay)  # slow stage: producer must wait
                ch.get()

        th = threading.Thread(target=consumer)
        th.start()
        for i in range(3):
            ch.put(i)
        th.join()
    waits = tr.spans("channel-wait")
    put_waits = [s for s in waits if s.name == "put-wait"]
    # capacity 1 + slow consumer: puts 2 and 3 block ~delay each
    assert len(put_waits) == 2
    total = sum(s.dur for s in put_waits)
    assert total == pytest.approx(2 * delay, rel=0.5)
    snap = default_registry().snapshot()
    assert snap["channel/hand-pipe/put_block_s"]["value"] == pytest.approx(
        total, rel=1e-6)
    assert snap["channel/hand-pipe/put_block_s_hist"]["count"] == 2


def test_channel_records_nothing_when_disarmed():
    ch = Channel("silent", capacity=2)
    ch.put(1)
    ch.get()
    assert default_registry().snapshot() == {}


# ---------------------------------------------------------------------------
# executor task spans: per-device exclusivity
# ---------------------------------------------------------------------------
class _DevWorker:
    def __init__(self, devices):
        self.devices = tuple(devices)
        self.offloaded = False

    def offload(self):
        self.offloaded = True

    def onload(self):
        self.offloaded = False


def _overlaps(ivs):
    ivs = sorted(ivs)
    return any(ivs[i][1] > ivs[i + 1][0] + 1e-9 for i in range(len(ivs) - 1))


def test_task_spans_never_overlap_on_exclusive_devices():
    workers = {"a": _DevWorker([0]), "b": _DevWorker([1])}

    def task(w, chunk):
        time.sleep(0.002)
        return chunk

    fns = {"a": task, "b": task}
    sched = Pipelined(Leaf("a", 1, 2), Leaf("b", 1, 2), granularity=2,
                      n_s=1, n_t=1)
    batch = {"x": np.zeros((8, 2), np.float32)}
    with tracing() as tr:
        ExecutionFlowManager(workers, fns).run(sched, batch)
    tasks = tr.spans("task")
    assert len(tasks) == 8  # 4 chunks through each of 2 stages
    by_dev = {}
    for s in tasks:
        for d in s.args["devices"]:
            by_dev.setdefault(d, []).append((s.t0, s.t1))
    assert set(by_dev) == {0, 1}
    for d, ivs in by_dev.items():
        assert not _overlaps(ivs), f"overlapping task spans on device {d}"
    # pipe-stage chunk spans recorded from the named executor threads
    assert len(tr.spans("pipe")) == 8


def test_temporal_shared_device_spans_sequential():
    workers = {"a": _DevWorker([0]), "b": _DevWorker([0])}
    fns = {"a": lambda w, c: c, "b": lambda w, c: c}
    sched = Temporal(Leaf("a", 1, 4), Leaf("b", 1, 4))
    with tracing() as tr:
        ExecutionFlowManager(workers, fns).run(
            sched, {"x": np.zeros((4, 2), np.float32)})
    ivs = [(s.t0, s.t1) for s in tr.spans("task")]
    assert len(ivs) == 2 and not _overlaps(ivs)


# ---------------------------------------------------------------------------
# context switch and iteration spans
# ---------------------------------------------------------------------------
def _state_worker(name):
    import jax.numpy as jnp

    from repro.core.worker import Worker

    w = Worker(name, devices=(0,))
    w.register_state("params", {"w": jnp.arange(8.0)})  # 32 bytes
    w.register_state("opt", {"m": jnp.zeros(4)})         # 16 bytes
    return w


def test_onload_span_ends_after_the_transfer_is_ready(monkeypatch):
    from repro.core import switching

    w = _state_worker("ready/0")
    sw = switching.ContextSwitcher({"r": w})
    real, ready = switching.jax.block_until_ready, []

    def block(trees):
        out = real(trees)
        ready.append((time.perf_counter(), trees))
        return out

    monkeypatch.setattr(switching.jax, "block_until_ready", block)
    sw.offload_worker("r")
    assert sw.onload_worker("r") > 0.0  # untraced: blocks all the same
    assert len(ready) == 1
    sw.offload_worker("r")
    with tracing() as tr:
        sw.onload_worker("r")
    (span,) = tr.spans("switch")
    t_ready, trees = ready[-1]
    assert span.name == "onload:r" and span.t1 >= t_ready
    # the block waited on the very trees now in the worker's state
    assert {id(t) for t in trees} == {id(w.get_state(k))
                                      for k in ("opt", "params")}
    assert sorted(span.args["key"].split("+")) == ["opt", "params"]
    assert span.args["bytes"] == 48
    w.shutdown()


def test_switch_bytes_counted_per_key():
    from repro.core.switching import ContextSwitcher

    w = _state_worker("bytes/0")
    sw = ContextSwitcher({"b": w})
    with tracing() as tr:
        sw.offload_worker("b")
        sw.offload_worker("b")  # nothing resident: no span, no bytes
        sw.onload_worker("b")
    spans = tr.spans("switch")
    assert [(s.name, s.args["bytes"]) for s in spans] == [
        ("offload:b", 16), ("offload:b", 32), ("onload:b", 48)]
    assert [s.args["key"] for s in spans[:2]] == ["opt", "params"]
    assert default_registry().snapshot()["switch/bytes"]["value"] == 96
    w.shutdown()


def test_iteration_span_is_named_iteration_with_its_number():
    from repro.rl.runner import WorkflowRunner

    class Runner(WorkflowRunner):
        def __init__(self):
            self.fault_injector = None

        def _iteration(self):
            with trace_mod.active().span("work", "task"):
                return "out"

        def _record_stats(self, it, wall, out):
            return it, out

    with tracing() as tr:
        assert Runner().run_iteration(4) == (4, "out")
        assert Runner().run_iteration(5) == (5, "out")
    its = tr.spans("iteration")
    assert [(s.name, s.args) for s in its] == [
        ("iteration", {"iteration": 4}), ("iteration", {"iteration": 5})]
    assert [s.args for s in tr.spans("task")] == [{"iteration": 4},
                                                 {"iteration": 5}]


# ---------------------------------------------------------------------------
# interval algebra
# ---------------------------------------------------------------------------
def test_interval_algebra():
    assert merge_intervals([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert intersect([(0, 2), (3, 4)], [(1, 3.5)]) == [(1, 2), (3, 3.5)]
    assert subtract([(0, 4)], [(1, 2), (3, 5)]) == [(0, 1), (2, 3)]
    assert complement([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


# ---------------------------------------------------------------------------
# plan-vs-actual on simulated profiles
# ---------------------------------------------------------------------------
def _toy_profiles():
    return {
        "gen": CostModel("gen", base_time=0.5, slope_time=0.02,
                         onload_time=0.2, offload_time=0.1),
        "train": CostModel("train", base_time=0.3, slope_time=0.01),
    }


class _FakePlan:
    def __init__(self, schedule, placement):
        self.schedule = schedule
        self.placement = placement
        self.members = {}


def test_plan_vs_actual_matches_prediction_on_replayed_sim():
    profiles = _toy_profiles()
    sched = Temporal(Leaf("gen", 4, 64), Leaf("train", 4, 64),
                     switch_cost=0.3)
    placement = {"gen": [0, 1, 2, 3], "train": [0, 1, 2, 3]}
    sim = Simulator(profiles).run(sched, 64)
    tracer = replay_sim(sim, placement=placement)
    rep = plan_vs_actual(_FakePlan(sched, placement), profiles, tracer, 64)
    # a replayed simulation IS the prediction: ratio lands at 1 exactly
    assert rep.wall_ratio == pytest.approx(1.0, abs=1e-9)
    assert all(r.ratio == pytest.approx(1.0, abs=1e-9) for r in rep.drift)
    # the switch bubble is attributed, not left as idle
    gaps = rep.gap_totals()
    assert gaps["switch"] == pytest.approx(0.3 * len(placement["gen"]),
                                           rel=1e-6)
    assert rep.bubble_fraction() > 0


def test_plan_vs_actual_pipelined_straggler_attribution():
    profiles = _toy_profiles()
    sched = Pipelined(Leaf("gen", 2, 16), Leaf("train", 2, 16),
                      granularity=16, n_s=2, n_t=2)
    placement = {"gen": [0, 1], "train": [2, 3]}
    sim = Simulator(profiles).run(sched, 64)
    tracer = replay_sim(sim, placement=placement)
    rep = plan_vs_actual(_FakePlan(sched, placement), profiles, tracer, 64)
    assert rep.wall_ratio == pytest.approx(1.0, abs=1e-9)
    # train's devices idle while gen fills the pipeline: straggler gap
    train_dev = next(d for d in rep.devices if d.device == 2)
    assert train_dev.gaps["straggler"] > 0


def test_drift_feedback_scales_cost_models():
    profiles = _toy_profiles()
    sched = Leaf("gen", 4, 64)
    placement = {"gen": [0, 1, 2, 3]}
    sim = Simulator(profiles).run(sched, 64)
    # fabricate a measured timeline 2x slower than predicted
    tracer = Tracer(clock=lambda: 0.0)
    tracer.epoch = 0.0
    for s in sim.spans:
        tracer.add(s.worker, "task", s.start, s.start + 2 * (s.end - s.start),
                   lane=s.worker, worker=s.worker, devices=placement[s.worker])
    tracer.add("iteration", "iteration", 0.0, 2 * sim.makespan, lane="run")
    rep = plan_vs_actual(_FakePlan(sched, placement), profiles, tracer, 64)
    assert rep.wall_ratio == pytest.approx(2.0, rel=1e-6)
    base0, slope0 = profiles["gen"].base_time, profiles["gen"].slope_time
    applied = apply_drift(profiles, rep, blend=1.0)
    assert applied["gen"] == pytest.approx(2.0, rel=1e-6)
    assert profiles["gen"].base_time == pytest.approx(2 * base0)
    assert profiles["gen"].slope_time == pytest.approx(2 * slope0)
    # blended drift moves the simulator's prediction toward measurement
    sim2 = Simulator(profiles).run(sched, 64)
    assert sim2.makespan == pytest.approx(2 * sim.makespan, rel=1e-6)


def test_replay_export_roundtrip_deterministic():
    profiles = _toy_profiles()
    sched = Temporal(Leaf("gen", 2, 32), Leaf("train", 2, 32),
                     switch_cost=0.1)

    def build():
        sim = Simulator(profiles).run(sched, 32)
        tracer = replay_sim(sim, placement={"gen": [0], "train": [0]})
        return json.dumps(tracer.to_chrome(), sort_keys=True)

    assert build() == build()


# ---------------------------------------------------------------------------
# straggler cadence
# ---------------------------------------------------------------------------
def test_heartbeat_interval_percentile():
    clk = FakeClock()
    hb = HeartbeatMonitor(timeout=1e9, clock=clk)
    assert hb.interval_percentile("w") is None
    for dt in (1.0, 1.0, 1.0, 10.0):
        clk.advance(dt)
        hb.beat("w")
    p95 = hb.interval_percentile("w", 95.0)
    assert p95 == pytest.approx(10.0)
    assert hb.interval_percentile("w", 50.0) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# overhead bound: tracing on vs off at the executor choke point
# ---------------------------------------------------------------------------
def test_tracing_overhead_within_bound():
    workers = {"a": _DevWorker([0]), "b": _DevWorker([1])}

    def task(w, chunk):
        time.sleep(0.002)
        return chunk

    fns = {"a": task, "b": task}
    sched = Pipelined(Leaf("a", 1, 4), Leaf("b", 1, 4), granularity=4,
                      n_s=1, n_t=1)
    batch = {"x": np.zeros((16, 2), np.float32)}

    def run_once():
        mgr = ExecutionFlowManager(workers, fns)
        t0 = time.perf_counter()
        mgr.run(sched, batch)
        return time.perf_counter() - t0

    run_once()  # warm thread/allocator paths
    off = min(run_once() for _ in range(9))
    with tracing():
        run_once()
        on = min(run_once() for _ in range(9))
    assert on <= off * 1.05, (
        f"tracing overhead {100 * (on / off - 1):.1f}% exceeds 5% bound "
        f"(off {off * 1e3:.2f}ms, on {on * 1e3:.2f}ms)")


# ---------------------------------------------------------------------------
# logging satellite
# ---------------------------------------------------------------------------
def test_log_levels_and_trace_routing(capsys):
    from repro.utils import logging as rlog
    prev = rlog.set_level("warn")
    try:
        with tracing() as tr:
            rlog.info("tag", "hidden on stdout")
            rlog.warn("tag", "visible", k=1)
        out = capsys.readouterr().out
        assert "visible" in out and "hidden on stdout" not in out
        # both lines land in the trace regardless of the stdout threshold
        logs = tr.instants("log")
        assert [i.args["level"] for i in logs] == ["info", "warn"]
        snap = default_registry().snapshot()
        assert snap["log/info"]["value"] == 1
        assert snap["log/warn"]["value"] == 1
    finally:
        rlog.set_level("debug" if prev == 10 else
                       {10: "debug", 20: "info", 30: "warn",
                        40: "error"}[prev])


def test_log_env_level_parsing(monkeypatch):
    from repro.utils import logging as rlog
    monkeypatch.setenv("REPRO_LOG_LEVEL", "error")
    assert rlog._env_level() == rlog.LEVELS["error"]
    monkeypatch.setenv("REPRO_LOG_LEVEL", "bogus")
    assert rlog._env_level() == rlog.LEVELS["info"]


def test_log_lines_do_not_interleave(capsys):
    from repro.utils import logging as rlog
    n, threads = 50, []
    for i in range(4):
        def emit(i=i):
            for j in range(n):
                rlog.warn("interleave", f"t{i}-{j}")
        threads.append(threading.Thread(target=emit, name=f"pipe-prod-{i}"))
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 4 * n
    # every line is whole: exactly one message token, well-formed prefix
    for line in lines:
        assert line.count("interleave") == 1 and line.startswith("[")
