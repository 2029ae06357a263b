"""The readers of the program's own spans and counters, on hand-built
inputs: engine host time and steps per iteration from the program's
spans, switch and sync bytes from its counters, and the device's idle
time charged to the innermost program span of a constructed trace and
of the recorded one."""
import gzip
import importlib.util
from pathlib import Path

import pytest

from bench import program_idle, trace_reduce
from repro.obs import MetricsRegistry, set_registry, tracing

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "fixtures" / "mamba_short.xplane.pb.gz"


def _metric(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(**kw):
    ctx = {"window_s": 10.0, "stage_spans": [], "program_spans": [],
           "trace": None, "iteration_flops": 0.0, "iterations": 0,
           "chips": 1, "peaks": {"bf16_flops": 100.0}}
    ctx.update(kw)
    return ctx


STEPS = [
    # step 0: 10 ms, a prefill chunk and a decode overlapping it: 2 ms host
    ("engine-step", "engine", 0.000, 0.010),
    ("engine-prefill", "engine", 0.001, 0.005),
    ("engine-decode", "engine", 0.004, 0.009),
    # step 1: 4 ms, decode only: 1 ms host
    ("engine-step", "engine", 1.000, 1.004),
    ("engine-decode", "engine", 1.001, 1.004),
    # step 2: nothing to advance: all 1 ms host
    ("engine-step", "engine", 2.000, 2.001),
    ("task", "task", 0.0, 3.0),
]


def test_engine_host_ms_subtracts_the_children_union():
    ctx = _ctx(program_spans=STEPS, iterations=2)
    assert _metric("engine_host_ms")(ctx) == pytest.approx((2 + 1 + 1) / 3)
    assert _metric("engine_steps_per_iter")(ctx) == pytest.approx(1.5)


def test_engine_readers_without_the_spans():
    # the parent program records engine-step spans without children
    steps_only = [s for s in STEPS if s[0] == "engine-step"]
    assert _metric("engine_host_ms")(_ctx(program_spans=steps_only)) is None
    assert _metric("engine_steps_per_iter")(_ctx(iterations=3)) is None
    assert _metric("engine_steps_per_iter")(
        _ctx(program_spans=steps_only)) is None  # no iteration


@pytest.fixture
def registry():
    prev = set_registry(MetricsRegistry())
    yield
    set_registry(prev)


def test_switch_gb_per_iter_reads_the_program_counters(registry):
    from repro.obs import metrics

    read = _metric("switch_gb_per_iter")
    with tracing():
        metrics.active().counter("sync/bytes").inc(1e9)
    # no switch/bytes counter (as the parent program keeps none)
    assert read(_ctx(iterations=2)) is None
    with tracing():
        metrics.active().counter("switch/bytes").inc(3e9)
    assert read(_ctx(iterations=2)) == pytest.approx(2.0)
    assert read(_ctx(iterations=0)) is None


def _trace(ops, host):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": host}


def test_idle_goes_to_the_innermost_program_span():
    # window 0-100; device busy 10-20 and 60-70; idle 0-10, 20-60, 70-100
    trace = _trace([(10, 20, "a"), (60, 70, "b")],
                   [(0, 100, trace_reduce.WINDOW)])
    spans = [(0, 100, "iteration"),
             (5, 50, "engine-step"),
             (25, 35, "engine-decode"),
             (40, 45, "onload:actor"),   # starts inside the step
             (80, 90, "weight-sync")]
    out = program_idle.idle_by_span(trace, spans)
    assert out["busy_s"] == pytest.approx(20e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    got = {n: v * 1e9 for n, v in out["idle_by_span"].items()}
    assert got == pytest.approx({
        "iteration": 5 + 10 + 10 + 10,   # 0-5, 50-60, 70-80, 90-100
        "engine-step": 5 + 5 + 5 + 5,    # 5-10, 20-25, 35-40, 45-50
        "engine-decode": 10,             # 25-35
        "onload:actor": 5,               # 40-45
        "weight-sync": 10})              # 80-90
    groups = {g: v * 1e9 for g, v in out["idle_by_group"].items()}
    assert groups == pytest.approx({"engine": 30, "switch": 15,
                                    "unattributed": 35})
    # the same busy union and window as the stage reduction
    r = trace_reduce.reduce(trace)
    assert (out["busy_s"], out["window_s"]) == (r["busy_s"], r["window_s"])


def test_idle_outside_any_program_span_and_equal_starts():
    trace = _trace([(40, 60, "a")], [(0, 100, trace_reduce.WINDOW)])
    spans = [(10, 30, "task"), (10, 20, "offload:rollout"),
             (70, 130, "execute")]
    out = program_idle.idle_by_span(trace, spans)
    got = {n: v * 1e9 for n, v in out["idle_by_span"].items()}
    # 0-10, 30-40 and 60-70 under no span; 10-20 under the shorter of
    # the two spans that start at 10
    assert got == pytest.approx({program_idle.NO_SPAN: 10 + 10 + 10,
                                 "offload:rollout": 10, "task": 10,
                                 "execute": 30})


def test_recorded_trace_idle_adds_up_to_the_stage_reduction(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    trace = trace_reduce.load(str(path))
    spans = program_idle.load(str(path))
    assert spans == []  # recorded before the program annotated its spans
    r = trace_reduce.reduce(trace, top=100)
    out = program_idle.idle_by_span(trace, spans)
    assert (out["busy_s"], out["window_s"]) == (r["busy_s"], r["window_s"])
    assert list(out["idle_by_span"]) == [program_idle.NO_SPAN]
    assert sum(out["idle_by_group"].values()) == pytest.approx(
        sum(v for _, v in r["idle_gaps"]), rel=1e-9)


def test_load_reads_the_program_spans_of_a_profile(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing() as tr:
            with tr.span("engine-step", "engine"):
                with tr.span("engine-decode", "engine"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = program_idle.load(path)
    assert [n for _, _, n in spans] == ["engine-step", "engine-decode"]
    (s0, e0, _), (s1, e1, _) = spans
    assert s0 <= s1 < e1 <= e0
