"""Fast checks of the benchmark's yardstick: FLOP counts against a hand
count, the per-layer metric readers on synthetic spans, the trace
reduction on constructed intervals, the direction measure of the
correctness check, the peak table, and ``run.main`` on a machine without
a chip."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench import check, peaks, run, trace_reduce, traffic

BENCH = Path(__file__).resolve().parents[1]


def _module(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric(name):
    return _module(BENCH / "metrics" / f"{name}.py").read


GRANITE = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
           "head_dim": 2, "num_local_experts": 4, "num_experts_per_tok": 2,
           "intermediate_size": 3, "vocab_size": 10, "num_hidden_layers": 1}
MAMBA = {"d_model": 4, "d_state": 2, "headdim": 2, "expand": 2, "d_conv": 4,
         "chunk_size": 8, "vocab_size": 10, "n_layer": 1}


def test_granite_token_flops_hand_count():
    ref = _module(BENCH / "configs" / "granite_moe_3b_a800m.py")
    # q,o: 4*2*2 each; k,v: 4*1*2 each; scores+values at context 5:
    # 2*2*2*5; top-2 experts of 3 matrices 4x3; router 4x4; head 4x10
    macs = (16 + 16 + 8 + 8) + 40 + 2 * 3 * 12 + 16 + 40
    assert ref.token_flops(GRANITE, 5) == 2 * macs


def test_mamba_token_flops_hand_count():
    ref = _module(BENCH / "configs" / "mamba2_370m.py")
    # d_inner 8, 4 heads of 2, state 2: in_proj 4x(16+4+4), out_proj 8x4,
    # conv 4x12, D skip 4*2, head 4x10
    fixed = 4 * 24 + 8 * 4 + 4 * 12 + 8
    decode = 2 * 4 * 2 * 2                  # state update + readout
    chunked = 6 * 2 / 2 + 4 * 2 * 6 / 2 + 2 * 4 * 2 * 2  # Q = min(8, 6)
    assert ref.token_flops(MAMBA, 3) == 2 * (fixed + decode + 40)
    assert ref.token_flops(MAMBA, 3, seq=6) == 2 * (fixed + chunked + 40)


def test_iteration_flops_composition():
    class Flat:
        @staticmethod
        def token_flops(c, context, seq=0):
            return 1.0
    mix = {"batch": 8, "group": 4, "prompt_len": 3, "max_new_tokens": 5}
    f = traffic.iteration_flops(Flat, {}, mix)
    assert f == {"rollout": 2 * 3 + 8 * 4, "inference": 8 * 8,
                 "actor": 3 * 8 * 8}


def _ctx(**kw):
    ctx = {"window_s": 10.0, "stage_spans": [], "program_spans": [],
           "trace": None, "iteration_flops": 0.0, "iterations": 0,
           "chips": 1, "peaks": {"bf16_flops": 100.0}}
    ctx.update(kw)
    return ctx


def test_stage_shares_union_spans():
    spans = [("rollout", 0.0, 4.0), ("rollout", 3.0, 6.0),
             ("inference", 6.0, 7.0), ("actor", 7.0, 9.5)]
    ctx = _ctx(stage_spans=spans)
    assert _metric("rollout_share")(ctx) == pytest.approx(60.0)
    assert _metric("inference_share")(ctx) == pytest.approx(10.0)
    assert _metric("actor_share")(ctx) == pytest.approx(25.0)


def test_program_span_readers():
    spans = [("engine-step", "engine", 0.0, 0.002),
             ("engine-step", "engine", 1.0, 1.004),
             ("offload:actor", "switch", 2.0, 3.0),
             ("prefetch", "switch", 2.5, 4.0),
             ("weight-sync", "sync", 5.0, 5.5),
             ("execute", "phase", 0.0, 9.0)]
    ctx = _ctx(program_spans=spans)
    assert _metric("engine_step_ms")(ctx) == pytest.approx(3.0)
    assert _metric("switch_share")(ctx) == pytest.approx(25.0)


def test_readers_return_none_without_data():
    ctx = _ctx()
    for name in ("rollout_share", "inference_share", "actor_share",
                 "engine_step_ms", "switch_share", "idle_share", "mfu"):
        assert _metric(name)(ctx) is None, name


def test_idle_share_and_mfu():
    ctx = _ctx(trace={"busy_s": 2.5, "window_s": 10.0},
               iteration_flops=50.0, iterations=4, chips=2)
    assert _metric("idle_share")(ctx) == pytest.approx(75.0)
    # 200 FLOP over 10 s x 2 chips x 100 FLOP/s
    assert _metric("mfu")(ctx) == pytest.approx(10.0)


def test_direction_gap_hand_count():
    a = {"x": np.array([1.0, 0.0], np.float32), "y": np.array([[0.0, 2.0]])}
    assert check.direction_gap(a, a) == pytest.approx(0.0, abs=1e-12)
    ortho = {"x": np.array([0.0, 1.0]), "y": np.array([[0.0, 0.0]])}
    assert check.direction_gap(ortho, a) == pytest.approx(1.0)
    part = {"x": np.array([3.0, 0.0]), "y": np.array([[0.0, 0.0]])}
    # cos = 3 / (3 * sqrt(5)); the scale of either side does not count
    assert check.direction_gap(part, a) == pytest.approx(1 - 5 ** -0.5)


def test_trace_reduce_constructed():
    ms = 1_000_000
    trace = {
        "host": [(0, 100 * ms, "bench:window"),
                 (0, 60 * ms, "bench:rollout"),
                 (60 * ms, 100 * ms, "bench:actor")],
        "devices": {"/device:TPU:0": {
            "ops": [(10 * ms, 20 * ms, "fusion.1"), (15 * ms, 30 * ms, "dot.2"),
                    (70 * ms, 90 * ms, "dot.2"), (95 * ms, 120 * ms, "copy.3")],
            "modules": [(5 * ms, 35 * ms, "jit_step"),
                        (65 * ms, 130 * ms, "jit_train")]}},
    }
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)  # 10-30, 70-90, 95-100
    ops = dict(r["device_ops"])
    assert ops["jit_train/dot.2"] == pytest.approx(0.02)
    assert ops["jit_step/dot.2"] == pytest.approx(0.015)
    idle = dict(r["idle_gaps"])
    # 0-10, 30-60 under rollout; 60-70, 90-95 under actor
    assert idle["rollout"] == pytest.approx(0.04)
    assert idle["actor"] == pytest.approx(0.015)


def test_trace_reduce_needs_window_and_ops():
    assert trace_reduce.reduce({"host": [], "devices": {}}) is None


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def test_main_without_chip_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", "granite.grpo-short", "--seed", "5",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_every_cell_loads_from_data():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["mix"]["batch"] % cell["mix"]["group"] == 0
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        for m in cell["per_layer"]:
            assert (BENCH / "metrics" / f"{m['name']}.py").exists()
