#!/usr/bin/env python3
"""Chip benchmark of the GRPO main path: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json`` with its plain reference beside it) and a
traffic mix (``bench/traffic/<name>.json``).  The run builds the
program's ``GRPORunner`` on the chips the cell asks for, collocated as
the mix says, with weights and prompts from ``--seed``; profiles and
plans it (which warms every shape at the cell's batch) and runs whole
warm-up iterations until one compiles nothing: that is set-up.  The window then repeats
``run_iteration`` until ``--seconds`` have passed and ends at the next
iteration boundary.  ``--trace 1`` arms the program's spans and a
``jax.profiler`` trace of the window and reports the per-layer metrics
(``bench/metrics/<name>.py``) instead of the end-to-end ones.

After the window the program is freed and the reference follows the
first four training steps of set-up (``check.py``); the numbers it
compares are printed beside their limits on the last lines of standard
error and under ``checks`` in the result, the last line of standard
output.  Without a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from bench import check, peaks, trace_reduce, traffic  # noqa: E402
from bench.stages import Stages  # noqa: E402

OUT_DIR = ROOT / "bench_out"
# JAX's persistent compilation cache: a fixed directory in the checkout,
# the same one the program's entry points use (the directory is part of
# the cache key, so it never moves)
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# the cell, from data
# ---------------------------------------------------------------------------
def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json") -> dict:
    spec = json.loads(spec_path.read_text())
    w = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in {spec_path.name}")
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    cfg_path = ROOT / entry["file"]
    c = json.loads(cfg_path.read_text())

    return {
        "name": workload, "chips": w["chips"], "config": c,
        "reference": _module(cfg_path.with_name(c["reference"])),
        "mix": traffic.load(w["traffic"]),
        "end_to_end": spec["end_to_end"],
        "per_layer": spec["per_layer"],
        "limits": check.load_limits(workload),
    }


def program_config(c: dict):
    """The program's ModelConfig for configuration ``c``: its registry
    entry with ``c["program"]`` applied, checked key by key against the
    sizes the file states."""
    from repro.configs import get_config

    import dataclasses

    cfg = get_config(c["registry"])
    cfg = cfg.replace(**{k: (dataclasses.replace(getattr(cfg, k), **v)
                             if isinstance(v, dict) else v)
                         for k, v in c["program"].items()})
    for key, attr in c["program_keys"].items():
        got = cfg
        for part in attr.split("."):
            got = getattr(got, part)
        if got != c[key]:
            raise ValueError(f"{c['name']}: the program runs {attr}={got!r} "
                             f"where the configuration states {key}={c[key]!r}")
    return cfg


def program_seed(seed: int) -> int:
    """The seed handed to the program and the reference: ``--seed`` may
    exceed 32 bits, the program's keys take 31."""
    return seed % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# device, cache, compile counting
# ---------------------------------------------------------------------------
def require_devices(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts, while ``on``, programs handed to the backend (compiled, or
    loaded from the persistent cache: ``loaded`` counts those) and
    functions traced."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "backend",
              "/jax/core/compile/jaxpr_trace_duration": "traced"}

    def __init__(self):
        self.on = False
        self.counts = {"backend": 0, "loaded": 0, "traced": 0}
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, fun_name="?", **_):
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1
            self.names.append(f"{self.EVENTS[event]} {fun_name}")

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.counts["loaded"] += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def build_runner(cell: dict, seed: int, stages: Stages):
    from repro.core import Cluster
    from repro.rl import GRPOConfig, GRPORunner
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import TrainHParams

    c, mix, tr = cell["config"], cell["mix"], cell["mix"]["train"]
    hp = TrainHParams(
        optimizer=AdamWConfig(lr=tr["lr"], b1=tr["b1"], b2=tr["b2"],
                              eps=tr["eps"], weight_decay=tr["weight_decay"],
                              clip_norm=tr["clip_norm"]),
        clip_eps_low=tr["clip_eps"], clip_eps_high=tr["clip_eps"])
    rl = GRPOConfig(batch_size=mix["batch"], group_size=mix["group"],
                    prompt_len=mix["prompt_len"],
                    max_new_tokens=mix["max_new_tokens"],
                    temperature=mix["temperature"], iterations=1,
                    mode=mix["mode"], seed=seed,
                    profile_batches=(mix["batch"],))
    runner = GRPORunner(program_config(c), rl, hp,
                        cluster=Cluster(num_nodes=1,
                                        devices_per_node=cell["chips"]))
    runner.task_fns = stages.wrap(runner.task_fns, replace={
        "reward": traffic.reward_stage(mix, c["vocab_size"])})
    return runner


MAX_WARM_ITERATIONS = 8


def set_up(cell: dict, seed: int, quiet: bool = True):
    """Runner, stages and capture after profile, plan and warm-up
    iterations: as many as it takes the actor to reach the train steps
    the capture follows (iteration 0 takes the fourth) and, with
    ``quiet``, one iteration to run without compiling or tracing
    anything.  Returns them with the next iteration's index."""
    stages = Stages()
    capture = check.Capture(cell["mix"]["train"]["b1"])
    stages.before.append(capture.before)
    stages.after.append(capture.after)
    runner = build_runner(cell, seed, stages)
    runner.profile()
    runner.plan_execution()
    it, was_quiet = 0, False
    while not capture.complete or (quiet and not was_quiet):
        if it >= MAX_WARM_ITERATIONS:
            raise RuntimeError(f"still compiling after {it} warm-up iterations")
        counter = CompileCounter()
        counter.on = True
        runner.run_iteration(it)
        counter.close()
        was_quiet = not any(counter.counts.values())
        if not was_quiet:
            compiled = [n for n in counter.names if n.startswith("backend")]
            print(f"warm-up iteration {it}: {counter.counts} {compiled}",
                  flush=True)
        it += 1
    return runner, stages, capture, it


def window(runner, stages: Stages, first: int, seconds: float,
           trace_dir=None):
    """Whole iterations until ``seconds`` have passed.  Returns (start,
    end, iterations, positions trained, program spans or None)."""
    from repro.obs import trace as ptrace

    trained = [0]

    def count(stage, _w, _chunk, out):
        if stage == "actor":
            trained[0] += int(out["tokens"].size)

    stages.after.append(count)
    tracer = None
    if trace_dir is not None:
        tracer = ptrace.install(ptrace.Tracer())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    stages.spans.clear()
    it = first
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            t0 = time.perf_counter()
            while True:
                runner.run_iteration(it)
                it += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            t1 = time.perf_counter()
    finally:
        stages.after.remove(count)
        if trace_dir is not None:
            jax.profiler.stop_trace()
            ptrace.uninstall()
    spans = None
    if tracer is not None:
        spans = [(s.name, s.cat, s.t0, s.t1) for s in tracer.spans()
                 if s.t0 >= t0 and s.t1 <= t1]
    return t0, t1, it - first, trained[0], spans


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices, log=print, keep_trace=None) -> dict:
    c, mix = cell["config"], cell["mix"]
    pseed = program_seed(seed)
    runner, stages, capture, first = set_up(cell, pseed)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s!r} s (profile, plan, {first} warm-up iterations)")

    counter = CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = OUT_DIR / "trace" / f"{cell['name']}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    counter.on = True
    t0, t1, iters, trained, prog_spans = window(runner, stages, first,
                                               seconds, trace_dir)
    counter.close()
    window_s = t1 - t0
    log(f"window {window_s!r} s, {iters} iterations, {trained} positions "
        f"trained; in the window: "
        f"{counter.counts['backend'] - counter.counts['loaded']} compiled, "
        f"{counter.counts['loaded']} loaded from the cache, "
        f"{counter.counts['traced']} traced {counter.names}")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    kind = devices[0].device_kind
    dev_info = {"platform": devices[0].platform, "kind": kind,
                "count": len(devices), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    if not trace:
        values = {"train_tok_s": trained / (window_s * len(devices)),
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        xplane = trace_reduce.find_xplane(str(trace_dir))
        reduced = (trace_reduce.reduce(trace_reduce.load(xplane))
                   if xplane else None)
        if keep_trace and xplane:
            shutil.copy(xplane, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            raise RuntimeError("the traced window holds no device operation")
        dev_info.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        flops = traffic.iteration_flops(cell["reference"], c, mix)
        ctx = {"window_s": window_s, "stage_spans": stages.in_window(t0, t1),
               "program_spans": prog_spans, "trace": reduced,
               "iteration_flops": sum(flops.values()), "iterations": iters,
               "chips": len(devices), "peaks": peaks.peaks_for(kind)}
        for m in cell["per_layer"]:
            value = _module(ROOT / "bench" / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # free the program before the reference runs
    runner.teardown()
    del runner, stages
    gc.collect()
    ref_side = check.Reference(cell["reference"], c, mix).follow(pseed, capture)
    read = check.readings(capture.program_side(), ref_side, capture, mix)
    correct, checks = check.judge(read, cell["limits"])
    log("readings: " + json.dumps(read))
    result = {"correct": correct, "attempted": iters, "failed": 0,
              "metrics": metrics, "device": dev_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb to this file")
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        devices = require_devices(cell["chips"])
        peaks.peaks_for(devices[0].device_kind)
        cache = enable_cache()
        print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
              f"cache {cache}", flush=True)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, log=lambda s: print(s, flush=True),
                          keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 — the cause goes to stderr, no result
        traceback.print_exc()
        return 1
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    if not result["checks"]:
        print("check: no limits for this workload", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
