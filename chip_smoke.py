#!/usr/bin/env python3
"""Chip smoke test: the GRPO main path, end to end, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # rollout/trainer placement on a 2x2 host

One chip: granite-moe-3b-a800m at its published widths, cut in depth to
fit one v5e (``LAYERS``), random weights from ``SEED``.  The script checks
the compiled fused-sampling kernel against its oracle, compares a forward
on the chip with the same forward on the host CPU, then runs GRPO
iterations through ``GRPORunner`` (profile -> Controller plan ->
PagedEngine rollout -> inference -> reward -> actor train step -> weight
sync) and checks what comes out: finite loss and grad-norm, tokens
generated, the engine on a weight version above 0, every worker's mesh
holding exactly the chips its placement names.

Four chips: the same GRPO run twice from the same seed, once
``disaggregated`` (rollout and actor on disjoint chips, asserted from the
arrays' device sets) and once ``collocated``, then one fixed batch
through the actor train step data-parallel over the actor's chips and on
one chip, whose loss and grad-norm must agree.

Every phase raises on failure.  Without a TPU the script exits non-zero
before any phase runs.  The last line of standard output is a JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import Cluster  # noqa: E402
from repro.kernels import ops as kops, ref as kref  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.rl import GRPOConfig, GRPORunner  # noqa: E402
from repro.train.trainer import TrainHParams, make_train_step  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

MODEL = "granite-moe-3b-a800m"
SEED = 0
# Depth cut: the deepest that fits one v5e (16 GB).  By its
# memory_analysis() for a v5e the compiled actor train step (batch 32 x 48
# tokens, f32 params + grads + two Adam moments, outputs not aliased to
# inputs) needs 7.41 GB at 2 layers, 10.17 GB at 3 and 12.85 GB at 4.
# Around it the runner keeps more weight copies on the device (rollout,
# engine and inference copies, on/offload round trips while profiling)
# and the paged KV pool (0.27 GB per layer).  On the chip, 2 layers
# peaked at 10.70 GB of peak_bytes_in_use, about 34.5 bytes per parameter
# besides the KV pool: about 14.4 GB at 3 layers, which has not been run,
# and 17.9 GB at 4.
LAYERS = 2

# Logit tolerances.  Per position p, e_p = max_v |chip - cpu| / max_v |cpu|;
# the gates are on a quantile of e_p and on top-1 agreement, and max e_p
# is printed but not gated.  At the chip's default precision every f32
# matmul rounds its operands to bf16 (8-bit significand, relative error
# up to 2^-8 per product), which across the ~20 matmuls from embedding to
# logits moves a typical position by about 1e-2 of its logit scale (a CPU
# forward with bf16-rounded weights, activations unrounded: median e_p
# 0.9%; rounding both operands about doubles it): median <= 5e-2.
# A token whose router holds two experts closer than that error at the
# top-8 boundary routes differently and moves by O(0.1-0.5) (the same CPU
# emulation with only the router rounded: one position of 64 at 0.19, the
# median at 0.2%), so the maximum is not bounded by rounding; top-1 flips
# where two logits lie closer than the error: >= 0.75.  At "highest" the
# chip emulates f32 and only reduction order differs: 90th percentile
# <= 1e-3 and top-1 >= 0.97.
LOGIT_TOL = {"default": (0.5, 5e-2, 0.75), "highest": (0.9, 1e-3, 0.97)}
# Actor step data-parallel vs one chip: the same f32 program, reduced in a
# different order.
STEP_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu() -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform} "
              f"devices); nothing was run", file=sys.stderr)
        raise SystemExit(1)
    return devices


def model_config(layers: int = LAYERS):
    return get_config(MODEL).replace(num_layers=layers)


def grpo_config(mode: str, iterations: int = 3) -> GRPOConfig:
    return GRPOConfig(batch_size=32, group_size=8, prompt_len=16,
                      max_new_tokens=32, iterations=iterations, mode=mode,
                      seed=SEED, profile_batches=(32,))


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def device_set(tree) -> set:
    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            out |= set(leaf.sharding.device_set)
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def sampler_phase(cfg) -> None:
    """The fused sampling kernel (compiled on a TPU backend) against its
    oracle at the engine's shapes: tokens equal, logprobs close."""
    B, V = 8, cfg.padded_vocab
    kl, kg = jax.random.split(jax.random.PRNGKey(SEED))
    logits = 4.0 * jax.random.normal(kl, (B, V), jnp.float32)
    gumbel = jax.random.gumbel(kg, (B, V), jnp.float32)
    for temperature in (1.0, 0.0):
        tok, lp = kops.fused_sample(logits, gumbel, temperature=temperature,
                                    vocab_size=cfg.vocab_size)
        want_tok, want_lp = kref.fused_sample_ref(
            logits, gumbel, temperature=temperature,
            vocab_size=cfg.vocab_size)
        same = int(np.sum(np.asarray(tok) == np.asarray(want_tok)))
        dlp = float(np.max(np.abs(np.asarray(lp) - np.asarray(want_lp))))
        print(f"sampler: fused_sample B={B} V={V} temperature={temperature}"
              f" backend={jax.default_backend()}: tokens equal {same}/{B}, "
              f"max |dlogprob|={dlp!r}")
        check(same == B, "fused sampling tokens differ from the oracle")
        check(dlp <= 1e-4, "fused sampling logprobs differ from the oracle")


def logits_phase(cfg, params) -> None:
    """Forward on the default device against the same params on the host
    CPU, at the chip's default precision and at "highest"."""
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (4, 16), 3,
                                cfg.vocab_size)
    cpu = jax.devices("cpu")[0]
    V = cfg.vocab_size

    def forward(p, t):
        return M.forward(p, cfg, t)[0][..., :V]

    ref = np.asarray(jax.jit(forward)(jax.device_put(params, cpu),
                                      jax.device_put(tokens, cpu)))
    scale = np.max(np.abs(ref), axis=-1)
    for precision, (q, tol, top1_min) in LOGIT_TOL.items():
        with jax.default_matmul_precision(precision):
            got = np.asarray(jax.jit(forward)(params, tokens))
        check(bool(np.all(np.isfinite(got))), "non-finite logits on chip")
        err = np.max(np.abs(got - ref), axis=-1) / scale  # per position
        at_q = float(np.quantile(err, q))
        top1 = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
        print(f"logits: {precision} precision vs cpu, shape "
              f"{tuple(got.shape)}: per-position rel err median="
              f"{float(np.median(err))!r} q{q}={at_q!r} (tol {tol}) "
              f"max={float(np.max(err))!r}; top-1 agreement={top1!r} "
              f"(min {top1_min})")
        check(at_q <= tol, f"{precision}-precision logits: q{q} {at_q}")
        check(top1 >= top1_min, f"{precision}-precision top-1 {top1}")


def check_placement(runner, devices) -> None:
    """Each worker sits exactly on the chips its plan placement names:
    cluster ids index real devices, nothing folds."""
    for name, ids in runner.plan.placement.items():
        w = runner.workers[name]
        check(tuple(w.devices) == tuple(ids),
              f"{name} bound to {w.devices}, plan says {ids}")
        check(all(0 <= i < len(devices) for i in ids),
              f"{name} placed on cluster ids {ids} beyond {len(devices)} "
              f"devices")
        mesh = w.device_mesh
        got = set() if mesh is None else set(mesh.devices.flat)
        check(got == {devices[i] for i in ids},
              f"{name} mesh holds {got}, placement names {ids}")


def run_grpo(cfg, rl: GRPOConfig, devices, hp: TrainHParams):
    """One GRPO run through the runner's public entry points; prints the
    plan, set-up (profile + compile) seconds and every iteration."""
    cluster = Cluster(num_nodes=1, devices_per_node=len(devices))
    runner = GRPORunner(cfg, rl, hp, cluster=cluster)
    t0 = time.perf_counter()
    runner.profile()
    runner.plan_execution()
    setup = time.perf_counter() - t0
    print(f"grpo {rl.mode}: plan\n{runner.plan.pretty()}")
    runner.run_loop(verbose=False)
    gen = sum(n for n, _ in runner.rollout.request_records())
    walls = [st.wall_time for st in runner.stats]
    print(f"grpo {rl.mode}: set-up (profile, first compiles) "
          f"{setup!r} s; iteration walls {walls!r} s; steady "
          f"(mean of iterations 1..) {float(np.mean(walls[1:]))!r} s/iter")
    for st in runner.stats:
        loss = st.metrics.get("loss", float("nan"))
        gnorm = st.metrics.get("grad_norm", float("nan"))
        print(f"grpo {rl.mode}: iter {st.iteration} wall={st.wall_time!r} "
              f"loss={loss!r} grad_norm={gnorm!r} "
              f"reward={st.mean_reward!r}")
        check(np.isfinite(loss) and np.isfinite(gnorm),
              f"non-finite loss/grad-norm at iteration {st.iteration}")
    check(len(runner.stats) == rl.iterations, "missing iterations")
    engine = runner.rollout.engine
    print(f"grpo {rl.mode}: tokens generated {gen} over "
          f"{rl.iterations} iterations; engine weight_version "
          f"{engine.weight_version}; use_sampling_kernel "
          f"{engine.use_sampling_kernel}")
    check(gen > 0, "no tokens generated")
    check(engine.weight_version > 0, "engine never took a weight update")
    check_placement(runner, devices)
    return runner


def check_sampling_kernel(runner) -> None:
    check(runner.rollout.engine.use_sampling_kernel,
          "the paged engine is not sampling through the fused kernel")


def one_chip_phase(devices, cfg=None, rl=None) -> None:
    cfg = cfg or model_config()
    rl = rl or grpo_config("collocated")
    hp = TrainHParams()
    m = cfg.moe
    params = init_model(jax.random.PRNGKey(SEED), cfg)
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
          f"experts={m.num_experts} top_k={m.top_k} "
          f"expert_d_ff={m.expert_d_ff} vocab={cfg.vocab_size} "
          f"tied={cfg.tie_embeddings}; depth cut to {cfg.num_layers} of "
          f"{get_config(cfg.name).num_layers} layers; "
          f"{param_count(params)} params")
    sampler_phase(cfg)
    logits_phase(cfg, params)
    del params
    runner = run_grpo(cfg, rl, devices, hp)
    check_sampling_kernel(runner)
    runner.teardown()


def four_chip_phase(devices, cfg=None, rl_iterations: int = 3) -> None:
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
          f"JAX sees {len(devices)}")
    cfg = cfg or model_config()
    hp = TrainHParams()
    state = None
    for mode in ("disaggregated", "collocated"):
        runner = run_grpo(cfg, grpo_config(mode, rl_iterations), devices, hp)
        check_sampling_kernel(runner)
        engine = runner.rollout.engine
        roll = device_set((engine.params, engine.cache))
        act = device_set((runner.actor.get_state("params"),
                          runner.actor.get_state("opt")))
        print(f"placement {mode}: rollout engine on "
              f"{sorted(d.id for d in roll)}, actor on "
              f"{sorted(d.id for d in act)}")
        if mode == "disaggregated":
            check(bool(roll) and bool(act) and roll.isdisjoint(act),
                  "disaggregated rollout and actor share chips")
        else:
            mesh = runner.actor.device_mesh
            state = (jax.device_get(runner.actor.get_state("params")),
                     jax.device_get(runner.actor.get_state("opt")))
        runner.teardown()
        del runner, engine
        gc.collect()
    actor_step_phase(cfg, hp, mesh, devices[0], *state)


def actor_step_phase(cfg, hp, mesh, one, params, opt) -> None:
    """One fixed batch through the actor train step, data-parallel over
    the actor's chip slice and on one chip: loss and grad-norm agree."""
    B, S, prompt = 32, 48, 16
    rng = np.random.default_rng(SEED)
    mask = np.zeros((B, S), np.float32)
    mask[:, prompt:] = 1.0
    batch = {
        "tokens": rng.integers(3, cfg.vocab_size, (B, S)).astype(np.int32),
        "old_logprobs": np.full((B, S), -np.log(cfg.vocab_size),
                                np.float32),
        "advantages": rng.normal(size=(B, S)).astype(np.float32) * mask,
        "loss_mask": mask,
    }
    step = jax.jit(make_train_step(cfg, hp))
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(mesh.axis_names[0]))
    out = {}
    for name, p_sh, b_sh in (("slice", rep, data), ("one chip", one, one)):
        _, _, metrics = step(jax.device_put(params, p_sh),
                             jax.device_put(opt, p_sh),
                             jax.device_put(batch, b_sh))
        out[name] = (float(metrics["loss"]), float(metrics["grad_norm"]))
        gc.collect()
    (l4, g4), (l1, g1) = out["slice"], out["one chip"]
    print(f"actor step: {mesh.devices.size}-chip slice loss={l4!r} "
          f"grad_norm={g4!r}; one chip loss={l1!r} grad_norm={g1!r}; "
          f"rtol {STEP_RTOL}")
    check(np.isfinite([l4, g4, l1, g1]).all(), "non-finite actor step")
    check(abs(l4 - l1) <= STEP_RTOL * abs(l1), "slice loss != one chip")
    check(abs(g4 - g1) <= STEP_RTOL * abs(g1), "slice grad-norm != one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip placement phase")
    args = ap.parse_args(argv)
    devices = require_tpu()
    cache = enable_compile_cache()
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}; "
          f"compile cache {cache}")
    if args.four_chips:
        four_chip_phase(devices)
    else:
        one_chip_phase(devices)
    stats = devices[0].memory_stats() or {}
    print(f"memory: device 0 peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
