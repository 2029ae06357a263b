#!/usr/bin/env python3
"""Compile a cell's actor train step, logprob-recompute step and engine
decode step for a described TPU v5e, at the cell's exact shapes, and print
each program's ``memory_analysis()``.  Nothing runs and no chip is needed:

    JAX_PLATFORMS=cpu python3 bench/aot_memory.py --workload granite.grpo-short

The compiler refuses here what would not fit the chip.  It counts one
program at a time, not the other weight copies the runner keeps on the
device.  The engine step is compiled without the fused sampling kernel
(its interpret-mode lowering is all a CPU backend offers), which holds
only one row of logits per slot.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import run  # noqa: E402


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _report(name, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / 1e9:.3f} GB; sum {total / 1e9:.3f} GB",
          flush=True)


def main(argv=None) -> int:
    from jax.experimental import topologies

    from repro.models import init_model
    from repro.serve.engine import PagedEngine
    from repro.train.optimizer import AdamWConfig, init_adamw
    from repro.train.trainer import (TrainHParams, make_prefill_step,
                                     make_train_step)

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    cfg = run.program_config(cell["config"])
    mix, tr = cell["mix"], cell["mix"]["train"]
    B, S = mix["batch"], mix["prompt_len"] + mix["max_new_tokens"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(init_adamw, params)
    hp = TrainHParams(optimizer=AdamWConfig(
        lr=tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"],
        weight_decay=tr["weight_decay"], clip_norm=tr["clip_norm"]),
        clip_eps_low=tr["clip_eps"], clip_eps_high=tr["clip_eps"])
    f32 = jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=one)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one),
             "old_logprobs": f32, "advantages": f32, "loss_mask": f32}
    p_sh, o_sh = _shapes(params, one), _shapes(opt, one)
    print(f"{args.workload}: {cfg.name}, {cfg.num_layers} layers, batch "
          f"{B} x {S} positions, described v5e", flush=True)
    _report("actor train step", jax.jit(make_train_step(cfg, hp)).lower(
        p_sh, o_sh, batch).compile())
    _report("logprob recompute", jax.jit(make_prefill_step(cfg)).lower(
        p_sh, {"tokens": batch["tokens"]}).compile())
    eng = PagedEngine(cfg, max_new_tokens=mix["max_new_tokens"],
                      temperature=mix["temperature"],
                      use_sampling_kernel=False)
    lay = eng.layout
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    nb = eng.max_batch
    if hasattr(lay, "_prefill_fn"):
        step_args = (p_sh, *_shapes((lay.cache.k, lay.cache.v), one),
                     i32(nb), i32(nb), i32(nb, eng.max_blocks), i32(nb))
    else:
        step_args = (p_sh, _shapes(lay.cache, one), i32(nb), i32(nb), i32(nb),
                     jax.ShapeDtypeStruct((nb,), jnp.bool_, sharding=one))
    _report(f"engine decode step ({lay.name})",
            lay._step_fn.lower(*step_args).compile())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
