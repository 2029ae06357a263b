"""Production training launcher: mesh + sharded state + train loop.

The mesh spans every device JAX sees, (data=N, model=1): weights and
optimizer state shard FSDP-style over the data axis.  In a multi-host
job (``REPRO_COORD_ADDR``, ``REPRO_NUM_PROCESSES``, ``REPRO_PROCESS_ID``
set; see ``launch.cluster.maybe_init_jax_distributed``) this process
joins it through ``jax.distributed`` before anything touches the
backend.  ``--smoke`` runs a reduced-width config, so the launcher itself
is exercised on CPU.

Usage:
  python -m repro.launch.train --arch yi-9b --smoke --steps 10
  REPRO_COORD_ADDR=$HOST:1234 REPRO_NUM_PROCESSES=64 REPRO_PROCESS_ID=$I \
      python -m repro.launch.train --arch mistral-large-123b --seq 4096 \
      --batch 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.cluster import maybe_init_jax_distributed
from repro.launch.mesh import make_local_mesh
from repro.models import init_model
from repro.train import TrainHParams, init_adamw, lm_loss, make_train_step
from repro.train.checkpoint import save_checkpoint
from repro.train.optimizer import AdamWConfig
from repro.train.sharding_rules import array_batch_specs, param_specs
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import log
from repro.utils.sharding import set_active_mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-width config (CPU)")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args(argv)

    maybe_init_jax_distributed()  # must precede every backend query
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    mesh = make_local_mesh(data=jax.device_count())
    set_active_mesh(mesh)
    log("launch", f"arch={cfg.name} mesh={dict(mesh.shape)} "
        f"params≈{cfg.param_count() / 1e9:.2f}B")

    hp = TrainHParams(
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=10, clip_norm=1.0),
        n_microbatches=args.n_micro,
        remat=not args.smoke,
    )

    with mesh:
        params = init_model(jax.random.PRNGKey(0), cfg)
        pspecs = param_specs(mesh, cfg, params)
        params = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, pspecs, is_leaf=lambda x: isinstance(x, P))
        opt = init_adamw(params)
        step = jax.jit(make_train_step(cfg, hp, loss_fn=lm_loss),
                       donate_argnums=(0, 1))

        rng = np.random.default_rng(0)
        t0 = time.time()
        for i in range(args.steps):
            batch_np = {"tokens": rng.integers(
                0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32)}
            specs = array_batch_specs(mesh, batch_np)
            batch = jax.tree_util.tree_map(
                lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                batch_np, specs, is_leaf=lambda x: isinstance(x, P))
            params, opt, metrics = step(params, opt, batch)
            if i % 10 == 0 or i == args.steps - 1:
                log("train", f"step {i}",
                    loss=f"{float(metrics['loss']):.4f}",
                    gnorm=f"{float(metrics['grad_norm']):.3f}")
        tokens = args.steps * args.batch * args.seq
        log("done", f"{tokens / (time.time() - t0):.0f} tok/s")

    if args.checkpoint:
        save_checkpoint(args.checkpoint, {"params": params, "opt": opt},
                        step=args.steps, metadata={"arch": cfg.name})
        log("ckpt", f"saved to {args.checkpoint}")
    set_active_mesh(None)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
