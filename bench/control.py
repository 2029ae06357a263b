#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
planted faults', each against the float32 reference, over many seeds in
one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--out FILE]

For each seed the program runs set-up's first four train steps (three in
``profile()``, the fourth in iteration 0 after the weight sync, at the
cell's sizes) and is freed; then the reference follows them in float32
and, put in the program's place, as

* ``control``: the reference with weights, activations, gradients and
  Adam state in bfloat16, the precision below the float32 the
  configuration states;
* ``half_batch``: the reference training on half of the batch, the mean
  taken over the rest;
* ``token_altered``: the program's rollouts with one generated token per
  row replaced where it was produced (its logprob left as it was);
* ``stale_engine``: the reference scoring every rollout at W0, as an
  engine would that the weight sync left behind;
* ``opt_reset``: the reference with Adam's moments zeroed before the
  fourth step, as a switch that lost the optimizer state would leave it.

A state left unchanged reads 1 on ``change_leaf_gap`` by construction
and needs no run.  One JSON line per seed; ``limits/<workload>.json``
records the limits set from them.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, run  # noqa: E402


def capture_first_steps(cell: dict, pseed: int) -> check.Capture:
    runner, stages, cap, _ = run.set_up(cell, pseed, quiet=False)
    runner.teardown()
    del runner, stages
    gc.collect()
    return cap


def alter_tokens(cap: check.Capture, vocab: int, prompt_len: int) -> check.Capture:
    bad = copy.copy(cap)
    bad.rollouts = []
    for r in cap.rollouts:
        toks = r["tokens"].copy()
        pos = prompt_len + np.arange(toks.shape[0]) % (toks.shape[1] - prompt_len)
        rows = np.arange(toks.shape[0])
        toks[rows, pos] = (toks[rows, pos] + 1) % vocab
        bad.rollouts.append(dict(r, tokens=toks))
    return bad


class Sides:
    """The reference's variants for one cell, compiled once."""

    def __init__(self, cell: dict):
        ref, c, mix = cell["reference"], cell["config"], cell["mix"]
        self.f32 = check.Reference(ref, c, mix, jnp.float32)
        self.control = check.Reference(ref, c, mix, jnp.bfloat16)


def seed_readings(cell: dict, seed: int, sides: Sides = None,
                  faults: bool = True) -> dict:
    c, mix = cell["config"], cell["mix"]
    sides = sides or Sides(cell)
    pseed = run.program_seed(seed)
    cap = capture_first_steps(cell, pseed)
    f32 = sides.f32.follow(pseed, cap)
    out = {"seed": seed,
           "program": check.readings(cap.program_side(), f32, cap, mix),
           "worst_leaves": check.worst_leaves(cap.program_side(), f32)}
    out["control"] = check.readings(sides.control.follow(pseed, cap), f32,
                                    cap, mix)
    if not faults:
        return out
    half = sides.f32.follow(pseed, cap, actor_rows=mix["batch"] // 2)
    out["half_batch"] = check.readings(half, f32, cap, mix)
    bad = alter_tokens(cap, c["vocab_size"], mix["prompt_len"])
    out["token_altered"] = check.readings(cap.program_side(),
                                          sides.f32.follow(pseed, bad), bad, mix)
    out["stale_engine"] = check.readings(
        dict(f32, rollout_lp=[w0 if w0 is not None else lp for lp, w0 in
                              zip(f32["rollout_lp"], f32["rollout_lp_w0"])]),
        f32, cap, mix)
    out["opt_reset"] = check.readings(
        sides.f32.follow(pseed, cap, reset_opt_at=check.STEPS), f32, cap, mix)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.require_devices(cell["chips"])
    run.enable_cache()
    sides = Sides(cell)
    sink = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        line = json.dumps(seed_readings(cell, seed, sides))
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
