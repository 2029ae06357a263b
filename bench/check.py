"""The comparison that decides ``correct``.

Set-up drives the runner from the seed through its first four train
steps.  ``profile()`` takes three of them: three rollouts on the first
batch at the initial weights W0, the inference pass on the last of those
rollouts, and the train step three times on that scored batch.
Iteration 0 takes the fourth, the way the window runs every iteration:
the weight sync puts W3 into the rollout engine and the inference
worker, ``Controller.execute`` runs the stages under the plan's
placement (collocated: the actor's params and Adam state go to the host
and back around the rollout), and the actor runs the train step that was
compiled anew after ``bind_placement``.  :class:`Capture` records what
those steps produced, as they ran, through the same stage calls the
window drives.  After the window, with the program's state freed, the
plain float32 reference of the configuration (``configs/<name>.py``)
builds W0 itself from the seed and follows the same four steps at
``precision="highest"``; :func:`readings` reduces the two to the numbers
that the limits in ``limits/<workload>.json`` bound.

The control is the same reference one precision below the float32 the
configuration states: weights, activations, gradients and Adam state in
bfloat16.  ``control.py`` reads it, and the planted faults.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
STEPS = 4  # train steps the capture follows: three in profile(), one in iteration 0


def flat(tree) -> Dict[str, object]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): x for p, x in leaves}


@jax.jit
def _sq_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), tree)


def leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    return {k: math.sqrt(float(v)) * scale
            for k, v in flat(_sq_norms(tree)).items()}


def host_leaves(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in flat(jax.device_get(tree)).items()}


def _sq_sum(x: np.ndarray) -> float:
    x = x.reshape(-1)
    return sum(float(np.dot(c, c)) for c in
               (x[i:i + (1 << 20)] for i in range(0, x.size, 1 << 20)))


def host_change_norms(before: Dict[str, np.ndarray], after) -> Dict[str, float]:
    """Per-leaf norms of ``after - before``: differences in float32,
    squares summed in float64 over chunks."""
    return {k: math.sqrt(_sq_sum(np.asarray(a, np.float32)
                                 - np.asarray(before[k], np.float32)))
            for k, a in host_leaves(after).items()}


class Capture:
    """Stage hooks that record set-up's first steps (see module doc).

    Each rollout and inference pass is recorded with ``k``, the number of
    train steps the actor had taken when it ran (its weights are W_k);
    each train step with its batch and the ``k`` of the inference pass
    that scored that batch.  Of the training state it keeps the first
    gradient as the optimizer got it (``mu / (1 - b1)`` after step 1),
    the change W3 - W0, the change W4 - W0 and Adam's first moment after
    step 4."""

    def __init__(self, b1: float):
        self.b1 = b1
        self.rollouts: List[dict] = []
        self.inferences: List[dict] = []
        self.steps: List[dict] = []
        self.losses: List[float] = []
        self.grad_norm: Optional[float] = None
        self.grad1: Optional[Dict[str, float]] = None
        self.grad1_vec: Optional[Dict[str, np.ndarray]] = None
        self.change3: Optional[Dict[str, float]] = None
        self.change4: Optional[Dict[str, float]] = None
        self.mu4: Optional[Dict[str, float]] = None
        self._w0: Optional[Dict[str, np.ndarray]] = None

    @property
    def complete(self) -> bool:
        return len(self.steps) >= STEPS

    def before(self, stage: str, worker, chunk) -> None:
        if stage == "actor" and not self.steps:
            self._w0 = host_leaves(worker.get_state("params"))

    def after(self, stage: str, worker, chunk, out) -> None:
        k = len(self.steps)
        if k >= STEPS:
            return
        if stage == "rollout":
            self.rollouts.append({"k": k, **{n: np.asarray(out[n]) for n in
                                             ("tokens", "logprobs", "lengths")}})
        elif stage == "inference":
            self.inferences.append({"k": k, "tokens": np.asarray(out["tokens"]),
                                    "logprobs": np.asarray(out["old_logprobs"])})
        elif stage == "actor":
            tokens = np.asarray(chunk["tokens"])
            scored = [r["k"] for r in self.inferences
                      if np.array_equal(r["tokens"], tokens)]
            if not scored:
                raise RuntimeError("a train step's batch was never scored "
                                   "by the inference pass")
            self.steps.append({"tokens": tokens, "k": scored[-1]})
            self.losses.append(float(out["metrics"]["loss"]))
            if k == 0:
                self.grad_norm = float(out["metrics"]["grad_norm"])
                mu = worker.get_state("opt").mu
                self.grad1 = leaf_norms(mu, 1.0 / (1.0 - self.b1))
                self.grad1_vec = host_leaves(mu)
            if k == 2:
                self.change3 = host_change_norms(
                    self._w0, worker.get_state("params"))
            if k == 3:
                self.change4 = host_change_norms(
                    self._w0, worker.get_state("params"))
                self.mu4 = leaf_norms(worker.get_state("opt").mu)
                self._w0 = None

    def program_side(self) -> dict:
        return {"rollout_lp": [r["logprobs"] for r in self.rollouts],
                "inference_lp": [r["logprobs"] for r in self.inferences],
                "losses": self.losses, "grad_norm": self.grad_norm,
                "grad1": self.grad1, "grad1_vec": self.grad1_vec,
                "change3": self.change3, "change4": self.change4,
                "mu4": self.mu4}


# ---------------------------------------------------------------------------
# the reference following the same steps
# ---------------------------------------------------------------------------
def _aligned_logprobs(logits, tokens, vocab):
    """Entry t scores tokens[t] under the logits of position t-1."""
    lp = jax.nn.log_softmax(logits[:, :-1, :vocab], axis=-1)
    picked = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.pad(picked, ((0, 0), (1, 0)))


class Reference:
    """The reference's side of the comparison, with its compiled
    programs, for one configuration, mix and precision: weights,
    activations, gradients and Adam state in ``dtype``.  One instance
    serves any number of seeds."""

    def __init__(self, ref, c: dict, mix: dict, dtype=jnp.float32):
        self.c, self.mix, self.dtype = c, mix, dtype
        vocab = c["vocab_size"]
        tr = mix["train"]

        def logprobs(params, tokens, limit):
            logits, aux = ref.forward(params, c, tokens, capacity_limit=limit)
            return _aligned_logprobs(logits, tokens, vocab), aux

        def loss_fn(params, tokens, old, adv, mask):
            lp, aux = logprobs(params, tokens, True)
            ratio = jnp.exp(lp[:, 1:] - old[:, 1:])
            a, m = adv[:, 1:], mask[:, 1:]
            pg = -jnp.minimum(ratio * a, jnp.clip(
                ratio, 1 - tr["clip_eps"], 1 + tr["clip_eps"]) * a)
            return jnp.sum(pg * m) / jnp.maximum(jnp.sum(m), 1.0) + aux

        def step(params, mu, nu, t, tokens, old, adv, mask):
            loss, g = jax.value_and_grad(loss_fn)(params, tokens, old, adv, mask)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree_util.tree_leaves(g)))
            g = jax.tree_util.tree_map(
                lambda x: x * jnp.minimum(1.0, tr["clip_norm"] / jnp.maximum(gn, 1e-12)), g)
            mu = jax.tree_util.tree_map(lambda m, x: tr["b1"] * m + (1 - tr["b1"]) * x, mu, g)
            nu = jax.tree_util.tree_map(lambda v, x: tr["b2"] * v + (1 - tr["b2"]) * x * x, nu, g)
            c1, c2 = 1 - tr["b1"] ** t, 1 - tr["b2"] ** t

            def upd(p, m, v):
                delta = (m / c1) / (jnp.sqrt(v / c2) + tr["eps"])
                if tr["weight_decay"] > 0 and p.ndim >= 2:
                    delta = delta + tr["weight_decay"] * p
                return (p - tr["lr"] * delta).astype(p.dtype)
            return jax.tree_util.tree_map(upd, params, mu, nu), mu, nu, loss, g, gn

        self._init = jax.jit(lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(dtype), ref.init_params(key, c)))
        self._lp_exact = jax.jit(lambda p, t: logprobs(p, t, False)[0])
        self._lp_limit = jax.jit(lambda p, t: logprobs(p, t, True)[0])
        self._step = jax.jit(step)

    def follow(self, seed: int, cap: Capture, actor_rows: Optional[int] = None,
               reset_opt_at: Optional[int] = None) -> dict:
        """W0 from the seed, then the captured train steps on the actor's
        batches (each scored by the reference at the weights its
        inference pass ran at), and the reference's logprobs of every
        captured rollout (exact routing, as generation routes) and
        inference batch (with the configuration's capacity limit, as the
        logprob recompute and the train step route) at the weights each
        ran at, and at W0.  Planted faults: ``actor_rows`` trains on the
        first rows of each batch alone; ``reset_opt_at`` zeroes Adam's
        moments before that step (state lost across a switch)."""
        mix, vocab = self.mix, self.c["vocab_size"]
        need = ({r["k"] for r in cap.rollouts + cap.inferences}
                | {st["k"] for st in cap.steps})
        with jax.default_matmul_precision("highest"):
            w0 = self._init(jax.random.PRNGKey(seed))
            zeros = jax.tree_util.tree_map(jnp.zeros_like, w0)
            weights, params, mu, nu = {0: w0}, w0, zeros, zeros
            side, losses, adv_abs = {}, [], []
            for t, st in enumerate(cap.steps, start=1):
                _, mask, adv = traffic.score(st["tokens"], mix, vocab)
                tokens = jnp.asarray(st["tokens"])
                old = self._lp_limit(weights[st["k"]], tokens)
                if actor_rows is not None:
                    tokens, old = tokens[:actor_rows], old[:actor_rows]
                    mask, adv = mask[:actor_rows], adv[:actor_rows]
                if reset_opt_at == t:
                    mu, nu = zeros, zeros
                params, mu, nu, loss, g, gn = self._step(
                    params, mu, nu, float(t), tokens, old,
                    jnp.asarray(adv, self.dtype), jnp.asarray(mask, self.dtype))
                if t in need:
                    weights[t] = params
                losses.append(float(loss))
                adv_abs.append(float(np.sum(np.abs(adv) * mask)
                                     / max(np.sum(mask), 1.0)))
                if t == 1:
                    side["grad1"] = leaf_norms(g)
                    side["grad1_vec"] = host_leaves(g)
                    side["grad_norm"] = float(gn)
                if t in (3, 4):
                    side[f"change{t}"] = leaf_norms(jax.tree_util.tree_map(
                        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                        params, w0))
            side["mu4"] = leaf_norms(mu)
            side["rollout_lp"] = [
                np.asarray(self._lp_exact(weights[r["k"]], jnp.asarray(r["tokens"])))
                for r in cap.rollouts]
            # the rollouts after a sync, scored at W0 as well: how far the
            # weights moved them
            side["rollout_lp_w0"] = [
                np.asarray(self._lp_exact(w0, jnp.asarray(r["tokens"]))) if r["k"] else None
                for r in cap.rollouts]
            side["inference_lp"] = [
                np.asarray(self._lp_limit(weights[r["k"]], jnp.asarray(r["tokens"])))
                for r in cap.inferences]
            side["losses"] = losses
            side["mean_abs_adv"] = float(np.mean(adv_abs))
        return side


# ---------------------------------------------------------------------------
# readings and limits
# ---------------------------------------------------------------------------
def _lp_gaps(got, want, records, mask_of, keep) -> np.ndarray:
    out = [np.abs(np.asarray(g, np.float64) - np.asarray(w, np.float64))[mask_of(r)]
           for g, w, r in zip(got, want, records) if keep(r["k"])]
    return np.concatenate(out) if out else np.zeros((0,))


def _leaf_gaps(got: Dict[str, float], want: Dict[str, float],
               keep=None) -> Dict[str, float]:
    """Per leaf, |got - want| over the larger of want and the median
    leaf's want."""
    med = float(np.median(list(want.values())))
    return {k: abs(got[k] - w) / max(w, med) for k, w in want.items()
            if keep is None or k in keep}


def _moved(ref: dict):
    """Leaves the reference's first gradient moves: at least a thousandth
    of the median leaf's gradient norm (a key's bias under softmax has a
    gradient that is nought to rounding and moves under Adam by
    round-off alone)."""
    g_med = float(np.median(list(ref["grad1"].values())))
    return {k for k, v in ref["grad1"].items() if v >= 1e-3 * g_med}


def direction_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> float:
    """1 - cosine between two whole gradients (all leaves as one vector),
    accumulated in float64."""
    dot = gg = ww = 0.0
    for k, w in want.items():
        a = np.asarray(got[k]).reshape(-1)
        b = np.asarray(w).reshape(-1)
        for i in range(0, a.size, 1 << 22):
            x = a[i:i + (1 << 22)].astype(np.float64)
            y = b[i:i + (1 << 22)].astype(np.float64)
            dot, gg, ww = dot + x @ y, gg + x @ x, ww + y @ y
    if gg == 0.0 or ww == 0.0:
        return 1.0
    return float(1.0 - dot / math.sqrt(gg * ww))


SOURCES = {
    "grad_leaf_gap": ("grad1", False), "change_leaf_gap": ("change3", True),
    "change4_leaf_gap": ("change4", True)}


def worst_leaves(side: dict, ref: dict) -> Dict[str, str]:
    """Which leaf sets each worst-leaf number (for the log)."""
    out = {}
    for name, (key, moved) in SOURCES.items():
        gaps = _leaf_gaps(side[key], ref[key], _moved(ref) if moved else None)
        out[name] = max(gaps, key=gaps.get)
    return out


def _move_gap(side_lp, ref_lp, ref_w0, records, mask_of) -> float:
    """How far the passes after a sync moved from W0 (mean |logprob at
    their weights - logprob at W0|), the side's against the reference's:
    |side's - reference's| / reference's."""
    keep = [i for i, r in enumerate(records) if r["k"]]
    if not keep:
        return float("nan")
    w0 = [ref_w0[i] for i in keep]
    recs = [records[i] for i in keep]
    moved = _lp_gaps([side_lp[i] for i in keep], w0, recs, mask_of, bool).mean()
    ref_moved = _lp_gaps([ref_lp[i] for i in keep], w0, recs, mask_of, bool).mean()
    return float(abs(moved - ref_moved) / ref_moved)


def readings(side: dict, ref: dict, cap: Capture, mix: dict) -> Dict[str, float]:
    """Numbers comparing ``side`` (the program's, or the control's)
    with the float32 reference ``ref`` on the same captured steps.
    ``synced_*`` read the passes after the first weight sync that follows
    a train step (iteration 0's, at W3); the other logprob numbers those
    at W0."""
    P = mix["prompt_len"]

    def generated(r):
        pos = np.arange(r["tokens"].shape[1])[None, :]
        return (pos >= P) & (pos < r["lengths"][:, None])

    def response(r):
        return traffic.response_mask(r["tokens"], P) > 0

    def at_w0(k):
        return k == 0

    def synced(k):
        return k > 0

    roll = _lp_gaps(side["rollout_lp"], ref["rollout_lp"], cap.rollouts,
                    generated, at_w0)
    roll_s = _lp_gaps(side["rollout_lp"], ref["rollout_lp"], cap.rollouts,
                      generated, synced)
    inf = _lp_gaps(side["inference_lp"], ref["inference_lp"], cap.inferences,
                   response, at_w0)
    loss_gap = max(abs(a - b) for a, b in zip(side["losses"], ref["losses"]))
    moved = _moved(ref)
    out = {
        "rollout_lp_p90": float(np.quantile(roll, 0.9)),
        "rollout_lp_mean": float(roll.mean()),
        "inference_lp_mean": float(inf.mean()),
        "synced_lp_mean": float(roll_s.mean()),
        "synced_move_gap": _move_gap(side["rollout_lp"], ref["rollout_lp"],
                                     ref["rollout_lp_w0"], cap.rollouts, generated),
        "loss_gap": loss_gap / ref["mean_abs_adv"],
        "grad_norm_gap": abs(side["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"],
        "grad_dir_gap": direction_gap(side["grad1_vec"], ref["grad1_vec"]),
        "opt4_median_gap": float(np.median(list(_leaf_gaps(
            side["mu4"], ref["mu4"], moved).values()))),
    }
    for name, (key, only_moved) in SOURCES.items():
        out[name] = max(_leaf_gaps(side[key], ref[key],
                                   moved if only_moved else None).values())
    return out


def load_limits(workload: str) -> Dict[str, float]:
    path = LIMITS_DIR / f"{workload}.json"
    if not path.exists():
        return {}
    return json.loads(path.read_text())["limits"]


def judge(read: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {value, limit}}) over the numbers with a limit.
    No limit at all is not correct."""
    checks = {k: {"value": read[k], "limit": v} for k, v in limits.items()}
    ok = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks
