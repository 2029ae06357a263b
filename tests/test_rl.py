"""RL layer: advantages (property-based), reward, env, engine, e2e runner."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import Cluster
from repro.models import init_model
from repro.rl import (
    EnvConfig,
    GRPOConfig,
    GRPORunner,
    VecReachEnv,
    gae_advantages,
    grpo_advantages,
    math_reward,
)
from repro.serve import Engine
from repro.train import TrainHParams, make_prefill_step
from repro.train.data import EOS, PromptDataset, encode_digits
from repro.train.optimizer import AdamWConfig


# ---------------------------------------------------------------------------
# advantages
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    n_groups=st.integers(1, 8),
    group=st.integers(2, 8),
    seed=st.integers(0, 100),
)
def test_grpo_advantages_group_properties(n_groups, group, seed):
    rng = np.random.default_rng(seed)
    r = rng.normal(size=n_groups * group).astype(np.float32)
    adv = grpo_advantages(r, group)
    g = adv.reshape(n_groups, group)
    # zero mean and ~unit std per group (unless the group was constant)
    np.testing.assert_allclose(g.mean(axis=1), 0.0, atol=1e-5)
    for i in range(n_groups):
        if r.reshape(n_groups, group)[i].std() > 1e-4:
            assert abs(g[i].std() - 1.0) < 1e-2


def test_gae_known_case():
    # single env, 2 steps, gamma=1, lam=1, zero values:
    # adv = reward-to-go
    rewards = np.array([[1.0], [2.0]], np.float32)
    values = np.zeros((3, 1), np.float32)
    dones = np.zeros((2, 1), np.float32)
    adv, ret = gae_advantages(rewards, values, dones, gamma=1.0, lam=1.0)
    np.testing.assert_allclose(adv[:, 0], [3.0, 2.0])
    np.testing.assert_allclose(ret, adv)  # values are zero


def test_gae_resets_at_done():
    rewards = np.array([[1.0], [5.0]], np.float32)
    values = np.zeros((3, 1), np.float32)
    dones = np.array([[1.0], [0.0]], np.float32)  # episode ends at t=0
    adv, _ = gae_advantages(rewards, values, dones, gamma=1.0, lam=1.0)
    assert adv[0, 0] == pytest.approx(1.0)  # no bleed from t=1


# ---------------------------------------------------------------------------
# reward
# ---------------------------------------------------------------------------
def test_math_reward_exact_match():
    plen = 4
    B, S = 2, 10
    toks = np.zeros((B, S), np.int32)
    answers = np.array([12, 7], np.int32)
    # correct: digits of 12 then EOS
    toks[0, plen:plen + 3] = encode_digits(12) + [EOS]
    # wrong: digits of 9
    toks[1, plen:plen + 2] = encode_digits(9) + [EOS]
    r = math_reward(toks, answers, plen)
    assert r[0] == 5.0 and r[1] == -5.0


# ---------------------------------------------------------------------------
# env
# ---------------------------------------------------------------------------
def test_env_progress_reward_sign():
    env = VecReachEnv(EnvConfig(num_envs=4, max_steps=100), seed=0)
    obs = env.observe()
    # greedy action toward the goal must give positive progress
    d = env.goal - env.pos
    from repro.rl.env import _DIRS
    best = np.argmax(d @ _DIRS[1:].T, axis=1) + 1
    _, r, _, _ = env.step(best)
    assert (r > 0).all()


def test_env_oracle_policy_succeeds():
    env = VecReachEnv(EnvConfig(num_envs=16, max_steps=64), seed=1)
    from repro.rl.env import _DIRS
    succ = 0
    for _ in range(64):
        d = env.goal - env.pos
        a = np.argmax(d @ _DIRS[1:].T, axis=1) + 1
        _, _, _, info = env.step(a)
        succ += int(info["success"].sum())
    assert succ >= 16  # oracle reaches goals quickly


# ---------------------------------------------------------------------------
# engine behaviour logprobs
# ---------------------------------------------------------------------------
def test_engine_logprobs_match_prefill_recompute():
    """Behaviour logprobs from generation must equal the inference worker's
    recompute — the correctness contract between rollout and training."""
    cfg = get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128)
    params = init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, max_new_tokens=6, temperature=1.0)
    ds = PromptDataset(4, prompt_len=6, seed=0)
    b = ds.next_batch()
    res = eng.generate(params, jnp.asarray(b["prompt_tokens"]),
                       key=jax.random.PRNGKey(5))
    pf = jax.jit(make_prefill_step(cfg))
    recomputed = pf(params, {"tokens": jnp.asarray(res.tokens)})
    S = b["prompt_tokens"].shape[1]
    gen_lp = np.asarray(res.logprobs)[:, S:]
    rec_lp = np.asarray(recomputed)[:, S:]
    mask = np.asarray(res.tokens)[:, S:] != 0
    np.testing.assert_allclose(gen_lp[mask], rec_lp[mask], atol=2e-3)


# ---------------------------------------------------------------------------
# end-to-end M2Flow runner in all three modes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["collocated", "disaggregated", "auto"])
def test_grpo_runner_modes(mode):
    cfg = get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128)
    rl = GRPOConfig(batch_size=8, group_size=4, iterations=2,
                    max_new_tokens=4, mode=mode, seed=0,
                    profile_batches=(4, 8))
    runner = GRPORunner(cfg, rl, TrainHParams(optimizer=AdamWConfig(lr=1e-3)))
    stats = runner.run(verbose=False)
    assert len(stats) == 2
    assert all(np.isfinite(s.mean_reward) for s in stats)
    assert runner.throughput() > 0


def test_grpo_runner_on_one_device_cluster():
    """A one-device cluster (one chip) hosts every worker: the fixed
    construction-time slices are capped at the cluster, and each worker's
    mesh is exactly the device its placement names."""
    cfg = get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128)
    rl = GRPOConfig(batch_size=8, group_size=4, iterations=2,
                    max_new_tokens=4, mode="collocated", seed=0,
                    profile_batches=(8,))
    runner = GRPORunner(cfg, rl, TrainHParams(optimizer=AdamWConfig(lr=1e-3)),
                        cluster=Cluster(num_nodes=1, devices_per_node=1))
    stats = runner.run(verbose=False)
    assert len(stats) == 2
    assert runner.rollout.engine.weight_version > 0
    for name, ids in runner.plan.placement.items():
        assert ids == [0]
        assert list(runner.workers[name].device_mesh.devices.flat) == [
            jax.devices()[0]]


def test_grpo_runner_learns_on_tiny_task():
    """80 iterations must lift train accuracy well above random on
    single-digit addition — the end-to-end learning check (recipe
    validated in EXPERIMENTS.md §E8: 0.08 -> ~0.4)."""
    cfg = get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=256)
    rl = GRPOConfig(batch_size=32, group_size=8, iterations=80,
                    max_new_tokens=3, mode="collocated", seed=0,
                    profile_batches=(8,))
    runner = GRPORunner(
        cfg, rl, TrainHParams(optimizer=AdamWConfig(lr=1e-3, clip_norm=1.0),
                              entropy_coef=0.02))
    runner.data.max_operand = 3  # single-digit-answer curriculum
    runner.data.add_only = True
    stats = runner.run(verbose=False)
    first = np.mean([s.accuracy for s in stats[:10]])
    last = np.mean([s.accuracy for s in stats[-10:]])
    assert last > first + 0.1, (first, last)


def test_async_offpolicy_mode_learns_and_ratios_drift():
    """AReaL-style 1-step-stale rollouts: the PPO ratios must move off 1
    (staleness is real) yet training still improves accuracy."""
    cfg = get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=256)
    rl = GRPOConfig(batch_size=32, group_size=8, iterations=50,
                    max_new_tokens=3, mode="collocated", seed=0,
                    profile_batches=(8,), async_offpolicy=True)
    runner = GRPORunner(
        cfg, rl, TrainHParams(optimizer=AdamWConfig(lr=1e-3, clip_norm=1.0),
                              entropy_coef=0.02))
    runner.data.max_operand = 3
    runner.data.add_only = True
    stats = runner.run(verbose=False)
    kls = [s.metrics.get("approx_kl", 0.0) for s in stats[2:] if s.metrics]
    assert max(kls) > 1e-5  # off-policy: ratios genuinely drift
    first = np.mean([s.accuracy for s in stats[:10]])
    last = np.mean([s.accuracy for s in stats[-10:]])
    assert last > first, (first, last)


def test_rlhf_ppo_four_model_workflow():
    """Full paper-Fig.-1 RLHF: actor+critic+reference+reward through the
    runtime; critic learns (value loss drops) and the KL anchor is live."""
    from repro.rl import PPOConfig, RLHFRunner

    cfg = get_config("stablelm-12b").reduced().replace(
        vocab_size=32, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=256)
    runner = RLHFRunner(cfg, PPOConfig(batch_size=16, iterations=12,
                                       max_new_tokens=3))
    stats = runner.run(verbose=False)
    assert len(stats) == 12
    assert all(np.isfinite(s.value_loss) for s in stats)
    # critic fits the +-5 reward scale: early loss ~ 25, must drop
    assert np.mean([s.value_loss for s in stats[-4:]]) < stats[0].value_loss
    # the reference-KL penalty is actually wired into the actor loss
    assert "kl_ref" in stats[-1].metrics
    # the 6-node workflow graph is schedulable
    from repro.core import Scheduler, SchedulerConfig
    from repro.core.profiler import paper_like_profiles
    prof = paper_like_profiles()
    prof["reference"] = prof["critic_v"] = prof["inference"]
    prof["actor"] = prof["training"]
    t, s = Scheduler(prof, SchedulerConfig(
        total_batch=64, device_quantum=8)).schedule(runner.graph(), 32, 64)
    assert np.isfinite(t) and s is not None


def test_grpo_plan_chunks_never_split_groups():
    """Regression: a slow rollout profile (e.g. the paged engine on tiny
    models) used to push the auto planner to pipeline chunks smaller than
    group_size; the reward worker then fell back to groups of 1, whose
    group-relative advantages are identically zero — training silently
    stopped learning.  Every planned chunk must be a group multiple."""
    from repro.core.scheduler import leaves

    cfg = get_config("yi-9b").reduced().replace(
        vocab_size=32, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128)
    rl = GRPOConfig(batch_size=32, group_size=8, iterations=1,
                    max_new_tokens=3, mode="auto", seed=0,
                    profile_batches=(8,))
    runner = GRPORunner(cfg, rl, TrainHParams(optimizer=AdamWConfig(lr=1e-3)))
    runner.profile()
    runner.plan_execution()
    assert runner.controller.scheduler_cfg.chunk_multiple == rl.group_size
    for lf in leaves(runner.plan.schedule):
        assert lf.batch % rl.group_size == 0, (lf.worker, lf.batch)
