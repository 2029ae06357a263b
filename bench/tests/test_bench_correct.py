"""The comparison that decides ``correct``, driven through the harness on
the CPU at a tiny size with each cell's own limits: an unbroken run is
correct, and the run is not correct with the bfloat16 control put in the
program's place, nor with the timed path broken underneath by each fault
a one-chip training cell can have (a step that leaves the state
unchanged, half of the batch left out, a token altered where the engine
produces it) or that the window's iteration can bring (the rollout
engine left at stale weights by the weight sync, the optimizer state
lost across the collocated switch).  The harness's look for a chip is
skipped: the CPU device stands in for it.
"""
import jax
import numpy as np
import pytest

from bench import check, control, run

SEED = 4242


def tiny(workload: str) -> dict:
    """The cell at a tiny size: widths, depth and vocabulary cut, traffic
    shortened; limits, mix rules and reference as the cell has them."""
    cell = run.load_cell(workload)
    c = dict(cell["config"])
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=32, num_local_experts=4,
             num_experts_per_tok=2, vocab_size=512, num_hidden_layers=2)
    c["program"] = dict(
        c["program"], num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        vocab_size=512, max_seq_len=64,
        moe=dict(c["program"].get("moe", {}), num_experts=4, top_k=2,
                 expert_d_ff=32))
    cell["config"] = c
    cell["mix"] = dict(cell["mix"], batch=8, group=4, prompt_len=8,
                       max_new_tokens=8)
    return cell


def run_tiny(cell):
    return run.run_cell(cell, SEED, 0.0, False, jax.devices()[:1],
                        log=lambda s: None)


CELLS = ["granite.grpo-short"]


@pytest.mark.parametrize("workload", CELLS)
def test_program_correct_and_control_not(workload):
    cell = tiny(workload)
    assert cell["limits"], f"no limits for {workload}"
    r = control.seed_readings(cell, SEED, faults=False)
    ok, _ = check.judge(r["program"], cell["limits"])
    assert ok, r["program"]
    ok, checks = check.judge(r["control"], cell["limits"])
    assert not ok, checks


def _unchanged_state(monkeypatch):
    from repro.rl.workers import ActorWorker
    real = ActorWorker.train

    def train(self, chunk):
        keep = (self.get_state("params"), self.get_state("opt"))
        out = real(self, chunk)
        self.set_state("params", keep[0])
        self.set_state("opt", keep[1])
        return out
    monkeypatch.setattr(ActorWorker, "train", train)


def _half_batch(monkeypatch):
    from repro.rl.workers import ActorWorker
    real = ActorWorker.train

    def train(self, chunk):
        half = {k: (v[: len(v) // 2] if isinstance(v, np.ndarray)
                    and v.ndim >= 1 else v) for k, v in chunk.items()}
        out = real(self, half)
        return dict(chunk, metrics=out["metrics"])
    monkeypatch.setattr(ActorWorker, "train", train)


def _token_altered(monkeypatch):
    from repro.rl.workers import RolloutWorker
    real = RolloutWorker.generate

    def generate(self, chunk):
        out = real(self, chunk)
        toks = out["tokens"].copy()
        P = chunk["prompt_tokens"].shape[1]
        toks[:, P + 1] = (toks[:, P + 1] + 1) % self.cfg.vocab_size
        return dict(out, tokens=toks)
    monkeypatch.setattr(RolloutWorker, "generate", generate)


def _stale_engine(monkeypatch):
    from repro.rl.workers import RolloutWorker
    real = RolloutWorker.update_weights

    def update_weights(self, params, version=None):
        if self.get_state("params") is None:
            real(self, params, version)
    monkeypatch.setattr(RolloutWorker, "update_weights", update_weights)


def _opt_lost_in_switch(monkeypatch):
    import jax
    from repro.core.worker import Worker
    from repro.rl.workers import ActorWorker
    real = Worker.onload

    def onload(self, keys=None):
        opt = self._host_state.get("opt")
        if isinstance(self, ActorWorker) and opt is not None:
            zero = lambda t: jax.tree_util.tree_map(np.zeros_like, t)  # noqa: E731
            self._host_state["opt"] = opt._replace(mu=zero(opt.mu),
                                                   nu=zero(opt.nu))
        return real(self, keys)
    monkeypatch.setattr(Worker, "onload", onload)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _token_altered, _stale_engine,
                                   _opt_lost_in_switch])
def test_fault_is_not_correct(fault, monkeypatch):
    cell = tiny("granite.grpo-short")
    fault(monkeypatch)
    result = run_tiny(cell)
    assert result["checks"]
    assert result["correct"] is False, result["checks"]
