"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
only launch/dryrun.py forces placeholder devices (in a subprocess).  The
dry-run topology is configurable there via REPRO_DRYRUN_HOSTS /
REPRO_DRYRUN_DEVICES (hosts x devices-per-host, default 1x512), and
launch.cluster.cluster_from_env reads the same knobs so a test or script
can stand up a simulated multi-host cluster without touching XLA flags:

    REPRO_DRYRUN_HOSTS=4 REPRO_DRYRUN_DEVICES=8 python -m repro.launch.dryrun
"""
import jax
import pytest

# keep CPU tests deterministic and fast
jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def tiny(cfg):
    """Shrink a reduced config further for fast unit tests."""
    kw = dict(vocab_size=64, d_model=64, d_ff=128 if cfg.d_ff else 0,
              max_seq_len=128)
    if cfg.num_heads:
        kw.update(num_heads=4, num_kv_heads=2, head_dim=16)
    return cfg.replace(**kw)
