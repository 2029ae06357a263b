"""Launcher + resharding coverage (subprocess keeps device state clean)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import timed_weight_sync, transfer_stats


def _subprocess_env() -> dict:
    """Minimal env for launcher subprocesses — but carry over the
    backend pin: without JAX_PLATFORMS, jax's backend probing can block
    for minutes on sandboxed containers and the subprocess times out."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", "/tmp")}
    for var in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME"):
        if var in os.environ:
            env[var] = os.environ[var]
    return env


def test_transfer_stats():
    tree = {"a": jnp.ones((4, 4), jnp.float32), "b": jnp.ones(2, jnp.bfloat16)}
    st = transfer_stats(tree)
    assert st["bytes"] == 64 + 4 and st["arrays"] == 2


def test_weight_sync_roundtrip_single_device():
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    dst = jax.tree_util.tree_map(lambda x: x.sharding, tree)
    out, secs = timed_weight_sync(tree, dst)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert secs >= 0.0


def test_train_launcher_smoke():
    """python -m repro.launch.train --smoke must run a few steps end to
    end (mesh build, sharded init, jitted train loop, logging)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "yi-9b",
         "--smoke", "--steps", "3", "--batch", "2", "--seq", "32"],
        capture_output=True, text=True, timeout=420,
        env=_subprocess_env(), cwd="/root/repo")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "step 0" in out.stdout and "tok/s" in out.stdout


def test_train_launcher_mesh_spans_all_devices():
    """The launcher's mesh covers every device present (here four
    virtual CPU devices), not a hard-coded pod shape."""
    env = dict(_subprocess_env(),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "yi-9b",
         "--smoke", "--steps", "2", "--batch", "4", "--seq", "32"],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "mesh={'data': 4, 'model': 1}" in out.stdout, out.stdout
    assert "step 1" in out.stdout


def test_resharding_between_specs_subprocess():
    """Reshard a pytree between two different layouts on an 8-device mesh
    and verify values survive (the weight-update barrier path)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.comm import reshard
        from repro.launch.mesh import _make_mesh
        mesh = _make_mesh((2, 4), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        a = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
        dst = {"w": NamedSharding(mesh, P("model", None))}
        out = reshard({"w": a}, dst)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
        assert out["w"].sharding.spec == P("model", None)
        print("RESHARD_OK")
    """)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=240,
                         env=_subprocess_env(), cwd="/root/repo")
    assert "RESHARD_OK" in out.stdout, out.stdout + out.stderr
