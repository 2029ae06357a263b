"""chip_smoke.py refuses to run without a TPU: no phase runs and no result
line is printed."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def test_chip_smoke_exits_nonzero_without_tpu():
    env = {"PATH": "/usr/bin:/bin", "HOME": os.environ.get("HOME", ROOT),
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=240, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), line
