"""The trace reduction on a trace recorded on the chip: one GRPO iteration
of ``mamba2.grpo-short`` on a TPU v5e (``bench/run.py --trace 1
--seconds 0 --keep-trace``), gzipped.  Busy time is checked against an
independent count on a 1 us grid, and the device ops and idle gaps
against their own totals."""
import gzip
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce

FIXTURE = Path(__file__).parent / "fixtures" / "mamba_short.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return trace_reduce.load(str(path))


def test_recorded_trace_has_a_device_and_the_stages(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    names = {n for _, _, n in trace["host"]}
    assert {"bench:window", "bench:rollout", "bench:inference",
            "bench:actor"} <= names


def test_busy_time_against_a_grid_count(trace):
    r = trace_reduce.reduce(trace)
    lo = min(s for s, _, n in trace["host"] if n == trace_reduce.WINDOW)
    hi = max(e for _, e, n in trace["host"] if n == trace_reduce.WINDOW)
    grid = np.zeros((hi - lo) // 1000 + 1, bool)  # 1 us bins
    for s, e, _ in trace["devices"]["/device:TPU:0"]["ops"]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // 1000:(e - lo) // 1000] = True
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    # each op edge can fall anywhere in its microsecond bin
    n_ops = len(trace["devices"]["/device:TPU:0"]["ops"])
    assert abs(r["busy_s"] - grid.sum() * 1e-6) <= 2e-6 * n_ops
    assert 0 < r["busy_s"] < r["window_s"]


def test_idle_gaps_add_up_to_the_idle_time(trace):
    r = trace_reduce.reduce(trace, top=100)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["device_ops"] and all(v > 0 for _, v in r["device_ops"])
    assert all("/" in n and " " not in n for n, _ in r["device_ops"])
