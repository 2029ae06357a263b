"""Every kernel of ``kernels/ops.py`` compiled by Mosaic for a described
TPU v5e chip at real model widths.

Nothing runs: the TPU compiler is installed without a chip, and refuses
here what the chip would refuse (block shapes off the (8, 128) tiling,
too much VMEM, primitives Mosaic cannot lower), which the interpret-mode
tests in test_kernels.py cannot see.  Widths are granite-moe-3b-a800m's
(d_model 1536, 24 heads / 8 KV heads of 64, 40 experts top-8 of 512,
vocab 49155 padded to 51200) and mamba2-370m's (32 SSD heads of 64,
state 128, chunk 128).  The paged engine's decode step and prefill chunk
are compiled the same way, to read what the TPU compiler makes of the KV
page pool: buffer layouts and temporaries.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm as gmm
from repro.kernels import paged_attention as pa
from repro.kernels import sampling as samp
from repro.kernels import ssd_scan as ssd
from repro.kernels import ssm_update as su

F32, I32 = jnp.float32, jnp.int32

# name -> (kernel entry called with interpret=False, operand shapes)
CASES = {
    "flash_attention": (
        lambda q, k, v: fa.flash_attention_bhsd(q, k, v, interpret=False),
        [((1, 24, 512, 64), F32), ((1, 8, 512, 64), F32),
         ((1, 8, 512, 64), F32)]),
    "paged_attention": (
        lambda q, kp, vp, t, n: pa.paged_attention_bhd(
            q, kp, vp, t, n, interpret=False),
        [((8, 24, 64), F32), ((65, 16, 8, 64), F32),
         ((65, 16, 8, 64), F32), ((8, 8), I32), ((8,), I32)]),
    "ssd_scan": (
        lambda x, dt, a, b, c, d: ssd.ssd_scan_bhcsp(
            x, dt, a, b, c, d, interpret=False),
        [((2, 32, 4, 128, 64), F32), ((2, 32, 4, 128), F32), ((2, 32), F32),
         ((2, 4, 128, 128), F32), ((2, 4, 128, 128), F32), ((2, 32), F32)]),
    "ssm_state_update": (
        lambda st, x, dt, a, b, c, d: su.ssm_state_update_bh(
            st, x, dt, a, b, c, d, interpret=False),
        [((8, 32, 64, 128), F32), ((8, 32, 64), F32), ((8, 32), F32),
         ((8, 32), F32), ((8, 128), F32), ((8, 128), F32), ((8, 32), F32)]),
    "grouped_matmul": (
        lambda buf, w: gmm.grouped_matmul(buf, w, interpret=False),
        [((40, 128, 1536), F32), ((40, 1536, 512), F32)]),
    "moe_decode": (
        lambda x, idx, g, wg, wu, wd: gmm.moe_decode_gmm(
            x, idx, g, wg, wu, wd, interpret=False),
        [((8, 1536), F32), ((8, 8), I32), ((8, 8), F32),
         ((40, 1536, 512), F32), ((40, 1536, 512), F32),
         ((40, 512, 1536), F32)]),
    # the engine's default: temperature 1, padded vocab masked
    "fused_sample": (
        lambda lg, g: samp.fused_sample_bv(
            lg, g, temperature=1.0, vocab_size=49155, interpret=False),
        [((8, 51200), F32), ((8, 51200), F32)]),
    # every filter on; a batch that pads to two blocks; an unaligned vocab
    "fused_sample_filters": (
        lambda lg, g: samp.fused_sample_bv(
            lg, g, temperature=0.8, top_k=20, top_p=0.9, interpret=False),
        [((13, 49155), F32), ((13, 49155), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_sampler_compiles_replicated_over_four_chips(topo,
                                                           monkeypatch):
    """A jitted step replicated over several chips cannot partition a
    Mosaic kernel by itself; the paged engine's sampler runs under
    shard_map there (``sample_tokens_fused(mesh=...)``)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.kernels import ops
    from repro.serve.sampling import sample_tokens_fused

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # compile for TPU
    mesh = Mesh(np.array(topo.devices), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    keys = jax.ShapeDtypeStruct((8, 2), jnp.uint32, sharding=rep)
    logits = jax.ShapeDtypeStruct((8, 51200), F32, sharding=rep)
    compiled = jax.jit(lambda k, lg: sample_tokens_fused(
        k, lg, vocab_size=49155, mesh=mesh)).lower(keys, logits).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _paged_engine_programs(one_chip, num_pages):
    """The paged engine's decode step and prefill chunk, compiled for a
    described v5e at granite-moe-3b-a800m widths (2 layers) over a pool of
    ``num_pages`` pages; block tables stay 8 pages wide."""
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve.engine import PagedEngine

    cfg = get_config("granite-moe-3b-a800m").replace(num_layers=2)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    eng = PagedEngine(cfg, max_batch=8, max_seq_len=128,
                      num_pages=num_pages, max_new_tokens=32,
                      use_sampling_kernel=False)
    lay, B, C = eng.layout, eng.max_batch, eng.prefill_chunk
    pools = on_chip((lay.cache.k, lay.cache.v))
    step = lay._step_fn.lower(
        params, *pools, i32(B), i32(B), i32(B, eng.max_blocks),
        i32(B)).compile()
    prefill = lay._prefill_fn.lower(
        params, *pools, i32(C), i32(C), i32(eng.max_blocks),
        i32()).compile()
    return {"step": step, "prefill": prefill}, lay.cache.k.shape


@pytest.fixture(scope="module")
def paged_programs(one_chip):
    return {n: _paged_engine_programs(one_chip, n) for n in (65, 1025)}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_paged_engine_updates_kv_pool_in_place(program, paged_programs):
    """The page pool is carried through the layer scan and written by
    scatter in place: no copy, slice or slice update of a layer's or the
    whole pool is compiled, and the temporaries do not grow with the
    pool by as much as one layer's K pool."""
    (small, _), (big, shape) = (paged_programs[65], paged_programs[1025])
    pages = shape[1]
    moved = []
    for line in big[program].as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and pages in [int(d) for d in m.group(1).split(",") if d]:
            moved.append(line.strip()[:160])
    assert not moved, moved
    grown = (big[program].memory_analysis().temp_size_in_bytes
             - small[program].memory_analysis().temp_size_in_bytes)
    layer_growth = (pages - 65) * shape[2] * shape[3] * 4  # float32
    assert grown < layer_growth, (grown, layer_growth)
