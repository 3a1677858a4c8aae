"""Ahead-of-time compiles for a described TPU v5e 2x2 host.

Nothing runs: the TPU compiler, installed with jaxlib, compiles each kernel
of the main path at its real width for chips that are described, not
attached, and refuses what the chip would refuse (unaligned tiles, more
scoped VMEM than a kernel may use).  Interpret-mode tests cannot see either.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import collectives as C
from repro.launch.hloanalysis import collective_scopes
from repro.kernels.a2a_pack import a2a_pack_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # Without these the TPU library writes log files to fixed directories
    # under /tmp, outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip executable is written to a persistent cache but can
    # never be read back without the chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_one_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("per_chip", [4 * MIB, 64 * MIB])
def test_a2a_pack_compiles(one_chip, per_chip):
    d = 512
    blk = per_chip // (2 * 2 * d * 4)
    _compile_one_chip(functools.partial(a2a_pack_pallas, interpret=False),
                      one_chip, ((2, 2, blk, d), jnp.float32))


@pytest.mark.parametrize("T", [360, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_compiles(one_chip, T, dtype):
    d = 3840  # h2o-danube d_model
    _compile_one_chip(functools.partial(rmsnorm_pallas, interpret=False),
                      one_chip, ((T, d), dtype), ((d,), dtype))


def test_mamba_scan_compiles(one_chip):
    B, S, di, N = 1, 1024, 8192, 16  # falcon-mamba d_inner and state size
    _compile_one_chip(functools.partial(mamba_scan_pallas, interpret=False),
                      one_chip, ((B, S, di, N), jnp.float32),
                      ((B, S, di, N), jnp.float32), ((B, S, N), jnp.float32))


def test_flash_attention_compiles(one_chip):
    # h2o-danube: 32 query heads over 8 kv heads, head_dim 120 padded to
    # the 128-lane multiple that ops.flash_attention pads it to on a TPU.
    heads, kv_heads, S, hd = 32, 8, 1024, 128
    fn = functools.partial(flash_attention_pallas, group_size=heads // kv_heads,
                           window=4096, scale=120 ** -0.5, interpret=False)
    _compile_one_chip(fn, one_chip, ((heads, S, hd), jnp.bfloat16),
                      ((kv_heads, S, hd), jnp.bfloat16),
                      ((kv_heads, S, hd), jnp.bfloat16))


def test_fulllane_all_to_all_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("pod", "lane"),
                axis_types=(AxisType.Auto,) * 2)
    p = mesh.size
    d = 1024
    blk = 4 * MIB // (p * d * 4)  # 4 MiB per chip: p blocks of [blk, d] f32
    spec = P(("pod", "lane"))
    x = jax.ShapeDtypeStruct((p * p, blk, d), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    f = jax.shard_map(lambda v: C.fulllane_all_to_all(v, "pod", "lane"),
                      mesh=mesh, in_specs=spec, out_specs=spec)
    compiled = jax.jit(f).lower(x).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * MIB


@pytest.mark.parametrize("fn,phases", [
    ("fulllane_all_to_all", ("intra", "cross_pod")),
    ("hierarchical_psum", ("reduce_scatter", "cross_pod", "all_gather")),
])
def test_collective_phases_scoped(topo, fn, phases):
    """Each phase's named scope survives the v5e compiler on some
    collective of the phase, so a device trace of the chip can split the
    function's time by phase.  The v5e compiles the reduce-scatter phase to
    an all-reduce that keeps the scope only on its reducer, which
    ``collective_scopes`` reads in its place."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("pod", "lane"),
                axis_types=(AxisType.Auto,) * 2)
    p = mesh.size
    d = 1024
    blk = 4 * MIB // (p * d * 4)
    spec = P(("pod", "lane"))
    x = jax.ShapeDtypeStruct((p * p, blk, d), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    f = jax.shard_map(lambda v: getattr(C, fn)(v, "pod", "lane"),
                      mesh=mesh, in_specs=spec, out_specs=spec)
    scopes = [name for _, name in
              collective_scopes(jax.jit(f).lower(x).compile().as_text())]
    for phase in phases:
        assert any(f"{fn}/{phase}/" in s for s in scopes), (phase, scopes)
