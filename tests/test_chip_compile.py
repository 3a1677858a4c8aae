"""Ahead-of-time compiles for a described TPU v5e 2x2 host.

Nothing runs: the TPU compiler, installed with jaxlib, compiles each kernel
of the main path at its real width for chips that are described, not
attached, and refuses what the chip would refuse (unaligned tiles, more
scoped VMEM than a kernel may use).  Interpret-mode tests cannot see either.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_smoke_config
from repro.core import collectives as C
from repro.launch.hloanalysis import (
    _parse_computations,
    _type_bytes,
    collective_scopes,
)
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
    assert "collective-permute-start" in hlo and "all-to-all" not in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * MIB


_NOT_WRITES = {"parameter", "constant", "bitcast", "get-tuple-element",
               "tuple", "partition-id", "replica-id"}


def _fusion_writes(comps, fused) -> int:
    """Bytes a fusion writes: each output whole, except that a
    dynamic-update-slice into a fusion parameter or an uninitialised
    buffer writes its update in place."""
    by_name = {ins.name: ins for ins in fused.instrs}
    root = fused.instrs[-1]
    outs = ([by_name[o] for o in root.operands] if root.opcode == "tuple"
            else [root])
    total = 0
    for out in outs:
        if out.opcode == "dynamic-update-slice":
            base, update = (by_name[o] for o in out.operands[:2])
            if base.opcode == "parameter" or "AllocateBuffer" in base.raw:
                total += _type_bytes(update.result_type)
                continue
        total += _type_bytes(out.result_type)
    return total


def _entry_writes(text: str) -> int:
    """Bytes the entry computation's non-collective ops write into
    arrays (the scalar index arithmetic left out)."""
    comps, entry = _parse_computations(text)
    total = 0
    for ins in comps[entry].instrs:
        kind = ins.opcode.removesuffix("-start").removesuffix("-done")
        if re.match(r"\w+\[\]", ins.result_type):
            continue
        if ins.opcode in _NOT_WRITES or kind in (
                "all-to-all", "collective-permute") + _COLLECTIVES:
            continue
        calls = re.search(r"calls=%?([\w.\-]+)", ins.raw)
        if ins.opcode == "fusion" and calls:
            total += _fusion_writes(comps, comps[calls.group(1)])
        else:
            total += _type_bytes(ins.result_type)
    return total


def test_fulllane_all_to_all_schedule(topo):
    """At the a2a cell's shape ([4, 6144, 5120] bf16 a chip) the exchange
    copies no whole buffer, its local moves write at most 1.75 output
    buffers (the three send slices and the four landed blocks), and an
    on-node send starts before the first cross-pod block lands."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("pod", "lane"),
                axis_types=(AxisType.Auto,) * 2)
    spec = P(("pod", "lane"))
    x = jax.ShapeDtypeStruct((16, 6144, 5120), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    f = jax.shard_map(lambda v: C.fulllane_all_to_all(v, "pod", "lane"),
                      mesh=mesh, in_specs=spec, out_specs=spec)
    text = jax.jit(f).lower(x).compile().as_text()
    comps, entry = _parse_computations(text)
    ops = comps[entry].instrs
    assert not [ins.name for ins in ops if ins.opcode == "copy" and re.match(
        r"bf16\[(4|2,2),6144,5120\]", ins.result_type)]
    out_bytes = 4 * 6144 * 5120 * 2
    assert _entry_writes(text) <= 1.75 * out_bytes

    def first(opcode, phase):
        return next(k for k, ins in enumerate(ops) if ins.opcode == opcode
                    and f"fulllane_all_to_all/{phase}/" in ins.raw)

    assert first("collective-permute-start", "intra") < first(
        "collective-permute-done", "cross_pod")


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


_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather")


def _ops(text: str, kinds, entry_only: bool = True):
    """``(opcode, array type, replica groups)`` of the instructions of kind
    in ``kinds``, in program order: the entry computation's, or every
    computation's."""
    comps, entry = _parse_computations(text)
    out = []
    for name, comp in comps.items():
        if entry_only and name != entry:
            continue
        for ins in comp.instrs:
            kind = ins.opcode
            if kind.removesuffix("-start") in _COLLECTIVES:
                kind = kind.removesuffix("-start")
            if kind in kinds:
                groups = re.search(r"replica_groups=(\{[{}0-9,]*\})", ins.raw)
                # an async start's type is a tuple: its first array is the
                # operand, which has the result's dtype
                out.append((kind, re.search(r"\w+\[[0-9,]*\]",
                                            ins.result_type).group(0),
                            groups.group(1) if groups else None))
    return out


@pytest.fixture(scope="module")
def pod_data(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)


# One stacked MLP leaf of h2o-danube: [layers, d_model, d_ff] in f32.
_LEAF = (2, 3840, 10240)
_SYNC_KINDS = ("all-reduce", "reduce-scatter", "all-gather", "copy",
               "dynamic-slice")


def _sync_ops(mesh, fn, out_spec):
    x = jax.ShapeDtypeStruct(_LEAF, jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    f = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=out_spec,
                      check_vma=False)
    return _ops(jax.jit(f).lower(x).compile().as_text(), _SYNC_KINDS)


def test_hierarchical_reduce_scatter_is_a_reduce_scatter(pod_data):
    """Scattered along the leaf's ``d_model`` dim the reduce-scatter phase
    compiles to one reduce-scatter of half the leaf, with no relayout copy
    and no all-reduce over ``data``; the cross-pod all-reduce of the half
    follows."""
    ops = _sync_ops(pod_data,
                    lambda v: C.hierarchical_reduce_scatter(v, "pod", "data",
                                                            1),
                    P(None, "data"))
    data, pod = "{{0,1},{2,3}}", "{{0,2},{1,3}}"
    assert ops == [("reduce-scatter", "f32[2,1920,10240]", data),
                   ("all-reduce", "f32[2,1920,10240]", pod)], ops


def test_hierarchical_psum_lowering_unchanged(pod_data):
    """``hierarchical_psum`` (the a2a cell's and API users' path) keeps its
    flat scatter's phases: a full-size all-reduce over ``data`` (the
    compiler's form of the flat reduce-scatter), the cross-pod all-reduce
    of the half, the gather over ``data``."""
    x = jax.ShapeDtypeStruct(_LEAF, jnp.float32,
                             sharding=NamedSharding(pod_data, P()))
    f = jax.shard_map(lambda v: C.hierarchical_psum(v, "pod", "data"),
                      mesh=pod_data, in_specs=P(), out_specs=P(),
                      check_vma=False)
    ops = _ops(jax.jit(f).lower(x).compile().as_text(), _COLLECTIVES)
    data, pod = "{{0,1},{2,3}}", "{{0,2},{1,3}}"
    assert ops == [("all-reduce", "f32[78643200]", data),
                   ("all-reduce", "f32[39321600]", pod),
                   ("all-gather", "f32[78643200]", data)], ops


def test_train_step_gathers_no_moment(topo):
    """The tiny h2o step on a 2x2 (pod x data) keeps the f32 moments
    sharded through the step: every all-gather moves bf16 parameters."""
    from repro.models import lm
    from repro.training.optimizer import OptConfig, init_opt_state
    from repro.training.train_step import make_train_step_shardmap

    cfg = get_smoke_config("h2o_danube_3_4b")
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, fsdp=False))
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2, 1),
                ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    opt = OptConfig()
    mk, (pspec, ospec) = make_train_step_shardmap(cfg, mesh, opt)

    def place(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, s)),
            tree, specs)

    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: init_opt_state(params, opt))
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=NamedSharding(
                                         mesh, P(("pod", "data"))))
             for k in ("tokens", "labels")}
    text = mk(batch).lower(place(params, pspec), place(state, ospec),
                           batch).compile().as_text()
    gathers = _ops(text, ("all-gather",), entry_only=False)
    assert gathers and all(t.startswith("bf16[") for _, t, _ in gathers), (
        gathers)


def test_v2_lite_ep_step_fits_a_v5e(topo):
    """The benchmark's DeepSeek-V2-Lite step at published widths (the dense
    layer and 4 MoE layers, 16 experts a chip, 2 x 4096 tokens a chip) on
    the 2x2: it compiles, its memory fits a 16 GB chip, and every
    all-to-all or scoped collective-permute is the experts' exchange,
    under the layers' dispatch and combine scopes."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.training.optimizer import OptConfig, init_opt_state
    from repro.training.train_step import make_train_step_shardmap

    cfg = dataclasses.replace(get_config("deepseek_v2_lite"), num_layers=5,
                              vocab_size=25600)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2, 1),
                ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    opt = OptConfig()
    mk, (pspec, ospec) = make_train_step_shardmap(cfg, mesh, opt)

    def place(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, s)),
            tree, specs)

    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: init_opt_state(params, opt))
    batch = {k: jax.ShapeDtypeStruct((8, 4096), jnp.int32,
                                     sharding=NamedSharding(
                                         mesh, P(("pod", "data"))))
             for k in ("tokens", "labels")}
    compiled = mk(batch).lower(place(params, pspec), place(state, ospec),
                               batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # the compiler's own collective-permutes (two small f32 sends of the
    # sync) carry no scope
    scopes = [n for k, n in collective_scopes(compiled.as_text())
              if k in ("all-to-all", "collective-permute") and n]
    # per MoE layer: dispatch and combine, each four block sends on the
    # 2x2 (two on-node, two cross-pod), in the forward pass, its
    # recomputation and its transpose
    assert len(scopes) == 24
    assert all(re.search(r"moe/(dispatch|combine)/fulllane_all_to_all/"
                         r"(intra|cross_pod)/", n) for n in scopes), scopes


def test_h2o_step_one_backward_on_a_v5e(topo, monkeypatch):
    """The benchmark's h2o step on one chip (2 layers at published widths,
    8 x 1024 tokens, configured for 8 micro-batches) where the device
    reports a 16 GB chip's limit: the step runs one backward, so no f32
    accumulator pass over the parameters (``grad/while/body/closed_call/
    add``) is left, and the resident state plus ``activation_bytes`` bound
    what the compiler allots, on the safe side."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.training import train_step
    from repro.training.optimizer import OptConfig, init_opt_state

    monkeypatch.setattr(train_step, "_memory_limit", lambda d: int(16e9))
    base = get_config("h2o_danube_3_4b")
    cfg = dataclasses.replace(base, num_layers=2, parallel=dataclasses.replace(
        base.parallel, fsdp=False))
    assert cfg.parallel.microbatches == 8
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    opt = OptConfig()
    mk, (pspec, ospec) = train_step.make_train_step_shardmap(cfg, mesh, opt)

    def place(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, s)),
            tree, specs)

    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: init_opt_state(params, opt))
    batch = {k: jax.ShapeDtypeStruct((8, 1024), jnp.int32,
                                     sharding=NamedSharding(
                                         mesh, P(("pod", "data"))))
             for k in ("tokens", "labels")}
    compiled = mk(batch).lower(place(params, pspec), place(state, ospec),
                               batch).compile()
    assert "grad/while/body/closed_call/add" not in compiled.as_text()
    mem = compiled.memory_analysis()
    # bf16 parameters, f32 gradient, two f32 moments: 14 B a parameter
    resident = 14 * sum(x.size for x in jax.tree.leaves(params))
    assert resident + train_step.activation_bytes(cfg, 8, 1024) >= (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes)
