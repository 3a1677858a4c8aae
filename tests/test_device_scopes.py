"""Named scopes on the device ops: every collective phase of
``core/collectives.py`` and every stage of the train step reach the compiled
program's ``op_name`` metadata, where a device trace can split time by them
(CPU compiles on the suite's virtual devices)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core import collectives as C
from repro.core import schedule as sched
from repro.launch.hloanalysis import collective_scopes
from repro.models import lm
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_step import (
    make_train_step_pjit,
    make_train_step_shardmap,
)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")

_AXES = ("pod", "data", "model")


def _mesh(shape, axes):
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def _scopes_of(text: str, kind: str | None = None) -> list[str]:
    return [name for k, name in collective_scopes(text)
            if kind is None or k == kind]


@pytest.fixture(scope="module")
def tiny_step():
    """The h2o smoke step on a 2x2 (pod x data) mesh, compiled; returns
    (shard_map HLO, pjit HLO)."""
    cfg = get_smoke_config("h2o_danube_3_4b")
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel, fsdp=False))
    mesh = _mesh((2, 2, 1), _AXES)
    opt = OptConfig(learning_rate=1e-3, warmup_steps=2)
    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: init_opt_state(params, opt))
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32)
             for k in ("tokens", "labels")}
    out = []
    for make in (make_train_step_shardmap, make_train_step_pjit):
        mk, _ = make(cfg, mesh, opt)
        out.append(mk(batch).lower(params, state, batch).compile().as_text())
    return out


def _op_names(text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', text)


def test_train_step_stages_scoped(tiny_step):
    shard_map_hlo, pjit_hlo = tiny_step
    names = _op_names(shard_map_hlo)
    for stage in ("grad", "sync", "optimizer"):
        assert any(f"/{stage}/" in n for n in names), stage
    pjit_names = _op_names(pjit_hlo)
    for stage in ("grad", "optimizer"):
        assert any(f"/{stage}/" in n for n in pjit_names), stage


def test_train_step_sync_phases_scoped(tiny_step):
    """Every h2o leaf takes the ZeRO-1 path: its gradient is reduced to the
    moment tile by ``hierarchical_reduce_scatter`` and the new parameter
    tiles are gathered under ``sync/param_gather``."""
    scopes = _scopes_of(tiny_step[0])
    for phase in ("hierarchical_reduce_scatter/reduce_scatter",
                  "hierarchical_reduce_scatter/cross_pod", "param_gather"):
        assert any(f"/sync/{phase}/" in s for s in scopes), (phase, scopes)


def _compile(fn, mesh, shape):
    spec = P(mesh.axis_names)
    f = jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    return jax.jit(f).lower(x).compile().as_text()


@pytest.mark.parametrize("fn,phases", [
    ("hierarchical_psum", ("reduce_scatter", "cross_pod", "all_gather")),
    ("fulllane_broadcast", ("cross_pod", "all_gather")),
    ("fulllane_all_to_all", ("intra", "cross_pod")),
])
def test_fulllane_phases_scoped(fn, phases):
    mesh = _mesh((2, 4), ("pod", "lane"))
    text = _compile(lambda v: getattr(C, fn)(v, "pod", "lane"), mesh,
                    (8 * 8, 16))
    scopes = _scopes_of(text)
    for phase in phases:
        assert any(f"{fn}/{phase}/" in s for s in scopes), (phase, scopes)


@pytest.mark.parametrize("fn,make", [
    ("kported_broadcast_ppermute", sched.kported_broadcast),
    ("kported_scatter_ppermute", sched.kported_scatter),
])
@pytest.mark.parametrize("k", [1, 2])
def test_kported_rounds_scoped(fn, make, k):
    mesh = _mesh((8,), ("x",))
    text = _compile(lambda v: getattr(C, fn)(v, ("x",), k=k), mesh, (64, 16))
    scopes = _scopes_of(text, "collective-permute")
    rounds = len(make(8, k, c=1, root=0).rounds)
    for r in range(rounds):
        assert any(f"{fn}/round{r}/" in s for s in scopes), (r, scopes)
    assert not any(f"{fn}/round{rounds}/" in s for s in scopes)


@pytest.mark.parametrize("shape", [(2, 2), (2, 4)])
def test_fulllane_all_to_all_schedule_event(shape):
    """Building the exchange records its block schedule: one cross-pod
    and ``Ni - 1`` intra rounds, the own-pod and forwarded blocks on the
    lane links under a cross-pod send, ``P - 1`` send slices and ``P``
    landed blocks written."""
    from repro.obs.trace import TRACER

    no, ni = shape
    p = no * ni
    mesh = _mesh(shape, ("pod", "lane"))
    was = bool(TRACER)
    TRACER.enable()
    mark = TRACER.mark()
    try:
        _compile(lambda v: C.fulllane_all_to_all(v, "pod", "lane"), mesh,
                 (p * p, 16))
    finally:
        if not was:
            TRACER.disable()
    events = [r["args"] for r in TRACER.records_since(mark)
              if r["name"] == "fulllane_all_to_all.schedule"]
    assert len(events) == 1
    block_bytes = 16 * 4
    assert events[0] == {
        "cross_pod_rounds": no - 1, "intra_rounds": ni - 1,
        "overlapped_blocks": no * (ni - 1),
        "forwarded_blocks": (no - 1) * (ni - 1),
        "local_move_bytes": (2 * p - 1) * block_bytes}
    assert events[0]["overlapped_blocks"] > 0
