"""ISSUE 6: fault model, schedule repair, degraded pricing, cache keying,
selector fallback ladder, and the elastic fault wiring.

Everything here is numpy-only (no jax) so the CI fast job runs the full
fault matrix; the jax ServeEngine chaos lives in ``tools/chaos.py
--engine`` and the full job's chaos-smoke step.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.core import schedule_ir as IR
from repro.core.faults import (
    HEALTHY,
    FaultSpec,
    FaultedMachine,
    UnrepairableFaultError,
    apply_faults,
    degradation_of,
    sample_faults,
)
from repro.core.passes import RepairSchedule, repair_schedule
from repro.core.schedule_ir import (
    compiled_schedule,
    relay_messages,
    schedule_cache_clear,
    schedule_cache_info,
)
from repro.core.selector import select
from repro.core.simulate import simulate
from repro.core.topology import HYDRA, NVLINK_IB, Machine, Topology
from repro.core.validate import check_schedule
from repro.training.elastic import (
    FaultEvent,
    StragglerMonitor,
    plan_remesh_for_faults,
)

SMALL_TOPOS = [
    Topology(3, 4, 2),
    Topology(4, 6, 2),
]

ALLTOALL_FAMILIES = ["kported", "bruck", "klane", "fulllane"]

COSTS = {"hydra": HYDRA.cost, "nvlink_ib": NVLINK_IB.cost}


def _machine(topo, cost_name="hydra"):
    return Machine(topo=topo, cost=COSTS[cost_name])


def _scenarios(topo):
    """The acceptance-criteria fault matrix for one topology."""
    return {
        "dead_lane": FaultSpec(dead_lanes=((1, 1),)),
        "dead_rail": FaultSpec(dead_rails=1),
        "dead_port": FaultSpec(dead_ranks=(topo.rank_of(1, 1),)),
        "dead_node": FaultSpec(dead_nodes=(topo.num_nodes - 1,)),
        "derated": FaultSpec(derated_links=((0, 2.0),)),
    }


def _final_deliveries(cs):
    """Required final (owner, block) pairs delivered by messages — the
    alltoall block-semantics signature a repair must preserve exactly."""
    p = cs.p
    nblk = np.diff(cs.blk_ptr)
    dst = np.repeat(cs.dst, nblk)
    required = (cs.blk_ids % p) == dst
    return set(zip(dst[required].tolist(), cs.blk_ids[required].tolist()))


# ---------------------------------------------------------------------------
# FaultSpec: canonicalization, fingerprints, sampling
# ---------------------------------------------------------------------------


def test_spec_canonicalizes_and_fingerprints_stably():
    a = FaultSpec(dead_lanes=((2, 1), (0, 1), (2, 1)), dead_ranks=(5, 3, 5))
    b = FaultSpec(dead_lanes=((0, 1), (2, 2)), dead_ranks=(3, 5))
    assert a == b and hash(a) == hash(b)
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != HEALTHY.fingerprint()
    assert HEALTHY.is_healthy and not a.is_healthy


def test_spec_validate_rejects_out_of_range():
    topo = Topology(3, 4, 2)
    with pytest.raises(ValueError):
        FaultSpec(dead_nodes=(7,)).validate(topo)
    with pytest.raises(ValueError):
        FaultSpec(dead_ranks=(12,)).validate(topo)
    with pytest.raises(ValueError):
        FaultSpec(dead_rails=1, dead_lanes=((0, 2),)).validate(topo)
    with pytest.raises(ValueError):
        FaultSpec(derated_links=((0, 0.5),))


def test_sample_faults_deterministic_and_repairable():
    topo = Topology(4, 6, 2)
    a = sample_faults(topo, seed=7, dead_rails=1, n_dead_lanes=1,
                      n_dead_ranks=2, n_derated_links=1)
    b = sample_faults(topo, seed=7, dead_rails=1, n_dead_lanes=1,
                      n_dead_ranks=2, n_derated_links=1)
    assert a == b
    assert a != sample_faults(topo, seed=8, dead_rails=1, n_dead_lanes=1,
                              n_dead_ranks=2, n_derated_links=1)
    a.validate(topo)
    deg = degradation_of(a, topo)
    # repairable by construction: every node keeps >= 1 rail and >= 1 port
    assert (deg.lanes >= 1).all()
    assert (~deg.dead_port.reshape(topo.num_nodes, -1)).any(axis=1).all()


# ---------------------------------------------------------------------------
# degraded pricing through the simulator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo", SMALL_TOPOS, ids=lambda t: f"{t.num_nodes}x{t.procs_per_node}")
def test_degraded_pricing_monotone_and_inf_contract(topo):
    m = _machine(topo)
    cs = compiled_schedule("alltoall", "klane", topo, topo.k_lanes, 5)
    t_h = simulate(cs, m).time_us
    # FaultedMachine with an empty spec is bit-exact with the base machine
    assert simulate(cs, FaultedMachine(topo=topo, cost=m.cost)).time_us == t_h
    # derated link: strictly more expensive, still finite
    t_d = simulate(cs, apply_faults(m, FaultSpec(derated_links=((0, 2.0),)))).time_us
    assert t_h < t_d < math.inf
    # dead rail: fewer lanes, weakly more expensive
    t_r = simulate(cs, apply_faults(m, FaultSpec(dead_rails=1))).time_us
    assert t_r >= t_h and math.isfinite(t_r)
    # dead port on a rank with inter traffic: inf until repaired
    t_p = simulate(cs, apply_faults(m, FaultSpec(dead_ranks=(topo.rank_of(1, 1),)))).time_us
    assert math.isinf(t_p)
    # dead node: inf
    t_n = simulate(cs, apply_faults(m, FaultSpec(dead_nodes=(0,)))).time_us
    assert math.isinf(t_n)


def test_apply_faults_healthy_is_identity():
    m = _machine(Topology(3, 4, 2))
    assert apply_faults(m, HEALTHY) is m
    fm = apply_faults(m, FaultSpec(dead_rails=1))
    assert isinstance(fm, FaultedMachine) and fm.topo == m.topo
    assert fm.degradation() is not None
    assert m.degradation() is None


# ---------------------------------------------------------------------------
# relay_messages primitive
# ---------------------------------------------------------------------------


def test_relay_messages_stages_hops_and_keeps_oracle():
    topo = Topology(3, 4, 2)
    cs = compiled_schedule("alltoall", "klane", topo, 2, 3)
    n = topo.procs_per_node
    inter = (cs.src // n) != (cs.dst // n)
    # relay the first inter message out through a same-node sibling
    m = int(np.argmax(inter))
    via_src = np.full(cs.num_msgs, -1, dtype=np.int64)
    proxy = (int(cs.src[m]) // n) * n + ((int(cs.src[m]) + 1) % n)
    via_src[m] = proxy
    out = relay_messages(cs, via_src, np.full(cs.num_msgs, -1, dtype=np.int64))
    assert out.num_msgs == cs.num_msgs + 1
    assert check_schedule(out).ok
    # payload conserved: both hops carry the original elems
    assert out.elems.sum() == cs.elems.sum() + cs.elems[m]
    assert _final_deliveries(out) == _final_deliveries(cs)


def test_relay_messages_rejects_self_relay():
    topo = Topology(3, 4, 2)
    cs = compiled_schedule("alltoall", "klane", topo, 2, 3)
    via = np.full(cs.num_msgs, -1, dtype=np.int64)
    via[0] = int(cs.src[0])
    with pytest.raises(ValueError, match="own endpoint"):
        relay_messages(cs, via, np.full(cs.num_msgs, -1, dtype=np.int64))


# ---------------------------------------------------------------------------
# RepairSchedule: the acceptance matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cost_name", sorted(COSTS), ids=str)
@pytest.mark.parametrize("topo", SMALL_TOPOS, ids=lambda t: f"{t.num_nodes}x{t.procs_per_node}")
@pytest.mark.parametrize("family", ALLTOALL_FAMILIES)
def test_repair_matrix(topo, family, cost_name):
    """(fault scenario) x (alltoall family) x (machine model): the repaired
    schedule passes the full oracle with block semantics identical to
    healthy; unrepairable faults revert; repaired schedules price finite on
    the degraded machine (reverted dead-node ones price inf)."""
    m = _machine(topo, cost_name)
    healthy = compiled_schedule("alltoall", family, topo, topo.k_lanes, 3)
    sig = _final_deliveries(healthy)
    for name, spec in _scenarios(topo).items():
        repaired, recs = repair_schedule(healthy, spec, topo=topo)
        assert check_schedule(repaired).ok, (name, family)
        assert _final_deliveries(repaired) == sig, (name, family)
        t = simulate(repaired, apply_faults(m, spec)).time_us
        if name == "dead_node":
            assert repaired is healthy and recs[0].applied is False
            assert math.isinf(t)
        else:
            assert math.isfinite(t), (name, family)
            t_h = simulate(healthy, m).time_us
            assert t >= t_h * (1 - 1e-9), (name, family)


TREE_FAMILIES = [(op, alg) for op in ("broadcast", "scatter")
                 for alg in ("kported", "klane", "fulllane")]


@pytest.mark.parametrize("scenario", ["dead_rail", "dead_port"])
@pytest.mark.parametrize("topo", SMALL_TOPOS, ids=lambda t: f"{t.num_nodes}x{t.procs_per_node}")
@pytest.mark.parametrize("op_alg", TREE_FAMILIES, ids=lambda t: "-".join(t))
def test_repair_matrix_tree_families(op_alg, topo, scenario):
    """The repair matrix for the broadcast and scatter families, on the
    schedules a faulted ``opt:`` candidate repairs (the color-packed
    ones): repaired and oracle-valid within the surviving budget, or
    reverted where the spec leaves a node the schedule routes through
    with no live network port."""
    op, alg = op_alg
    spec = _scenarios(topo)[scenario]
    packed = compiled_schedule(op, alg, topo, topo.k_lanes, 13,
                               optimize="color")
    try:
        RepairSchedule(spec, topo=topo).apply(packed)
    except UnrepairableFaultError:
        deg = degradation_of(spec, topo)
        n = topo.procs_per_node
        live = (~deg.dead_port).reshape(topo.num_nodes, n).any(axis=1)
        assert not live.all(), (op, alg, scenario)
        out, recs = repair_schedule(packed, spec, topo=topo)
        assert out is packed and recs[0].applied is False
        return
    repaired, recs = repair_schedule(packed, spec, topo=topo)
    assert check_schedule(repaired).ok, (op, alg, scenario)
    assert recs[0].oracle_ok in (True, None)
    assert repaired.total_elems() >= packed.total_elems()
    n = topo.procs_per_node
    inter = (repaired.src // n) != (repaired.dst // n)
    if scenario == "dead_rail":
        assert repaired.max_port_width() <= topo.k_lanes - 1
    else:
        dead = spec.dead_ranks[0]
        assert not ((repaired.src == dead) & inter).any()
        assert not ((repaired.dst == dead) & inter).any()
    t = simulate(repaired, apply_faults(_machine(topo), spec)).time_us
    assert math.isfinite(t), (op, alg, scenario)


def test_repair_dead_port_relays_not_regenerates():
    """Dead-NIC repair is a rewrite: the repaired schedule contains every
    healthy payload (same total elems through the relay) and only the
    dead rank's inter traffic gained hops."""
    topo = Topology(3, 4, 2)
    dead = topo.rank_of(1, 1)
    healthy = compiled_schedule("alltoall", "klane", topo, 2, 3)
    repaired, recs = repair_schedule(healthy, FaultSpec(dead_ranks=(dead,)), topo=topo)
    assert recs[0].applied and recs[0].oracle_ok
    n = topo.procs_per_node
    inter = (healthy.src // n) != (healthy.dst // n)
    touched = int(((healthy.src == dead) | (healthy.dst == dead))[inter].sum())
    assert repaired.num_msgs == healthy.num_msgs + touched
    # no message in the repaired schedule moves inter bytes through the
    # dead rank's network port
    rinter = (repaired.src // n) != (repaired.dst // n)
    assert not ((repaired.src == dead) & rinter).any()
    assert not ((repaired.dst == dead) & rinter).any()


def test_repair_repacks_overpacked_schedule():
    """A color-packed schedule whose port width exceeds the surviving lane
    budget must be re-packed down to it — the cache-invalidation story:
    healthy opt: recipes are not runnable under a dead rail."""
    topo = Topology(4, 6, 2)
    packed = compiled_schedule("alltoall", "klane", topo, 2, 3,
                               optimize="color")
    if packed.max_port_width() <= 1:
        pytest.skip("packer found no width-2 packing to repair")
    repaired, recs = repair_schedule(packed, FaultSpec(dead_rails=1), topo=topo)
    assert recs[0].applied
    assert repaired.max_port_width() <= 1
    assert check_schedule(repaired).ok
    assert _final_deliveries(repaired) == _final_deliveries(packed)


def test_repair_raises_unrepairable_inside_pass():
    topo = Topology(3, 4, 2)
    cs = compiled_schedule("alltoall", "klane", topo, 2, 3)
    with pytest.raises(UnrepairableFaultError, match="dead node"):
        RepairSchedule(FaultSpec(dead_nodes=(0,)), topo=topo).apply(cs)
    # the driver contract: revert, never raise
    out, recs = repair_schedule(cs, FaultSpec(dead_nodes=(0,)), topo=topo)
    assert out is cs and recs[0].applied is False


# ---------------------------------------------------------------------------
# cache keying: fault fingerprints isolate degraded entries
# ---------------------------------------------------------------------------


def test_cache_key_includes_fault_fingerprint():
    schedule_cache_clear()
    topo = Topology(3, 4, 2)
    spec = FaultSpec(dead_ranks=(topo.rank_of(1, 1),))
    healthy = compiled_schedule("alltoall", "klane", topo, 2, 3)
    faulted = compiled_schedule("alltoall", "klane", topo, 2, 3, faults=spec)
    assert faulted is not healthy
    assert faulted.num_msgs > healthy.num_msgs  # relayed, not reused
    # both entries cached independently
    info0 = schedule_cache_info()
    assert compiled_schedule("alltoall", "klane", topo, 2, 3) is healthy
    assert compiled_schedule("alltoall", "klane", topo, 2, 3, faults=spec) is faulted
    info1 = schedule_cache_info()
    assert info1["hits"] == info0["hits"] + 2
    assert info1["misses"] == info0["misses"]
    # a different fault set is a different entry
    other = compiled_schedule(
        "alltoall", "klane", topo, 2, 3, faults=FaultSpec(dead_rails=1)
    )
    assert other is not faulted
    # healthy spec normalizes to the healthy entry
    assert compiled_schedule("alltoall", "klane", topo, 2, 3, faults=HEALTHY) is healthy


# ---------------------------------------------------------------------------
# selector: graceful-degradation ladder
# ---------------------------------------------------------------------------

MESH = dict(num_nodes=3, procs_per_node=4, k_lanes=2)


def test_selector_deadline_zero_skips_opt_rung():
    ch = select("alltoall", 512, **MESH, deadline_s=0.0)
    assert not ch.algorithm.startswith("opt:")
    assert all(not a.startswith("opt:") for a, _ in ch.candidates)
    full = select("alltoall", 512, **MESH)
    assert any(a.startswith("opt:") for a, _ in full.candidates)


def test_selector_faulted_race_prices_repaired_schedules():
    healthy = select("alltoall", 512, **MESH)
    ch = select("alltoall", 512, **MESH, faults=FaultSpec(dead_rails=1))
    assert math.isfinite(ch.est_us)
    assert ch.est_us >= healthy.est_us * (1 - 1e-9)
    # a dead node cannot be repaired away: every candidate prices inf but
    # the ladder still returns a runnable choice for the elastic layer
    cn = select("alltoall", 512, **MESH, faults=FaultSpec(dead_nodes=(1,)))
    assert cn.algorithm
    assert math.isinf(cn.est_us)


def test_selector_healthy_faultspec_equals_no_faults():
    a = select("alltoall", 512, **MESH)
    b = select("alltoall", 512, **MESH, faults=HEALTHY)
    assert a.algorithm == b.algorithm and a.est_us == b.est_us


# ---------------------------------------------------------------------------
# elastic fault wiring
# ---------------------------------------------------------------------------


def test_observe_fault_lane_strikes_then_evicts():
    mon = StragglerMonitor(patience=3)
    assert mon.observe_fault(FaultEvent(kind="lane", node=0)) == "warn"
    assert mon.observe_fault(FaultEvent(kind="lane", node=0)) == "warn"
    assert mon.observe_fault(FaultEvent(kind="lane", node=1)) == "evict"


def test_observe_fault_node_is_immediate_evict():
    mon = StragglerMonitor(patience=3)
    assert mon.observe_fault(FaultEvent(kind="node", node=2)) == "evict"
    assert mon.strikes >= mon.patience


def test_fault_event_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(kind="gremlin", node=0)


def test_plan_remesh_for_faults_deterministic_and_deduped():
    events = [
        FaultEvent(kind="node", node=2, step=10),
        FaultEvent(kind="lane", node=0, step=11),
        FaultEvent(kind="node", node=2, step=12),  # duplicate report
    ]
    plan = plan_remesh_for_faults(
        events, num_pods=4, data_axis=2, model_axis=1,
        global_batch=32, last_committed_step=100,
    )
    assert plan.feasible and plan.mesh_shape == (3, 2, 1)
    assert plan.global_batch == 24 and plan.restart_step == 100
    assert "dead pods [2]" in plan.note
    # order-independent
    assert plan == plan_remesh_for_faults(
        list(reversed(events)), num_pods=4, data_axis=2, model_axis=1,
        global_batch=32, last_committed_step=100,
    )
    # lane-only faults never shrink the mesh
    lane_plan = plan_remesh_for_faults(
        [FaultEvent(kind="lane", node=1)], num_pods=4, data_axis=2,
        model_axis=1, global_batch=32, last_committed_step=100,
    )
    assert lane_plan.mesh_shape == (4, 2, 1) and lane_plan.global_batch == 32


# ---------------------------------------------------------------------------
# chaos harness library + bench_gate robustness
# ---------------------------------------------------------------------------


def test_chaos_schedule_sweep_smoke():
    import sys

    sys.path.insert(0, "tools")
    import chaos

    report = chaos.run_schedule_chaos(
        seed=3, num_nodes=3, procs_per_node=4, k_lanes=2, payload=2
    )
    assert report["ok"], [c for c in report["cells"] if not c["contract_ok"]]
    assert len(report["cells"]) == 2 * len(ALLTOALL_FAMILIES) * 7
    assert all(c["contract_ok"] for c in report["selector_ladder"])


def test_bench_gate_corrupt_files_one_line_fail(tmp_path, capsys):
    import sys

    sys.path.insert(0, "tools")
    import bench_gate

    good = tmp_path / "good.json"
    good.write_text(
        '{"cells": [{"table": "T", "impl": "x", "k": 1, "c": 1, "sim_us": 1.0}]}'
    )
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text('{"cells": [{"table"')  # truncated write
    # corrupt fresh file
    assert bench_gate.main([str(corrupt), "--baseline", str(good)]) == 1
    out = capsys.readouterr().out
    assert "bench_gate: FAIL" in out and "not a readable trajectory" in out
    # corrupt baseline file
    assert bench_gate.main([str(good), "--baseline", str(corrupt)]) == 1
    out = capsys.readouterr().out
    assert "bench_gate: FAIL" in out and "not a readable trajectory" in out
    assert "Traceback" not in out
    # wrong JSON shape (list instead of dict) also fails cleanly
    shape = tmp_path / "shape.json"
    shape.write_text("[1, 2, 3]")
    assert bench_gate.main([str(shape), "--baseline", str(good)]) == 1
    assert "bench_gate: FAIL" in capsys.readouterr().out


@pytest.mark.slow
def test_degraded_bench_cells_present():
    """The DEG table emits the headline cells (klane a2a under one dead
    rail, repaired, vs the native k=1 fallback) with finite degraded
    times.  Paper-scale (p=1152), so slow-marked; the check.sh bench
    smoke + bench_gate cover the DEG cells in tier-1."""
    from benchmarks.paper_tables import table_degraded

    rows = table_degraded()
    assert rows
    headline = [
        r for r in rows if r["impl"] == "deg:klane_a2a" and r["c"] == 869
    ]
    assert len(headline) == 1
    (r,) = headline
    assert math.isfinite(r["sim_us"]) and r["sim_us"] >= r["healthy_us"]
    # repair matches the natively regenerated k=1 schedule's price
    assert r["sim_us"] == pytest.approx(r["native_us"], rel=1e-6)
    for row in rows:
        assert math.isfinite(row["sim_us"])
        assert row["table"] == "DEG" and "fingerprint" in row
