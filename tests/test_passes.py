"""Schedule optimizer subsystem: pass manager accounting, lane-aware round
compaction (including the paper-scale acceptance cell), message coalescing,
property-style invariants on both machine models, and the selector's
``opt:`` candidates."""

import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import schedule as S
from repro.core import schedule_ir as IR
from repro.core import selector
from repro.core.passes import (
    CoalesceMessages,
    CompactRounds,
    PassManager,
    optimize_schedule,
)
from repro.core.simulate import simulate
from repro.core.topology import (
    Machine,
    Topology,
    hydra_machine,
    nvlink_ib_machine,
)
from repro.core.validate import validate_schedule

HYDRA = hydra_machine()
ALL_ALGS = sorted(S.ALGORITHMS)


def _machines_for(topo: Topology):
    """The same round structure timed under both machine models."""
    return [
        Machine(topo=topo, cost=HYDRA.cost),
        Machine(topo=topo, cost=nvlink_ib_machine().cost),
    ]


# ---------------------------------------------------------------------------
# acceptance criterion: paper-scale opt:klane alltoall
# ---------------------------------------------------------------------------


def test_opt_klane_alltoall_paper_scale_fewer_rounds():
    """ISSUE 2 acceptance: at the paper's 36x32 topology with k=2 lanes the
    optimized k-lane alltoall must run strictly fewer rounds than the
    (N-1)*n + (n-1) of the unoptimized schedule, never be slower, and be
    oracle-valid."""
    topo = Topology(36, 32, 2)
    base = IR.klane_alltoall_ir(topo, 9)
    assert base.num_rounds == 35 * 32 + 31
    opt, records = optimize_schedule(base, "ported", machine=HYDRA)
    assert opt.num_rounds < base.num_rounds
    # limit=k=2 admits exactly pairwise merges of the step structure
    assert opt.num_rounds == -(-35 * 32 // 2) + -(-31 // 2)
    assert simulate(opt, HYDRA).time_us < simulate(base, HYDRA).time_us
    assert validate_schedule(opt).ok
    assert opt.total_elems() == base.total_elems()
    assert records[0].applied and records[0].rounds_after == opt.num_rounds


def test_opt_klane_via_compiled_schedule_cache():
    topo = Topology(36, 32, 2)
    base = IR.compiled_schedule("alltoall", "klane", topo, 2, 9)
    opt = IR.compiled_schedule("alltoall", "klane", topo, 2, 9, optimize="ported")
    assert opt.num_rounds < base.num_rounds
    again = IR.compiled_schedule("alltoall", "klane", topo, 2, 9, optimize="ported")
    assert again is opt  # cached under the optimize-aware key


# ---------------------------------------------------------------------------
# compaction semantics
# ---------------------------------------------------------------------------


def test_lane_mode_preserves_port_width_one():
    """limit=1 compaction merges only port-disjoint rounds, so lane-legal
    schedules stay lane-legal."""
    topo = Topology(4, 6, 2)
    for op, alg in [("broadcast", "klane"), ("scatter", "klane")]:
        cs = IR.compiled_schedule(op, alg, topo, 2, 7)
        opt, _ = optimize_schedule(cs, "lane")
        assert opt.max_port_width() <= max(cs.max_port_width(), 1)
        assert validate_schedule(opt).ok


def test_klane_broadcast_lane_compaction_finds_disjoint_rounds():
    """The adapted k-lane broadcast serializes inter-node waves and on-node
    broadcasts that touch disjoint processors; strict lane compaction must
    recover at least one round."""
    cs = IR.compiled_schedule("broadcast", "klane", Topology(4, 6, 2), 2, 7)
    opt, _ = optimize_schedule(cs, "lane")
    assert opt.num_rounds < cs.num_rounds


def test_ported_mode_respects_port_budget():
    topo = Topology(4, 6, 2)
    cs = IR.compiled_schedule("alltoall", "klane", topo, 2, 7)
    opt, _ = optimize_schedule(cs, "ported")
    assert opt.num_rounds < cs.num_rounds
    assert opt.max_port_width() <= topo.k_lanes


def test_compaction_never_merges_combining_dependencies():
    """Bruck phases are causally chained (every phase forwards blocks
    received in the previous one): compaction must leave the phase count
    intact rather than corrupt data-flow."""
    cs = IR.bruck_alltoall_ir(27, 2, 5)
    nonempty = int((np.diff(cs.round_ptr) > 0).sum())
    opt, _ = optimize_schedule(cs, "ported")
    assert opt.num_rounds == nonempty
    assert validate_schedule(opt).ok


def test_compaction_requires_blocks():
    cs = IR.compile_schedule(S.kported_broadcast(9, 2, 5))  # blockless
    with pytest.raises(ValueError, match="block"):
        CompactRounds(limit=1).apply(cs)


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------


def test_coalesce_fuses_same_pair_messages():
    sch = S.Schedule(
        op="scatter",
        algorithm="test",
        p=3,
        k=1,
        rounds=(
            S.Round(
                (
                    S.Msg(0, 1, 4, (1,)),
                    S.Msg(0, 2, 4, (2,)),
                    S.Msg(0, 1, 3, (0,)),
                )
            ),
        ),
    )
    cs = IR.compile_schedule(sch, with_blocks=True)
    out = CoalesceMessages().apply(cs)
    assert out.num_msgs == 2 and out.num_rounds == 1
    assert out.total_elems() == cs.total_elems()
    i = int(np.flatnonzero(out.dst == 1)[0])
    assert out.elems[i] == 7
    np.testing.assert_array_equal(
        out.blk_ids[out.blk_ptr[i]:out.blk_ptr[i + 1]], [0, 1]
    )


def test_coalesce_noop_returns_same_object():
    cs = IR.kported_alltoall_ir(8, 2, 3)
    assert CoalesceMessages().apply(cs) is cs


# ---------------------------------------------------------------------------
# pass manager
# ---------------------------------------------------------------------------


class _SplitRounds:
    """Deliberately pessimizing pass: one message per round (adds alphas)."""

    name = "split_rounds"

    def apply(self, cs):
        ptr = np.arange(cs.num_msgs + 1, dtype=np.int64)
        return dataclasses.replace(cs, round_ptr=ptr, _stats={})


def test_policy_improved_reverts_pessimizing_pass():
    topo = Topology(3, 4, 2)
    machine = Machine(topo=topo, cost=HYDRA.cost)
    cs = IR.compiled_schedule("alltoall", "fulllane", topo, 2, 7)
    pm = PassManager(
        [_SplitRounds(), CompactRounds(limit=None)],
        machine=machine,
        policy="improved",
        validate=True,
    )
    opt, records = pm.run(cs)
    assert not records[0].applied  # split made it slower -> reverted
    assert records[1].applied
    assert records[0].time_after_us > records[0].time_before_us
    assert opt.num_rounds <= cs.num_rounds
    # trajectory bookkeeping is self-consistent
    assert records[1].rounds_before == cs.num_rounds
    assert records[1].rounds_after == opt.num_rounds
    assert records[1].msgs_after == opt.num_msgs
    d = records[1].as_dict()
    assert d["name"].startswith("compact_rounds")


def test_policy_improved_requires_machine():
    with pytest.raises(ValueError):
        PassManager([CompactRounds()], policy="improved")


def test_validate_flag_catches_broken_pass():
    class _Corrupt:
        name = "corrupt"

        def apply(self, cs):
            src = cs.src.copy()
            src[0] = (src[0] + 1) % cs.p
            return dataclasses.replace(cs, src=src, _stats={})

    cs = IR.compiled_schedule("alltoall", "klane", Topology(3, 4, 2), 2, 7)
    with pytest.raises(AssertionError, match="invalid"):
        PassManager([_Corrupt()], validate=True).run(cs)


def test_unknown_optimize_mode():
    cs = IR.kported_alltoall_ir(8, 2, 3)
    with pytest.raises(ValueError, match="unknown optimize mode"):
        optimize_schedule(cs, "nope")
    with pytest.raises(ValueError, match="unknown optimize mode"):
        IR.compiled_schedule(
            "alltoall", "kported", Topology(2, 4, 2), 2, 3, optimize="nope"
        )


# ---------------------------------------------------------------------------
# property-style invariants (hypothesis)
# ---------------------------------------------------------------------------

ALG_IDX = st.integers(min_value=0, max_value=len(ALL_ALGS) - 1)


@settings(max_examples=15, deadline=None)
@given(N=st.integers(2, 5), n=st.integers(2, 6), c=st.integers(1, 500),
       alg_i=ALG_IDX, mode_i=st.integers(0, 1))
def test_passes_preserve_validity_volume_and_time(N, n, c, alg_i, mode_i):
    """Every optimizer pipeline must (a) keep the oracle verdict valid,
    (b) preserve total element volume, (c) never increase the round count,
    and (d) never increase simulated time on either machine model."""
    topo = Topology(N, n, min(2, n))
    op, alg = ALL_ALGS[alg_i]
    mode = ("lane", "ported")[mode_i]
    cs = IR.compiled_schedule(op, alg, topo, min(2, n), c)
    opt, _ = optimize_schedule(cs, mode)  # validates internally
    assert validate_schedule(opt).ok
    assert opt.total_elems() == cs.total_elems()
    assert opt.num_rounds <= cs.num_rounds
    for machine in _machines_for(topo):
        assert (
            simulate(opt, machine).time_us
            <= simulate(cs, machine).time_us + 1e-9
        )


@settings(max_examples=10, deadline=None)
@given(N=st.integers(2, 5), n=st.integers(2, 6), c=st.integers(1, 500),
       alg_i=ALG_IDX)
def test_full_pipeline_improved_policy_both_machines(N, n, c, alg_i):
    """Compaction + keep-if-improved coalescing under the PassManager must
    end at least as fast as the input on the machine it optimizes for."""
    topo = Topology(N, n, min(2, n))
    op, alg = ALL_ALGS[alg_i]
    cs = IR.compiled_schedule(op, alg, topo, min(2, n), c)
    for machine in _machines_for(topo):
        pm = PassManager(
            [CompactRounds(limit=None), CoalesceMessages()],
            machine=machine,
            policy="improved",
            validate=True,
        )
        opt, _ = pm.run(cs)
        assert opt.total_elems() == cs.total_elems()
        assert (
            simulate(opt, machine).time_us
            <= simulate(cs, machine).time_us + 1e-9
        )


# ---------------------------------------------------------------------------
# selector integration: opt: candidates
# ---------------------------------------------------------------------------


def test_selector_offers_opt_candidates():
    algs = selector._candidate_algs("alltoall", Topology(2, 16, 8))
    assert "opt:klane" in algs and "opt:fulllane" in algs
    assert "klane" in algs


def test_select_ranks_opt_variants():
    ch = selector.select(
        "alltoall", 1 << 8, num_nodes=4, procs_per_node=16, k_lanes=4
    )
    names = [a for a, _ in ch.candidates]
    assert any(a.startswith("opt:") for a in names)
    # an optimized variant can never rank behind its own base family by
    # more than numerical noise (compaction is monotone)
    d = dict(ch.candidates)
    for a, t in ch.candidates:
        if a.startswith("opt:") and a[4:] in d:
            assert t <= d[a[4:]] + 1e-9


def test_crossover_table_with_opt_candidates():
    sizes = [1 << 4, 1 << 12, 1 << 24]
    table = selector.crossover_table(
        "alltoall", sizes=sizes, num_nodes=4, procs_per_node=16, k_lanes=4
    )
    assert [s for s, _, _ in table] == sizes
    assert all(est > 0 for _, _, est in table)
