"""ISSUE 4: the conflict-graph coloring round packer (``ColorRounds``),
the zero-block split-part causality lift in
``validate.block_dependencies``, the shared simulator costing hooks, and
the recipe replay of the ``"color"`` pipeline on the plan cell's
meshes."""

import dataclasses

import numpy as np
import pytest

from repro.core import schedule as S
from repro.core import schedule_ir as IR
from repro.core import selector
from repro.core.passes import ColorRounds
from repro.core.simulate import lane_time, port_time, simulate
from repro.core.topology import Topology, hydra_machine
from repro.core.validate import block_dependencies, validate_schedule

HYDRA = hydra_machine()


# ---------------------------------------------------------------------------
# ColorRounds: packing behaviour
# ---------------------------------------------------------------------------


def test_color_requires_blocks_and_divisible_nodes():
    blockless = IR.compile_schedule(S.kported_scatter(8, 2, 3))
    with pytest.raises(ValueError, match="block"):
        ColorRounds(limit=1, procs_per_node=4).apply(blockless)
    cs = IR.kported_alltoall_ir(8, 2, 3)
    with pytest.raises(ValueError, match="divisible"):
        ColorRounds(limit=1, procs_per_node=3).apply(cs)


def test_color_identity_when_input_already_packed():
    """A schedule the coloring reproduces exactly comes back as the same
    object (so run_passes records it as not-applied and the recipe cache
    stores an identity recipe)."""
    cs = IR.kported_alltoall_ir(8, 2, 3)  # ceil(7/2)=4 saturated rounds
    assert ColorRounds(limit=2, procs_per_node=4).apply(cs) is cs


def test_color_respects_dependency_chains():
    """Bruck's phases are fully chained; with the refined class-purity rule
    (an intra message already network-priced in its input round may share a
    color with inter traffic) the coloring reproduces exactly the nonempty
    phase count — no more, no less."""
    cs = IR.bruck_alltoall_ir(27, 2, 5)
    nonempty = int((np.diff(cs.round_ptr) > 0).sum())
    col = ColorRounds(limit=4 * cs.k, procs_per_node=9).apply(cs)
    assert col.num_rounds == nonempty
    assert validate_schedule(col).ok


def test_color_budget_ladder_on_klane_alltoall():
    """The klane alltoall packs to ceil(inter/L) + ceil(intra/L) at budget
    L — message granularity reproduces the optimal regular packing at
    every rung of the ladder."""
    topo = Topology(4, 6, 2)
    cs = IR.compiled_schedule("alltoall", "klane", topo, 2, 7)
    N, n = 4, 6
    for mult in (1, 2, 4):
        L = mult * cs.k
        col = ColorRounds(limit=L, procs_per_node=n).apply(cs)
        assert col.num_rounds == -(-(N - 1) * n // L) + -(-(n - 1) // L)
        assert validate_schedule(col).ok
        assert col.total_elems() == cs.total_elems()


def test_color_splits_rounds_first_fit_cannot():
    """Message granularity: the coloring splits input rounds apart — some
    input round's messages land in different colors, which no packer that
    moves whole rounds can do — and so packs the k-lane broadcast at the
    paper topology below the 23 rounds whole-round first-fit stopped at
    (coloring reaches <= 12), faster than the base in the k-ported model."""
    topo = Topology(36, 32, 2)
    base = IR.compiled_schedule("broadcast", "klane", topo, 2, 10_000)
    col = ColorRounds(limit=4 * base.k, procs_per_node=32).apply(base)
    assert col.num_rounds <= 12 < 23 < base.num_rounds
    assert validate_schedule(col).ok
    # the packer's message permutation, read off a tagged-payload copy
    tagged = dataclasses.replace(
        base, elems=np.arange(base.num_msgs, dtype=np.int64), _stats={}
    )
    morder = ColorRounds(limit=4 * base.k, procs_per_node=32).apply(
        tagged
    ).elems
    in_round = base.round_ids()[morder]
    out_round = col.round_ids()
    colors_per_input_round = [
        np.unique(out_round[in_round == r]).size
        for r in range(base.num_rounds)
    ]
    assert max(colors_per_input_round) > 1
    assert (
        simulate(col, HYDRA, ported=True).time_us
        < simulate(base, HYDRA, ported=True).time_us
    )


@pytest.mark.parametrize("op_alg", sorted(S.ALGORITHMS))
def test_color_valid_and_lex_raced_never_worse(op_alg):
    """ColorRounds is not provably never-slower, so the contract is: every
    coloring — the port-budget rung the repair uses and the structural
    rung the planner uses — is oracle-valid and volume-preserving (the
    selector races it against its base for speed)."""
    op, alg = op_alg
    topo = Topology(3, 4, 2)
    cs = IR.compiled_schedule(op, alg, topo, 2, 13)
    for limit in (cs.k, None):
        col = ColorRounds(limit=limit, procs_per_node=4).apply(cs)
        assert validate_schedule(col).ok
        assert col.total_elems() == cs.total_elems()
        assert col.num_msgs == cs.num_msgs


def test_color_headline_klane_alltoall_paper_scale():
    """ISSUE 4 acceptance: at the paper's 36x32/k=2 the coloring packer
    must pack the k-lane alltoall below PR 3's 288 first-fit rounds
    (target <= 260) with >= 4.2x simulated at c=1, oracle-valid."""
    topo = Topology(36, 32, 2)
    base = IR.klane_alltoall_ir(topo, 1)
    col = ColorRounds(limit=4 * base.k, procs_per_node=32).apply(base)
    assert col.num_rounds <= 260 < 288  # whole-round first-fit's plateau
    base_us = simulate(base, HYDRA).time_us
    col_us = simulate(col, HYDRA).time_us
    assert base_us / col_us >= 4.2
    assert validate_schedule(col).ok
    assert col.total_elems() == base.total_elems()


def test_optimize_mode_color_via_cache_and_selector_parse():
    topo = Topology(4, 6, 2)
    base = IR.compiled_schedule("alltoall", "klane", topo, 2, 7)
    opt = IR.compiled_schedule("alltoall", "klane", topo, 2, 7, optimize="color")
    assert opt.num_rounds < base.num_rounds
    assert (
        IR.compiled_schedule("alltoall", "klane", topo, 2, 7, optimize="color")
        is opt
    )
    assert selector._parse_alg("opt:klane") == ("klane", "color")
    assert selector._parse_alg("klane") == ("klane", None)
    with pytest.raises(ValueError, match="unknown optimize mode"):
        IR.compiled_schedule("alltoall", "klane", topo, 2, 7,
                             optimize="reorder")


# ---------------------------------------------------------------------------
# zero-block split parts: the dependency lift (ISSUE 4 bugfix satellite)
# ---------------------------------------------------------------------------


def _forward_chain_split():
    """p=6 alltoall fragment: 0 -> 1 delivers block (0->2), 1 -> 2 forwards
    it as one payload in 4 parallel parts (3 of them zero-block), 2 -> 3
    forwards on."""
    i64 = np.int64
    sp = IR.CompiledSchedule(
        op="alltoall",
        algorithm="toy",
        p=6,
        k=1,
        src=np.array([0, 1, 1, 1, 1, 2], dtype=i64),
        dst=np.array([1, 2, 2, 2, 2, 3], dtype=i64),
        elems=np.array([8, 2, 2, 2, 2, 8], dtype=i64),
        round_ptr=np.array([0, 1, 5, 6], dtype=i64),
        blk_ptr=np.array([0, 1, 2, 2, 2, 2, 3], dtype=i64),
        blk_ids=np.array([2, 2, 2], dtype=i64),
    )
    assert np.diff(sp.blk_ptr).tolist() == [1, 1, 0, 0, 0, 1]
    return sp


def test_zero_block_parts_have_no_edges_without_lift():
    """Pins the hazard the lift fixes: without it the zero-block parts are
    dependency-free (a packer may hoist them ahead of their payload's
    producer) and the downstream forwarder waits for only one part."""
    sp = _forward_chain_split()
    dep_ptr, dep_ids = block_dependencies(sp, lift_zero_block=False)
    ndep = np.diff(dep_ptr)
    assert ndep[2] == ndep[3] == ndep[4] == 0  # the zero-block parts
    assert ndep[5] == 1  # forwarder waits for the one block-bearing part


def test_zero_block_lift_pins_split_part_semantics():
    """The lift: parts inherit their siblings' providers, and a consumer
    waits for ALL parts of the delivering payload."""
    sp = _forward_chain_split()
    dep_ptr, dep_ids = block_dependencies(sp)

    def deps(i):
        return dep_ids[dep_ptr[i]:dep_ptr[i + 1]].tolist()

    assert deps(1) == [0]
    assert deps(2) == deps(3) == deps(4) == [0]  # requirement-side lift
    assert deps(5) == [1, 2, 3, 4]  # acquisition-side lift: all parts


def test_color_does_not_hoist_zero_block_parts():
    """ISSUE 4 acceptance for the satellite: the message-granularity packer
    keeps every split part strictly after the payload's producer and the
    downstream forwarder strictly after every part."""
    sp = _forward_chain_split()
    col = ColorRounds(limit=8, procs_per_node=6).apply(sp)
    # the toy is a partial alltoall: compare data-flow health against the
    # input instead of the full-op postcondition
    rep, base_rep = validate_schedule(col), validate_schedule(sp)
    assert rep.causality_violations == 0
    assert rep.missing_final == base_rep.missing_final
    rid = col.round_ids()
    provider_round = int(rid[col.src == 0][0])
    part_rounds = rid[col.src == 1]
    consumer_round = int(rid[col.src == 2][0])
    assert (part_rounds > provider_round).all()
    assert (consumer_round > part_rounds).all()


# ---------------------------------------------------------------------------
# the shared costing hooks
# ---------------------------------------------------------------------------


def test_costing_hooks_match_simulator_reference():
    """port_time/lane_time are THE simulator formulas: spot-check them
    against the reference expressions for both port models."""
    cost = HYDRA.cost
    t = port_time(cost, 100.0, 1, True, 2, ported=False)
    assert t == pytest.approx(cost.alpha_inter + cost.beta_inter * 100.0)
    t = port_time(cost, 100.0, 4, True, 2, ported=True)
    ref = max(
        cost.alpha_inter + cost.beta_inter * 100.0 / 2, cost.alpha_inter * 2
    )
    assert t == pytest.approx(ref)
    t = port_time(cost, 100.0, 4, False, 2, ported=True, alpha_batches=False)
    assert t == pytest.approx(cost.alpha_intra + cost.beta_intra * 100.0 / 2)
    t = lane_time(cost, 1000.0, 3, 2)
    assert t == pytest.approx(cost.alpha_inter + cost.beta_inter * 1000.0 / 2)


# ---------------------------------------------------------------------------
# recipe replay of the "color" pipeline on the plan cell's meshes
# ---------------------------------------------------------------------------

#: the plan cell's meshes (nodes, procs_per_node, k_lanes) and its dispatch
#: payload: T tokens a chip x top-6 x hidden 5120, split over the group
_PLAN_MESHES = [(4, 8, 8), (18, 8, 8)]
_PLAN_TOKENS = [1, 64, 4096]


def _plan_cell_pairs():
    pairs = []
    for nn, ppn, kl in _PLAN_MESHES:
        topo = Topology(nn, ppn, kl)
        for op in ("broadcast", "scatter", "alltoall"):
            for alg in selector._candidate_algs(op, topo):
                if not alg.startswith("opt:"):
                    pairs.append((nn, ppn, kl, op, alg))
    return pairs


@pytest.mark.parametrize("tokens", _PLAN_TOKENS)
@pytest.mark.parametrize(
    "mesh_op_alg", _plan_cell_pairs(),
    ids=lambda t: f"{t[0]}x{t[1]}-{t[3]}-{t[4]}",
)
def test_color_recipe_replay_on_plan_meshes(mesh_op_alg, tokens):
    """Every family the selector races on the plan cell's meshes: the
    ``optimize="color"`` schedule served from a recorded recipe equals
    ``ColorRounds`` run directly on that payload's base, passes the oracle
    and keeps the payload — the production path never re-checks a
    replay."""
    nn, ppn, kl, op, alg = mesh_op_alg
    topo = Topology(nn, ppn, kl)
    k = min(kl, ppn)
    c = max(1, tokens * 6 * 5120 // topo.p)
    # record the structure's recipe at another payload, so c replays it
    IR.compiled_schedule(op, alg, topo, k, c + 1, optimize="color")
    before = IR.schedule_cache_info()
    replay = IR.compiled_schedule(op, alg, topo, k, c, optimize="color")
    after = IR.schedule_cache_info()
    # a replay now, or one served from the cache since it was replayed
    assert (after["recipe_hits"], after["hits"]) in (
        (before["recipe_hits"] + 1, before["hits"]),
        (before["recipe_hits"], before["hits"] + 1),
    )
    base = IR.compiled_schedule(op, alg, topo, k, c)
    direct = ColorRounds(limit=None, procs_per_node=ppn).apply(base)
    for f in ("src", "dst", "elems", "round_ptr", "blk_ptr", "blk_ids"):
        assert np.array_equal(getattr(replay, f), getattr(direct, f)), f
    assert validate_schedule(replay).ok
    assert replay.total_elems() == base.total_elems()
