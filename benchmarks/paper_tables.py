"""One benchmark per paper table, reproduced on the calibrated Hydra model.

The paper's numbers are machine+library artifacts (36x32 dual-OmniPath,
three MPI libs); reproduction means the simulator recovers the *structure*:
per-(algorithm, k, c) times in the same regime, with the same orderings and
crossovers.  Each function emits one cell dict per table row

    {table, impl, k, c, sim_us, paper_us, wall_s}

where ``paper_us`` is the published Open MPI avg (when that cell exists in
the paper) and ``wall_s`` is the wall-clock cost of producing the cell
(schedule generation + simulation) — the perf trajectory tracked by
``benchmarks.run --json``.  ``csv_row`` renders the legacy CSV line.

``table_optimizer_deltas3`` adds the beyond-paper OPT3 cells: the
planner's optimizer pipeline (``compiled_schedule(..., optimize="color")``,
the rewrite behind every ``opt:`` candidate: recipe replay of the
structural coloring packer, oracle-checked) over the paper-scale (p=1152)
alltoall families (klane, fulllane, kported) and broadcast trees, each
cell with its unoptimized base for the optimized-vs-paper delta table
(``render_optimizer_deltas``) — priced on the selector's port model, as
the selector races the two.  ``tools/bench_gate.py`` gates the trajectory
in CI.  ``table_paper_opt_smoke`` (``--only paper-opt``) reruns one of the
alltoall cells as the CI fast-job scalability smoke.  OPT3 cells carry
``opt_wall_s`` — the optimizer's own wall-clock — so pipeline speed is on
the trajectory too (the gate stays on ``sim_us``).

All cells run on the compiled schedule IR (``repro.core.schedule_ir``):
the alltoall families are generated array-natively and every schedule is
cached process-wide, so the full paper sweep is seconds, not minutes.  The
simulated values are bit-identical to the legacy per-``Msg`` simulator
(pinned by ``tests/test_schedule_ir.py``).
"""

from __future__ import annotations

import time

from repro.core.schedule_ir import compiled_schedule
from repro.core.simulate import simulate
from repro.core.topology import Machine, Topology, hydra_machine
from repro.obs.trace import TRACER

M = hydra_machine()
TOPO = M.topo  # 36 x 32, k=2 physical

# Paper reference points (Open MPI 3.1.3, avg us) — table: {(impl,k,c): us}
PAPER = {
    # Table 2/3: alltoall on-node vs across nodes (p=32, c per proc)
    ("a2a_n1", 32, 31250): 4618.21,
    ("a2a_n32", 32, 31250): 448.03,
    ("a2a_n1", 32, 1875): 995.89,
    ("a2a_n32", 32, 1875): 72.78,
    # Tables 8-9: k-lane bcast
    ("klane_bcast", 1, 1_000_000): 19657.63,
    ("klane_bcast", 2, 1_000_000): 28057.86,
    ("klane_bcast", 6, 1_000_000): 26799.26,
    ("klane_bcast", 6, 10_000): 272.23,
    # Tables 10-11: k-ported bcast
    ("kported_bcast", 1, 1_000_000): 9206.83,
    ("kported_bcast", 2, 1_000_000): 8600.59,
    ("kported_bcast", 6, 1_000_000): 10819.07,
    ("kported_bcast", 6, 10_000): 136.73,
    # Table 12: full-lane bcast
    ("fulllane_bcast", 6, 1_000_000): 3309.16,
    ("fulllane_bcast", 6, 10_000): 82.44,
    # Tables 23-27: scatter (c per proc)
    ("kported_scatter", 1, 869): 453.82,
    ("kported_scatter", 6, 869): 388.39,
    ("klane_scatter", 1, 869): 458.39,
    ("klane_scatter", 6, 869): 460.32,
    ("fulllane_scatter", 6, 869): 1444.02,
    # Tables 38-41: alltoall p=1152 (c is the per-pair block, paper §4.4)
    ("kported_a2a", 1, 869): 11784.61,
    ("kported_a2a", 6, 869): 11187.27,
    ("kported_a2a", 6, 1): 1250.47,
    ("klane_a2a", 32, 1): 827.90,
    ("fulllane_a2a", 6, 1): 121.41,
    ("fulllane_a2a", 6, 869): 12233.77,
}

_BCAST_C = [100, 10_000, 1_000_000]
_SCATTER_C = [9, 87, 869]
_A2A_C = [1, 9, 87, 869]


def _cell(table, impl, k, c, op, alg, topo, gen_k, blk, machine=None):
    """Generate (cached) + simulate one table cell, timing the wall cost."""
    t0 = time.perf_counter()
    cs = compiled_schedule(op, alg, topo, gen_k, blk)
    us = simulate(cs, machine if machine is not None else M).time_us
    return {
        "table": table,
        "impl": impl,
        "k": k,
        "c": c,
        "sim_us": us,
        "paper_us": PAPER.get((impl, k, c), ""),
        "wall_s": time.perf_counter() - t0,
    }


def csv_row(cell: dict) -> str:
    return (
        f"{cell['table']},{cell['impl']},{cell['k']},{cell['c']},"
        f"{cell['sim_us']:.2f},{cell['paper_us']}"
    )


def table_alltoall_node_vs_network():
    """Paper §4.1 (Tables 2-7): 32-proc alltoall on one node vs 32 nodes."""
    rows = []
    for c in [32, 1875, 31250]:
        blk = max(1, c // 32)
        on = Topology(1, 32, 2)
        off = Topology(32, 1, 1)
        rows.append(_cell("T2-7", "a2a_n1", 32, c, "alltoall", "kported",
                          on, 32, blk, Machine(topo=on, cost=M.cost)))
        rows.append(_cell("T2-7", "a2a_n32", 32, c, "alltoall", "kported",
                          off, 32, blk, Machine(topo=off, cost=M.cost)))
    return rows


def table_broadcast():
    """Paper §4.2 (Tables 8-22): k-lane vs k-ported vs full-lane broadcast."""
    rows = []
    for c in _BCAST_C:
        for k in (1, 2, 6):
            rows.append(_cell("T8-9", "klane_bcast", k, c,
                              "broadcast", "klane", TOPO, k, c))
            rows.append(_cell("T10-11", "kported_bcast", k, c,
                              "broadcast", "kported", TOPO, k, c))
        rows.append(_cell("T12", "fulllane_bcast", 6, c,
                          "broadcast", "fulllane", TOPO, 6, c))
    return rows


def table_scatter():
    """Paper §4.3 (Tables 23-37)."""
    rows = []
    for c in _SCATTER_C:
        for k in (1, 2, 6):
            rows.append(_cell("T23-24", "klane_scatter", k, c,
                              "scatter", "klane", TOPO, k, c))
            rows.append(_cell("T25-26", "kported_scatter", k, c,
                              "scatter", "kported", TOPO, k, c))
        rows.append(_cell("T27", "fulllane_scatter", 6, c,
                          "scatter", "fulllane", TOPO, 6, c))
    return rows


def table_alltoall():
    """Paper §4.4 (Tables 38-49).  ``c`` is the per-pair block size, exactly
    as in the paper's tables (each process contributes c elements to every
    other process; at c=869 that is ~4 MB leaving each process, matching the
    paper's ~11-12 ms Open MPI cells)."""
    rows = []
    for c in _A2A_C:
        for k in (1, 6):
            rows.append(_cell("T39-40", "kported_a2a", k, c,
                              "alltoall", "kported", TOPO, k, c))
        rows.append(_cell("T38", "klane_a2a", 32, c,
                          "alltoall", "klane", TOPO, 32, c))
        rows.append(_cell("T41", "fulllane_a2a", 6, c,
                          "alltoall", "fulllane", TOPO, 6, c))
        rows.append(_cell("T41b", "bruck_a2a", 6, c,
                          "alltoall", "bruck", TOPO, 6, c))
    return rows


def _pass_walls(mark) -> str:
    """Per-pass wall-time breakdown for the rendered delta table (ISSUE 7
    satellite), from the flight recorder: the ``pass:{name}`` spans
    emitted since ``mark`` (empty when tracing is off, or when the cell
    replayed a recorded recipe and ran no pass).  Pass names are truncated
    at the first ``[`` (the parameter brackets carry commas) and pairs are
    ``;``-joined, so the breakdown stays one CSV-safe column in the
    comma-separated delta lines."""
    walls: dict[str, float] = {}
    if TRACER and mark is not None:
        for rec in TRACER.records_since(mark):
            if rec.get("ph") == "X" and rec["name"].startswith("pass:"):
                name = rec["name"][len("pass:"):].split("[", 1)[0]
                walls[name] = walls.get(name, 0.0) + rec.get("dur", 0) / 1e6
    return ";".join(f"{n}={w:.3f}" for n, w in walls.items())


#: (impl, k, c) -> simulated time of the optimized schedule, recorded by
#: the OPT3 table as it runs; ``table_lower_bounds`` (ordered after
#: it in ``ALL_TABLES``) turns each entry into an LB certificate cell.
#: A dict so re-running a table in-process overwrites instead of
#: duplicating.
_LB_PENDING: dict[tuple, dict] = {}


def _note_lb(impl, op, gen_k, c, opt_us):
    """Record one optimized alltoall cell for the LB certificate table."""
    if op != "alltoall":
        return
    _LB_PENDING[(impl, gen_k, c)] = {"op": op, "opt_us": opt_us}


def table_lower_bounds():
    """ISSUE 9: lower-bound certificates for every paper-scale (p=1152)
    optimized alltoall schedule — the heuristic-vs-optimal gap column the
    ROADMAP's "certify the packer" item asks for, without needing a SAT
    solver.

    The OPT3 table notes its alltoall cells as it runs; this table (ordered after them) prices the analytic bound
    (:func:`repro.core.analyze.lower_bound` — the ``ceil(log_{k+1} p)``
    round bound and the per-proc/per-node bandwidth bounds, each valid
    for *any* correct schedule under either port model) and emits one
    ``LB`` cell per optimized schedule with ``sim_us = gap_vs_lb``: the
    optimized simulated time divided by the bound, a certified ``>= 1``
    ratio the trajectory gate holds like any other cell.  ``lb_us`` /
    ``opt_us`` / ``rounds_lb`` ride along for the offline diff."""
    from repro.core.analyze import lower_bound

    rows = []
    for (impl, gen_k, c), note in sorted(_LB_PENDING.items()):
        t0 = time.perf_counter()
        lb = lower_bound(note["op"], M, gen_k, c)
        gap = note["opt_us"] / lb["time_us"] if lb["time_us"] > 0 else None
        rows.append({
            "table": "LB",
            "impl": f"lb:{impl}",
            "k": gen_k,
            "c": c,
            "sim_us": gap,
            "paper_us": "",
            "wall_s": time.perf_counter() - t0,
            "lb_us": lb["time_us"],
            "opt_us": note["opt_us"],
            "rounds_lb": lb["rounds_lb"],
            "gap_vs_lb": gap,
        })
    return rows


#: OPT3 cases (ISSUE 5): the paper-scale (p=1152) alltoall families —
#: klane, fulllane and kported — plus the broadcast trees.  Shared with the
#: ``--only paper-opt`` CI smoke, which runs exactly one of these cells.
OPT3_CASES = [
    # (impl, op, alg, gen_k, payloads)
    ("opt3:klane_a2a", "alltoall", "klane", 32, [1, 869]),
    ("opt3:fulllane_a2a", "alltoall", "fulllane", 6, [1, 869]),
    ("opt3:kported_a2a", "alltoall", "kported", 6, [1, 869]),
    ("opt3:kported_bcast", "broadcast", "kported", 2, [10_000]),
    ("opt3:kported_bcast", "broadcast", "kported", 6, [10_000, 1_000_000]),
    ("opt3:klane_bcast", "broadcast", "klane", 2, [10_000, 1_000_000]),
    ("opt3:fulllane_bcast", "broadcast", "fulllane", 6, [1_000_000]),
]


def _opt3_cell(impl, op, alg, gen_k, c, table="OPT3"):
    """One OPT3 cell: the planner's ``"color"`` pipeline against its
    unoptimized base, both priced by ``simulate`` on the paper machine in
    the selector's (1-ported) model — the two candidates the selector
    races for ``opt:{alg}`` and ``{alg}``.  ``opt_wall_s`` records the
    optimizer's own wall-clock (the optimized ``compiled_schedule`` call
    only, the base already cached: a coloring and its oracle check at the
    first payload of a structure, a recipe replay at the others)."""
    t0 = time.perf_counter()
    base = compiled_schedule(op, alg, TOPO, gen_k, c)
    mark = TRACER.mark() if TRACER else None
    t_opt = time.perf_counter()
    opt = compiled_schedule(op, alg, TOPO, gen_k, c, optimize="color")
    opt_wall = time.perf_counter() - t_opt
    base_us = simulate(base, M).time_us
    opt_us = simulate(opt, M).time_us
    if table == "OPT3":  # the smoke rerun must not retitle a blessed LB key
        _note_lb(impl, op, gen_k, c, opt_us)
    return {
        "table": table,
        "impl": impl,
        "k": gen_k,
        "c": c,
        "sim_us": opt_us,
        "paper_us": PAPER.get((impl.split(":", 1)[-1], gen_k, c), ""),
        "wall_s": time.perf_counter() - t0,
        "opt_wall_s": opt_wall,
        "pass_walls": _pass_walls(mark),
        "base_us": base_us,
        "rounds_before": base.num_rounds,
        "rounds_after": opt.num_rounds,
    }


def table_optimizer_deltas3():
    """The planner's optimizer pipeline at paper scale.  Each cell runs
    ``compiled_schedule(..., optimize="color")`` — the structural coloring
    packer (:class:`repro.core.passes.ColorRounds`) recorded once per
    structure and replayed at the other payload, oracle-checked — and
    prices it against the unoptimized base.

    Rows (``OPT3_CASES``): the paper-scale alltoall families (klane,
    fulllane, kported at p=1152) and the broadcast trees.  A cell where
    the coloring loses to its base (``sim_us > base_us``) is one where the
    selector's race picks the base."""
    return [
        _opt3_cell(impl, op, alg, gen_k, c)
        for impl, op, alg, gen_k, payloads in OPT3_CASES
        for c in payloads
    ]


def table_paper_opt_smoke():
    """ISSUE 5 CI satellite: a single paper-scale (p=1152) alltoall OPT
    cell (``--only paper-opt``) so the optimizer's scalability cannot
    silently regress in the fast job.  Uses its own table name (never in
    the blessed baseline, so the gate treats it as informational), and the
    fulllane family — dependency-carrying at ~2.6M block hops, the
    heaviest oracle + packer combination."""
    return [
        _opt3_cell(
            "opt3s:fulllane_a2a", "alltoall", "fulllane", 6, 1,
            table="OPT3-SMOKE",
        )
    ]


#: DEG scenarios (ISSUE 6): graceful degradation at paper scale.  Each
#: entry is (impl, op, alg, gen_k, payloads, FaultSpec factory).  The
#: headline cell is the paper's own machine losing one of its two OmniPath
#: rails under the k=2 lane alltoall — the repaired schedule on the
#: degraded machine vs the k=1 schedule a native library would fall back
#: to generating from scratch on the surviving rail.
DEG_CASES = [
    ("deg:klane_a2a", "alltoall", "klane", 2, [1, 869], "dead_rail"),
    ("deg:fulllane_a2a", "alltoall", "fulllane", 2, [1, 869], "dead_rail"),
    ("deg:klane_a2a_relay", "alltoall", "klane", 2, [1, 869], "dead_port"),
]


def table_degraded():
    """ISSUE 6: fault-repaired schedules priced on the degraded machine.

    ``sim_us`` is the repaired schedule simulated under the fault (the gate
    tracks the degraded trajectory); ``healthy_us`` is the same family on
    the intact machine, and for the dead-rail rows ``native_us`` is the
    natively regenerated k=1 schedule on a healthy one-rail machine — the
    repair-vs-regenerate comparison the graceful-degradation story rests
    on.  The dead-port rows exercise the relay rewrite (inter traffic of
    one NIC-dead rank staged through a surviving local rank)."""
    import dataclasses

    from repro.core.faults import FaultSpec, apply_faults

    scenarios = {
        "dead_rail": FaultSpec(dead_rails=1),
        "dead_port": FaultSpec(dead_ranks=(TOPO.rank_of(1, 1),)),
    }
    rows = []
    for impl, op, alg, gen_k, payloads, sname in DEG_CASES:
        spec = scenarios[sname]
        degraded = apply_faults(M, spec)
        for c in payloads:
            t0 = time.perf_counter()
            healthy = compiled_schedule(op, alg, TOPO, gen_k, c)
            healthy_us = simulate(healthy, M).time_us
            rep = compiled_schedule(op, alg, TOPO, gen_k, c, faults=spec)
            deg_us = simulate(rep, degraded).time_us
            row = {
                "table": "DEG",
                "impl": impl,
                "k": gen_k,
                "c": c,
                "sim_us": deg_us,
                "paper_us": "",
                "wall_s": time.perf_counter() - t0,
                "healthy_us": healthy_us,
                "scenario": sname,
                "fingerprint": spec.fingerprint(),
            }
            if sname == "dead_rail":
                k1_topo = dataclasses.replace(TOPO, k_lanes=1)
                native = compiled_schedule(op, alg, k1_topo, 1, c)
                row["native_us"] = simulate(
                    native, Machine(topo=k1_topo, cost=M.cost)
                ).time_us
            rows.append(row)
    return rows


def render_optimizer_deltas(rows) -> list[str]:
    """Human-readable optimized-vs-paper delta lines for the OPT3 cells
    (plus the CI paper-opt smoke when present).  ``pass_walls`` is
    the per-pass wall-time breakdown (ISSUE 7 satellite, flight-recorder
    sourced under ``--deltas``; ``name=secs`` ``;``-joined, last column so
    the lines stay naively comma-splittable) — it replaces the rendered
    ``opt_wall_s`` aggregate, which stays on the JSON cells for the CI
    gate's trajectory."""
    out = [
        "# optimizer: table,impl,c,rounds,opt_rounds,base_us,opt_us,"
        "speedup,paper_us,pass_walls"
    ]
    for r in rows:
        if r.get("table") not in ("OPT3", "OPT3-SMOKE"):
            continue
        speedup = r["base_us"] / r["sim_us"] if r["sim_us"] else float("inf")
        out.append(
            f"# optimizer: {r['table']},{r['impl']},{r['c']},{r['rounds_before']},"
            f"{r['rounds_after']},{r['base_us']:.2f},{r['sim_us']:.2f},"
            f"{speedup:.2f}x,{r['paper_us']},{r.get('pass_walls', '')}"
        )
    return out


def table_svc():
    """Schedule-as-a-service cells (ISSUE 8): the cold→persist→restart→
    warm-serve load test from :mod:`benchmarks.load`, emitting the SVC
    (deterministic service quality) and SVC-WALL (wall-clock) cells.
    Runs LAST: it clears the process-wide caches, which would otherwise
    cold-start the tables above mid-sweep."""
    from benchmarks.load import run_load

    cells, report = run_load()
    if TRACER:
        TRACER.event("bench.svc", hit_rate_pct=report["hit_rate_pct"],
                     store_recompiles=report["store_recompiles"],
                     batch_vs_loop_pct=report["batch_vs_loop_pct"])
    return cells


def table_res():
    """Resilient-serving cells (ISSUE 10): the chaos phase-2 drills from
    :mod:`tools.chaos` — crash injection, flaky-filesystem IO, and
    fault-event replanning — emitting RES (deterministic counts: torn/
    duplicate artifacts, recomputes, quarantines, replans, breaker trips)
    and RES-WALL (replan latency p99) cells.  Any drill contract breach
    fails the sweep outright — a regression here is a correctness bug,
    not a slow cell.  Runs after :func:`table_svc`: the drills clear the
    process caches too."""
    from tools.chaos import run_resilience_chaos

    t0 = time.perf_counter()
    rep = run_resilience_chaos(seed=0)
    wall = time.perf_counter() - t0
    if not rep["ok"]:
        raise RuntimeError(f"resilience drill contract breach: {rep}")
    crash, flaky, replan = rep["crash"], rep["flaky_io"], rep["replan"]
    if TRACER:
        TRACER.event("bench.res", recomputes=flaky["recomputes"],
                     quarantined=flaky["quarantined"],
                     breaker_trips=replan["breaker_trips"],
                     replan_p99_s=replan["replan_p99_s"])

    def cell(table, impl, value, wall_s):
        return {"table": table, "impl": impl, "k": 0, "c": 0,
                "sim_us": value, "paper_us": "", "wall_s": wall_s}

    return [
        cell("RES", "crash_torn", crash["torn"], wall),
        cell("RES", "crash_duplicates", crash["duplicates"], 0.0),
        cell("RES", "io_user_failures", flaky["user_failures"], 0.0),
        cell("RES", "io_recomputes", flaky["recomputes"], 0.0),
        cell("RES", "io_quarantined", flaky["quarantined"], 0.0),
        cell("RES", "replan_count", replan["replan_count"], 0.0),
        cell("RES", "breaker_trips", replan["breaker_trips"], 0.0),
        cell("RES-WALL", "replan_p99_us",
             replan["replan_p99_s"] * 1e6, wall),
    ]


ALL_TABLES = [
    table_alltoall_node_vs_network,
    table_broadcast,
    table_scatter,
    table_alltoall,
    table_optimizer_deltas3,
    # after the optimizer table: prices the analytic bound for every
    # optimized alltoall cell it noted
    table_lower_bounds,
    table_degraded,
    # LAST two: both clear the process caches (see docstrings)
    table_svc,
    table_res,
]
