"""ISSUE 7 observability: the flight recorder (nested spans, ring buffer,
exporters), the metrics registry, failure forensics, selector decision
records, the schedule-cache counter fixes, and the disabled-tracer
overhead budget.  Everything here is jax-free (CI fast job)."""

import dataclasses
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import schedule_ir as IR
from repro.core.passes import (
    ColorRounds,
    repair_schedule,
    run_passes,
)
from repro.core.faults import FaultSpec
from repro.core.selector import last_decision
from repro.core.topology import Topology
from repro.core.validate import check_schedule
from repro.obs import forensics, metrics
from repro.obs.trace import TRACER, Tracer, json_default


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with the process-wide tracer disabled
    and empty — the suite must not leak tracing into other test files
    (the disabled fast path is the production default)."""
    TRACER.disable()
    TRACER.clear()
    yield
    TRACER.disable()
    TRACER.clear()
    forensics.disable()


# ---------------------------------------------------------------------------
# tracer core


def test_span_nesting_parent_depth():
    t = Tracer(capacity=64)
    t.enable()
    a = t.start("outer", op="x")
    b = t.start("inner")
    t.finish(b, ok=True)
    t.finish(a)
    recs = t.records()
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert outer["parent"] is None and outer["depth"] == 0
    assert inner["parent"] == outer["sid"] and inner["depth"] == 1
    assert inner["args"] == {"ok": True}
    assert outer["args"] == {"op": "x"}
    # child interval sits inside the parent interval
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_span_context_manager_and_events():
    t = Tracer(capacity=64)
    t.enable()
    with t.span("cm", tag=1):
        t.event("ping", n=2)
    recs = t.records()
    assert [(r["name"], r["ph"]) for r in recs] == [("ping", "i"), ("cm", "X")]
    ping, cm = recs
    assert ping["parent"] == cm["sid"] and ping["depth"] == 1


def test_ring_wraparound_keeps_most_recent():
    t = Tracer(capacity=8)
    t.enable()
    for i in range(20):
        t.event(f"e{i}")
    assert t.total == 20
    assert t.dropped == 12
    recs = t.records()
    assert [r["name"] for r in recs] == [f"e{i}" for i in range(12, 20)]


def test_records_since_mark_and_wraparound():
    t = Tracer(capacity=8)
    t.enable()
    for i in range(5):
        t.event(f"a{i}")
    mark = t.mark()
    for i in range(3):
        t.event(f"b{i}")
    assert [r["name"] for r in t.records_since(mark)] == ["b0", "b1", "b2"]
    # after the ring laps the mark, only surviving records come back
    for i in range(10):
        t.event(f"c{i}")
    names = [r["name"] for r in t.records_since(mark)]
    assert names == [f"c{i}" for i in range(2, 10)]


def test_disabled_tracer_is_falsy_and_noop():
    t = Tracer(capacity=8)
    assert not t
    t.event("never")  # internally guarded
    with t.span("nope") as sp:
        assert sp is None
    assert t.records() == [] and t.total == 0
    t.enable()
    assert t
    t.disable()
    t.event("still-off")
    assert t.records() == []


def test_out_of_order_finish_pops_through():
    t = Tracer(capacity=16)
    t.enable()
    a = t.start("a")
    b = t.start("b")
    t.finish(a)  # finishes a, popping the forgotten b
    c = t.start("c")
    t.finish(c)
    c_rec = [r for r in t.records() if r["name"] == "c"][0]
    assert c_rec["parent"] is None and c_rec["depth"] == 0
    assert b.sid != c_rec["sid"]


def test_enable_resize_clears_and_json_default():
    t = Tracer(capacity=4)
    t.enable()
    t.event("x")
    t.enable(capacity=16)
    assert t.total == 0 and t.capacity == 16
    assert json_default(np.int64(3)) == 3
    assert json_default(np.arange(2)) == [0, 1]
    assert isinstance(json_default(object()), str)


def test_exports_roundtrip(tmp_path):
    t = Tracer(capacity=64)
    t.enable()
    with t.span("outer", arr=np.arange(2)):
        t.event("mid", v=np.float64(1.5))
    jsonl = tmp_path / "t.trace.jsonl"
    chrome = tmp_path / "t.trace.json"
    assert t.export_jsonl(str(jsonl)) == 2
    lines = [json.loads(line) for line in jsonl.read_text().splitlines()]
    assert [r["name"] for r in lines] == ["mid", "outer"]
    assert lines[1]["args"]["arr"] == [0, 1]
    assert t.export_chrome(str(chrome)) == 2
    doc = json.loads(chrome.read_text())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"X", "i"}
    for e in evs:
        assert isinstance(e["ts"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        else:
            assert e["s"] == "t"


# ---------------------------------------------------------------------------
# metrics registry


@pytest.fixture()
def fresh_metrics():
    """Isolated registry window: drop everything, restore nothing (the
    registry is get-or-create; other tests re-create what they need)."""
    metrics.clear()
    yield
    metrics.clear()


def test_counter_gauge_basics(fresh_metrics):
    c = metrics.counter("t.c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert metrics.counter("t.c") is c  # get-or-create
    g = metrics.gauge("t.g")
    g.set(2.5)
    g.add(-1.0)
    assert g.value == 1.5
    with pytest.raises(TypeError):
        metrics.gauge("t.c")


def test_histogram_buckets_and_observe_many(fresh_metrics):
    h = metrics.histogram("t.h", edges=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 50.0, 500.0):
        h.observe(v)
    # bisect_right: exact edge hits fall in the bucket ABOVE the edge
    # (bucket i counts edges[i-1] <= v < edges[i])
    assert h.counts == [1, 2, 1, 1]
    h2 = metrics.histogram("t.h2", edges=(1.0, 10.0, 100.0))
    h2.observe_many([0.5, 1.0, 5.0, 50.0, 500.0])
    assert h2.counts == h.counts
    assert h2.count == 5 and h2.sum == pytest.approx(556.5)
    assert h2.mean == pytest.approx(556.5 / 5)
    with pytest.raises(ValueError):
        metrics.histogram("t.bad", edges=(2.0, 1.0))


def test_snapshot_render_reset(fresh_metrics):
    metrics.counter("s.c").inc(3)
    metrics.histogram("s.h", edges=(1.0,)).observe(0.5)
    snap = metrics.snapshot()
    assert snap["s.c"] == {"type": "counter", "value": 3}
    assert snap["s.h"]["counts"] == [1, 0]
    json.dumps(snap)  # machine snapshot must be serializable as-is
    text = metrics.render_text()
    assert "s.c  3" in text and "s.h" in text
    metrics.reset()
    assert metrics.counter("s.c").value == 0
    assert metrics.histogram("s.h").counts == [0, 0]
    snap2 = metrics.snapshot()
    assert set(snap2) == {"s.c", "s.h"}  # reset keeps registry entries


def test_metrics_concurrent_increments(fresh_metrics):
    c = metrics.counter("race.c")
    h = metrics.histogram("race.h", edges=(0.5,))

    def worker():
        for _ in range(1000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert c.value == 8000
    assert h.count == 8000 and h.counts == [8000, 0]


# ---------------------------------------------------------------------------
# schedule cache counters (satellite: reset + race fix)


def test_schedule_cache_reset_keeps_entries():
    IR.schedule_cache_clear()
    topo = Topology(2, 2, 1)
    IR.compiled_schedule("alltoall", "klane", topo, 1, 3)
    IR.compiled_schedule("alltoall", "klane", topo, 1, 3)
    info = IR.schedule_cache_info()
    assert info["misses"] == 1 and info["hits"] == 1 and info["size"] == 1
    IR.schedule_cache_reset()
    info = IR.schedule_cache_info()
    assert info["hits"] == 0 and info["misses"] == 0
    assert info["recipe_hits"] == 0 and info["recipe_misses"] == 0
    assert info["size"] == 1  # entries survive the counter reset
    IR.compiled_schedule("alltoall", "klane", topo, 1, 3)
    assert IR.schedule_cache_info()["hits"] == 1  # still warm


def test_schedule_cache_counters_exact_under_threads():
    """Regression (ISSUE 7 satellite): hit/miss and recipe counters are
    read-modify-write on module globals; before the fix concurrent
    readers lost increments.  hits + misses must equal the exact call
    count, and the recipe counters must match the optimize calls."""
    IR.schedule_cache_clear()
    topo = Topology(3, 4, 2)
    calls_per_thread, n_threads = 25, 8
    errs = []

    def worker(seed):
        try:
            for i in range(calls_per_thread):
                c = 2 + (seed + i) % 3  # 3 distinct keys
                IR.compiled_schedule("alltoall", "klane", topo, 2, c,
                                     optimize="color")
        except Exception as e:  # pragma: no cover - diagnostic
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,))
               for s in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    info = IR.schedule_cache_info()
    total = n_threads * calls_per_thread
    # 3 optimized keys + their 3 unoptimized base keys; concurrent threads
    # may race to build the same cold key (both count a lookup miss, one
    # insertion wins), so misses >= 6 — but no increment may be LOST:
    # every outer call is one lookup, and every optimized-key miss adds
    # one nested base lookup and one recipe lookup, so
    #   hits + misses == total + (recipe_hits + recipe_misses)
    # holds exactly iff no read-modify-write update was dropped.
    assert info["size"] == 6
    assert info["recipes"] == 1  # recipe key drops the payload
    assert info["misses"] >= 6 and info["recipe_misses"] >= 1
    assert info["hits"] + info["misses"] == total + (
        info["recipe_hits"] + info["recipe_misses"]
    )
    # warm-cache phase: counters zeroed, entries kept -> every concurrent
    # call is a hit and the hit counter must land exactly on the total
    IR.schedule_cache_reset()
    threads = [threading.Thread(target=worker, args=(s,))
               for s in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    info = IR.schedule_cache_info()
    assert info["hits"] == total and info["misses"] == 0


# ---------------------------------------------------------------------------
# pipeline instrumentation


def _small_schedule(c=5):
    topo = Topology(3, 4, 2)
    return IR.compiled_schedule("alltoall", "klane", topo, 2, c), topo


def test_compile_span_nesting_and_cache_events():
    IR.schedule_cache_clear()
    TRACER.enable()
    topo = Topology(3, 4, 2)
    mark = TRACER.mark()
    IR.compiled_schedule("alltoall", "klane", topo, 2, 7, optimize="color")
    recs = TRACER.records_since(mark)
    by_sid = {r["sid"]: r for r in recs if r["ph"] == "X"}
    compiles = [r for r in by_sid.values() if r["name"] == "compile"]
    assert len(compiles) == 2  # optimized entry + its unoptimized base
    outer = [r for r in compiles if r["parent"] is None]
    assert len(outer) == 1 and outer[0]["args"]["path"] == "recipe"
    # a pass span nested under optimize, both under the outer compile;
    # the oracle checks the recorded rewrite under the same compile
    passes = [r for r in by_sid.values() if r["name"].startswith("pass:")]
    assert len(passes) == 1
    opt = by_sid[passes[0]["parent"]]
    assert opt["name"] == "optimize" and opt["parent"] == outer[0]["sid"]
    oracles = [r for r in by_sid.values() if r["name"] == "oracle"]
    assert [o["parent"] for o in oracles] == [outer[0]["sid"]]
    # a cache hit emits the instant event, no compile span
    mark = TRACER.mark()
    IR.compiled_schedule("alltoall", "klane", topo, 2, 7, optimize="color")
    hit_recs = TRACER.records_since(mark)
    assert [r["name"] for r in hit_recs] == ["cache.hit"]


def test_pass_spans_match_pass_records():
    cs, topo = _small_schedule()
    TRACER.enable()
    mark = TRACER.mark()
    _, records = run_passes(
        cs, [ColorRounds(limit=None, procs_per_node=topo.procs_per_node)]
    )
    recs = TRACER.records_since(mark)
    pass_spans = [r for r in recs if r["ph"] == "X"
                  and r["name"].startswith("pass:")]
    assert len(pass_spans) == len(records)
    for sp, pr in zip(pass_spans, records):
        assert sp["name"] == f"pass:{pr.name}"
        assert sp["args"]["applied"] == pr.applied
        assert sp["args"]["rounds_after"] == pr.rounds_after
    opt = [r for r in recs if r["ph"] == "X" and r["name"] == "optimize"]
    assert len(opt) == 1 and opt[0]["args"]["outcome"] == "ok"


def test_repair_spans_and_counters():
    cs, topo = _small_schedule()
    metrics.clear()
    TRACER.enable()
    mark = TRACER.mark()
    repaired, _ = repair_schedule(
        cs, FaultSpec(dead_ranks=(topo.rank_of(1, 1),)), topo=topo
    )
    assert repaired is not cs
    recs = TRACER.records_since(mark)
    names = {r["name"] for r in recs if r["ph"] == "X"}
    assert "repair" in names and "repair.oracle" in names
    assert "repair.relay" in names  # a dead port forces relaying
    rep = [r for r in recs if r["name"] == "repair"][0]
    assert rep["args"]["applied"] is True
    assert rep["args"]["outcome"] == "ok"
    assert metrics.counter("repair.applied").value == 1
    assert metrics.counter("repair.oracle_checks").value == 1
    assert metrics.gauge("repair.last_oracle_verify_s").value > 0


def test_span_closed_on_pipeline_exception():
    """An exception inside an instrumented region must not leave its span
    open (a leaked span would mis-parent everything after it)."""
    TRACER.enable()
    topo = Topology(3, 4, 2)
    with pytest.raises(KeyError):
        IR.compiled_schedule("alltoall", "nosuch", topo, 2, 5,
                             optimize="color")
    t = TRACER
    assert not t._stack(), "exception leaked an open span"
    err = [r for r in t.records() if r["ph"] == "X"
           and r["name"] == "compile"]
    assert err and err[-1]["args"]["path"] == "error"


# ---------------------------------------------------------------------------
# selector decision records


def test_select_explain_names_every_candidate():
    from repro.api import PlanRequest, explain, plan

    req = PlanRequest("alltoall", 869, num_nodes=3, procs_per_node=4,
                      k_lanes=2)
    dec = explain(req)
    assert dec.winner == plan(req).algorithm
    priced = [c for c in dec.candidates if c.status == "priced"]
    assert priced and all(c.est_us is not None for c in priced)
    assert {c.rung for c in dec.candidates} <= {"base", "opt"}
    assert dec.rung_fired == "raced" and dec.margin_us is not None
    assert last_decision().winner == dec.winner
    json.dumps(dec.as_dict())


def test_select_deadline_zero_skips_opt_rung():
    from repro.api import PlanRequest, explain

    dec = explain(PlanRequest("alltoall", 869, num_nodes=3,
                              procs_per_node=4, k_lanes=2,
                              faults=FaultSpec(dead_lanes=((1, 1),)),
                              deadline_s=0.0))
    opt = [c for c in dec.candidates if c.rung == "opt"]
    assert opt and all(c.status == "deadline-skipped" for c in opt)
    base_priced = [c for c in dec.candidates
                   if c.rung == "base" and c.status == "priced"]
    assert dec.winner in {c.algorithm for c in base_priced}


# ---------------------------------------------------------------------------
# forensics


def test_forensics_dump_and_unique_paths(tmp_path):
    TRACER.enable()
    TRACER.event("before-failure")
    metrics.counter("f.c").inc()
    p1 = forensics.dump("unit failure!", extra={"k": 1}, dir=str(tmp_path))
    p2 = forensics.dump("unit failure!", extra={"k": 2}, dir=str(tmp_path))
    assert os.path.basename(p1) == "unit_failure_.forensics.json"
    assert os.path.basename(p2) == "unit_failure_-2.forensics.json"
    doc = json.loads(open(p1).read())
    assert doc["reason"] == "unit failure!"
    assert doc["extra"] == {"k": 1}
    assert any(r["name"] == "before-failure" for r in doc["trace"]["records"])
    assert doc["metrics"]["f.c"]["value"] >= 1


def test_forensics_default_dir_is_artifacts(tmp_path, monkeypatch):
    # ISSUE 8 satellite: unarmed unconditional dumps land in the ignored
    # artifacts/ directory, not the repo root; REPRO_FORENSICS=dir still
    # redirects and =1 keeps the legacy cwd behavior
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("REPRO_FORENSICS", raising=False)
    p = forensics.dump("stray")
    assert os.path.dirname(p) == forensics.DEFAULT_DIR
    assert (tmp_path / "artifacts" / "stray.forensics.json").exists()
    assert not (tmp_path / "stray.forensics.json").exists()
    monkeypatch.setenv("REPRO_FORENSICS", str(tmp_path / "armed"))
    p2 = forensics.dump("stray")
    assert os.path.dirname(p2) == str(tmp_path / "armed")
    monkeypatch.setenv("REPRO_FORENSICS", "1")
    p3 = forensics.dump("stray")
    assert os.path.dirname(p3) == "."


def test_oracle_violation_auto_dump_armed_only(tmp_path):
    cs, _ = _small_schedule()
    bad_blk = cs.blk_ids.copy()
    src0 = cs.src[0]
    # round-0 alltoall senders only hold their own blocks: claiming a
    # foreign source row is a guaranteed causality violation
    bad_blk[cs.blk_ptr[0]] = ((src0 + 1) % cs.p) * cs.p
    bad = dataclasses.replace(cs, blk_ids=bad_blk, _stats={})
    # unarmed (the default): intentional corruption stays silent
    with pytest.raises(AssertionError):
        check_schedule(bad, raise_on_error=True)
    assert list(tmp_path.iterdir()) == []
    forensics.enable(str(tmp_path))
    try:
        with pytest.raises(AssertionError):
            check_schedule(bad, raise_on_error=True)
    finally:
        forensics.disable()
    dumps = list(tmp_path.glob("*.forensics.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "oracle_violation"
    assert doc["extra"]["ok"] is False


# ---------------------------------------------------------------------------
# deltas breakdown (satellite b)


def test_pass_walls_breakdown_traced_and_fallback():
    from benchmarks.paper_tables import _pass_walls

    cs, topo = _small_schedule()
    TRACER.enable()
    mark = TRACER.mark()
    run_passes(
        cs, [ColorRounds(limit=None, procs_per_node=topo.procs_per_node)]
    )
    traced = _pass_walls(mark)
    assert traced.startswith("color_rounds=")
    assert "," not in traced and "[" not in traced  # CSV-safe
    # untraced: no spans to read, an empty column
    TRACER.disable()
    assert _pass_walls(None) == ""


def test_render_optimizer_deltas_breakdown_column():
    from benchmarks.paper_tables import render_optimizer_deltas

    rows = [{
        "table": "OPT3", "impl": "opt3:klane_a2a", "c": 869,
        "rounds_before": 8, "rounds_after": 4, "base_us": 10.0,
        "sim_us": 5.0, "paper_us": 42.0, "opt_wall_s": 0.5,
        "pass_walls": "color_rounds=0.010;other_pass=0.002",
    }]
    lines = render_optimizer_deltas(rows)
    assert lines[0].endswith("speedup,paper_us,pass_walls")
    assert lines[1].endswith(
        "2.00x,42.0,color_rounds=0.010;other_pass=0.002"
    )
    # every line splits into the same number of comma cells
    assert len(lines[0].split(",")) == len(lines[1].split(","))


# ---------------------------------------------------------------------------
# disabled-tracer overhead (satellite c)


def test_disabled_tracer_overhead_under_2pct():
    """The ISSUE 7 overhead budget on a p=144 optimize run.

    Direct A/B wall-clock deltas at the 2% level are noise on shared CI
    runners, so the assertion is the analytic bound: (number of guard
    evaluations the run performs) x (measured per-guard cost) must be
    under 2% of the run's disabled-tracer wall.  The guard count is taken
    from a traced twin run (every record is >= one guard; scale by 4x for
    the sites that guard without recording), the per-guard cost from
    timing the literal disabled-path expression."""
    topo = Topology(12, 12, 2)  # p = 144
    IR.schedule_cache_clear()
    base = IR.compiled_schedule("alltoall", "klane", topo, 2, 5)

    def run_once():
        run_passes(base, [ColorRounds(limit=None, procs_per_node=12)])

    assert not TRACER
    run_once()  # warm caches
    t0 = time.perf_counter()
    run_once()
    disabled_wall = time.perf_counter() - t0

    TRACER.enable()
    mark = TRACER.mark()
    run_once()
    n_records = len(TRACER.records_since(mark))
    TRACER.disable()
    assert n_records > 0

    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        sp = TRACER.start("x") if TRACER else None
        if sp:
            TRACER.finish(sp)
    per_guard = (time.perf_counter() - t0) / n

    overhead = 4 * n_records * per_guard
    assert overhead < 0.02 * disabled_wall, (
        f"disabled-tracer overhead bound {overhead * 1e6:.1f}us is not "
        f"<2% of the {disabled_wall * 1e3:.1f}ms p=144 optimize wall "
        f"({n_records} records, {per_guard * 1e9:.0f}ns/guard)"
    )
