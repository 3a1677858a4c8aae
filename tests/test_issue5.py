"""The structural budget chooser of the bitset conflict-coloring
packer, the fingerprinted/recipe'd optimized-schedule cache (hit/miss,
fingerprint invalidation, thread-safety smoke), the selector's adaptive
fourth probe, and the bench gate's report-everything-in-one-run fix."""

import json
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import passes as P
from repro.core import schedule_ir as IR
from repro.core import selector
from repro.core.passes import (
    ColorRounds,
    choose_color_budget,
    pipeline_fingerprint,
)
from repro.core.topology import Topology
from repro.core.validate import validate_schedule


# ---------------------------------------------------------------------------
# budget chooser
# ---------------------------------------------------------------------------


def test_choose_color_budget_structural_prefers_deepest_useful():
    topo = Topology(4, 6, 2)
    cs = IR.compiled_schedule("alltoall", "klane", topo, 2, 7)
    mult, limit = choose_color_budget(cs)
    assert (mult, limit) == (8, 16)  # every rung still shrinks the bound
    col = ColorRounds(limit=None, procs_per_node=6).apply(cs)
    assert col.num_rounds == -(-18 // 16) + -(-5 // 16)
    assert validate_schedule(col).ok


# ---------------------------------------------------------------------------
# optimized-schedule cache: fingerprints, recipes, thread safety
# ---------------------------------------------------------------------------


def test_opt_cache_hit_miss_across_modes():
    """The two optimize values (None, "color") key distinct entries, and
    repeats of each hit."""
    IR.schedule_cache_clear()
    topo = Topology(4, 6, 2)
    a = IR.compiled_schedule("alltoall", "klane", topo, 2, 7,
                             optimize="color")
    plain = IR.compiled_schedule("alltoall", "klane", topo, 2, 7)
    assert a is not plain
    before = IR.schedule_cache_info()
    assert IR.compiled_schedule(
        "alltoall", "klane", topo, 2, 7, optimize="color"
    ) is a
    assert IR.compiled_schedule("alltoall", "klane", topo, 2, 7) is plain
    after = IR.schedule_cache_info()
    assert after["hits"] == before["hits"] + 2
    assert after["misses"] == before["misses"]


def test_opt_cache_recipe_replays_across_payloads():
    """The tentpole payoff: a payload-independent opt pipeline runs once;
    other payload sizes replay the recorded recipe (one gather) and match
    the directly-optimized schedule exactly."""
    IR.schedule_cache_clear()
    topo = Topology(4, 6, 2)
    a = IR.compiled_schedule("alltoall", "klane", topo, 2, 7,
                             optimize="color")
    info1 = IR.schedule_cache_info()
    assert info1["recipe_misses"] == 1
    b = IR.compiled_schedule("alltoall", "klane", topo, 2, 869,
                             optimize="color")
    info2 = IR.schedule_cache_info()
    assert info2["recipe_hits"] == 1  # pipeline did NOT run again
    assert b.num_rounds == a.num_rounds
    # recipe replay == running the pipeline directly on the c=869 base
    base = IR.compiled_schedule("alltoall", "klane", topo, 2, 869)
    direct = ColorRounds(limit=None, procs_per_node=6).apply(base)
    for f in ("src", "dst", "elems", "round_ptr", "blk_ptr", "blk_ids"):
        assert np.array_equal(getattr(b, f), getattr(direct, f)), f
    assert validate_schedule(b).ok


def test_opt_cache_fingerprint_invalidation(monkeypatch):
    IR.schedule_cache_clear()
    topo = Topology(4, 6, 2)
    a = IR.compiled_schedule("alltoall", "klane", topo, 2, 7,
                             optimize="color")
    monkeypatch.setattr(P, "PASS_PIPELINE_VERSION", "test-bump")
    b = IR.compiled_schedule("alltoall", "klane", topo, 2, 7,
                             optimize="color")
    assert b is not a  # stale entry not served under the new fingerprint
    assert b.num_rounds == a.num_rounds
    assert IR.schedule_cache_info()["recipe_misses"] >= 2


def test_pipeline_fingerprint_covers_names_and_version(monkeypatch):
    p1 = [ColorRounds(limit=None, procs_per_node=4)]
    p2 = [ColorRounds(limit=2, procs_per_node=4)]
    assert pipeline_fingerprint(p1) != pipeline_fingerprint(p2)
    f1 = pipeline_fingerprint(p1)
    monkeypatch.setattr(P, "PASS_PIPELINE_VERSION", "test-bump")
    assert pipeline_fingerprint(p1) != f1


def test_opt_cache_thread_smoke():
    """Concurrent compiled_schedule(optimize=) calls: no corruption, every
    result oracle-valid and structurally identical per payload."""
    IR.schedule_cache_clear()
    topo = Topology(4, 6, 2)

    def work(c):
        return c, IR.compiled_schedule(
            "alltoall", "klane", topo, 2, c, optimize="color"
        )

    payloads = [3, 5, 7, 11] * 6
    with ThreadPoolExecutor(max_workers=8) as ex:
        results = list(ex.map(work, payloads))
    by_c = {}
    for c, cs in results:
        ref = by_c.setdefault(c, cs)
        assert cs.num_rounds == ref.num_rounds
        assert np.array_equal(cs.round_ptr, ref.round_ptr)
    assert validate_schedule(by_c[3]).ok
    info = IR.schedule_cache_info()
    assert info["recipes"] == 1  # one structure recipe serves every payload


# ---------------------------------------------------------------------------
# selector: adaptive fourth probe
# ---------------------------------------------------------------------------


def _knee_cost(c, knee=1 << 18, slope=0.01, floor=1000.0):
    return floor + max(0, c - knee) * slope


def test_adaptive_probe_fixes_mid_sweep_regime_flip(monkeypatch):
    """A family flat until a knee deep inside the second segment: the
    3-probe fit overprices the interior by thousands of us and misranks it
    against a constant-cost competitor; the adaptive fourth probe (capped
    at 4) lands near the knee and fixes the ranking."""
    def fake_sim(op, alg, payload, num_nodes, procs_per_node, k_lanes):
        if alg == "kneel":
            return _knee_cost(payload)
        return 4000.0  # constant competitor

    monkeypatch.setattr(selector, "_sim_payload", fake_sim)
    selector.piecewise_cost.cache_clear()
    try:
        mesh = (4, 8, 2)
        c_lo, c_hi = 1 << 4, 1 << 24
        fit = selector.piecewise_cost("alltoall", "kneel", c_lo, c_hi, *mesh)
        flat = selector.piecewise_cost("alltoall", "flat", c_lo, c_hi, *mesh)
        probe = 1 << 19  # interior, past the knee
        true_knee = _knee_cost(probe)
        est = selector.piecewise_eval(fit, probe)
        # the forced 3-probe fit (PR 3 behaviour) misranks here
        c_mid = 1 << 14
        b2 = (_knee_cost(c_hi) - _knee_cost(c_mid)) / (c_hi - c_mid)
        est3 = _knee_cost(c_mid) + b2 * (probe - c_mid)
        assert est3 > 4000.0 > true_knee  # 3 probes: wrong side of the flip
        assert abs(est - true_knee) < abs(est3 - true_knee)
        assert est < selector.piecewise_eval(flat, probe)  # ranks right
    finally:
        selector.piecewise_cost.cache_clear()


def test_adaptive_probe_not_spent_on_agreeing_slopes(monkeypatch):
    calls = []

    def fake_sim(op, alg, payload, num_nodes, procs_per_node, k_lanes):
        calls.append(payload)
        return 10.0 + 0.5 * payload  # one affine regime

    monkeypatch.setattr(selector, "_sim_payload", fake_sim)
    selector.piecewise_cost.cache_clear()
    try:
        fit = selector.piecewise_cost("alltoall", "aff", 16, 1 << 20, 4, 8, 2)
        assert len(calls) == 3  # no fourth probe
        assert selector.piecewise_eval(fit, 12345) == pytest.approx(
            10.0 + 0.5 * 12345
        )
    finally:
        selector.piecewise_cost.cache_clear()


# ---------------------------------------------------------------------------
# bench gate: every problem reported in one run
# ---------------------------------------------------------------------------

sys.path.insert(0, "tools")
import bench_gate  # noqa: E402


def _dump(path, cells):
    path.write_text(json.dumps({"cells": cells}))


def test_bench_gate_reports_all_failures_in_one_run(tmp_path, capsys):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    _dump(base, [
        {"table": "T", "impl": "a", "k": 1, "c": 1, "sim_us": 100.0},
        {"table": "T", "impl": "b", "k": 1, "c": 1, "sim_us": 100.0},
        {"table": "T", "impl": "gone", "k": 1, "c": 1, "sim_us": 100.0},
    ])
    _dump(fresh, [
        {"table": "T", "impl": "a", "k": 1, "c": 1, "sim_us": 200.0},
        {"table": "T", "impl": "b", "k": 1, "c": 1, "sim_us": 150.0},
        {"table": "T", "impl": "broken", "k": 1, "c": 1},  # no sim_us
    ])
    rc = bench_gate.main([str(fresh), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 1
    assert out.count("FAIL") >= 4  # 2 regressions + 1 disappeared + 1 bad
    assert "impl': 'a'" in out or "'a'" in out
    assert "'b'" in out and "'gone'" in out and "malformed" in out


def test_bench_gate_refuses_to_bless_malformed(tmp_path, capsys):
    fresh = tmp_path / "fresh.json"
    base = tmp_path / "base.json"
    _dump(fresh, [
        {"table": "T", "impl": "a", "k": 1, "c": 1, "sim_us": 1.0},
        {"table": "T", "impl": "bad", "k": 1},
    ])
    rc = bench_gate.main(
        [str(fresh), "--baseline", str(base), "--update-baseline"]
    )
    assert rc == 1
    assert not base.exists()
    assert "will not bless" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# paper-opt smoke wiring
# ---------------------------------------------------------------------------


def test_paper_opt_smoke_wiring():
    """The CI smoke is wired: run.py accepts --only paper-opt, and the
    smoke table targets a p=1152 alltoall with its own (ungated) table
    name shared with no blessed cell."""
    import argparse
    import benchmarks.run as br
    from benchmarks.paper_tables import OPT3_CASES, table_paper_opt_smoke

    assert any(
        op == "alltoall" and alg in ("fulllane", "kported")
        for _, op, alg, _, _ in OPT3_CASES
    )
    assert table_paper_opt_smoke.__doc__
    # argparse accepts the new selection without running it
    old_argv = sys.argv
    try:
        sys.argv = ["run.py", "--only", "paper-opt"]
        ap = argparse.ArgumentParser()
        ap.add_argument(
            "--only",
            choices=["paper", "paper-opt", "tpu", "hlo", "roofline"],
        )
        assert ap.parse_args(["--only", "paper-opt"]).only == "paper-opt"
    finally:
        sys.argv = old_argv
    assert "paper-opt" in open(br.__file__).read()
