"""What remains of the scheduling-pass suite once its passes went: the
block-dependency export the coloring packer consumes, the selector's
3-probe piecewise fits, the bench-trajectory gate, and a dry parse of the
CI workflow."""

import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import schedule as S
from repro.core import schedule_ir as IR
from repro.core import selector
from repro.core.topology import Machine, Topology, hydra_machine
from repro.core.validate import block_dependencies

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# block-dependency DAG export (core.validate)
# ---------------------------------------------------------------------------


def test_block_dependencies_empty_for_direct_alltoall():
    """Direct alltoall only sends analytically-held blocks: no edges."""
    cs = IR.kported_alltoall_ir(8, 2, 3)
    dep_ptr, dep_ids = block_dependencies(cs)
    assert dep_ids.size == 0
    assert dep_ptr.shape == (cs.num_msgs + 1,) and dep_ptr[-1] == 0


def test_block_dependencies_chained_and_strictly_earlier():
    """Bruck forwards blocks phase over phase: edges exist and every
    provider sits in a strictly earlier round."""
    cs = IR.bruck_alltoall_ir(9, 2, 1)
    dep_ptr, dep_ids = block_dependencies(cs)
    assert dep_ids.size > 0
    rid = cs.round_ids()
    req_round = np.repeat(rid, np.diff(dep_ptr))
    assert np.all(rid[dep_ids] < req_round)
    # dep lists are unique and ascending per message (CSR canonical form)
    for i in range(cs.num_msgs):
        seg = dep_ids[dep_ptr[i]:dep_ptr[i + 1]]
        assert np.all(np.diff(seg) > 0)


def test_block_dependencies_requires_blocks():
    cs = IR.compile_schedule(S.kported_broadcast(9, 2, 5))  # blockless
    with pytest.raises(ValueError, match="block"):
        block_dependencies(cs)


# ---------------------------------------------------------------------------
# selector: 3-probe piecewise fits
# ---------------------------------------------------------------------------


def test_piecewise_fit_exact_at_three_probes():
    mesh = dict(num_nodes=4, procs_per_node=8, k_lanes=2)
    c_lo, c_hi = 1 << 10, 1 << 20
    for alg in ("fulllane", "opt:klane"):
        fit = selector.piecewise_cost("alltoall", alg, c_lo, c_hi, **mesh)
        assert fit is not None, alg
        c_mid = fit[0]
        assert c_lo < c_mid < c_hi
        for c in (c_lo, c_mid, c_hi):
            direct = selector._sim_payload(
                "alltoall", alg, c, *mesh.values()
            )
            assert selector.piecewise_eval(fit, c) == pytest.approx(
                direct, rel=1e-9
            ), (alg, c)


def test_piecewise_eval_segment_routing():
    fit = (100, 1.0, 2.0, 51.0, 1.5)  # seg1 up to c=100, seg2 beyond
    assert selector.piecewise_eval(fit, 10) == pytest.approx(21.0)
    assert selector.piecewise_eval(fit, 100) == pytest.approx(201.0)
    assert selector.piecewise_eval(fit, 200) == pytest.approx(351.0)


def test_piecewise_degenerate_sweeps():
    mesh = dict(num_nodes=4, procs_per_node=8, k_lanes=2)
    flat = selector.piecewise_cost("alltoall", "fulllane", 64, 64, **mesh)
    assert flat is not None and flat[2] == 0.0 == flat[4]
    narrow = selector.piecewise_cost("alltoall", "fulllane", 64, 65, **mesh)
    assert narrow is not None  # collapses to a single affine segment
    assert narrow[1:3] == narrow[3:5]


def test_crossover_table_midpoint_now_exact():
    """The 3rd probe makes the geometric-middle cell exact too — the
    regime-flip protection the 2-probe fit could not give."""
    sizes = [1 << 6, 1 << 13, 1 << 20]
    mesh = dict(num_nodes=4, procs_per_node=16, k_lanes=4)
    table = selector.crossover_table("alltoall", sizes=sizes, **mesh)
    assert [s for s, _, _ in table] == sizes
    s_mid, best_mid, est_mid = table[1]
    direct = selector._sim_payload("alltoall", best_mid, s_mid, *mesh.values())
    assert est_mid == pytest.approx(direct, rel=1e-9)


def test_proxy_machine_preserves_lane_count():
    """ISSUE 4 satellite: the fast-simulation proxy used to clamp
    ``k_lanes`` to the shrunken intra-node dimension with no compensation,
    mispricing every k-lane family whenever k_lanes > 16.  The proxy now
    shrinks only down to the lane count (and not at all when the lanes
    need every processor)."""
    cost = hydra_machine().cost
    # k_lanes within the default cap: proxy shrinks to 16, k preserved
    m = Machine(topo=Topology(2, 256, 8), cost=cost)
    proxy, scale = selector._proxy_machine(m)
    assert proxy.topo.procs_per_node == 16 and proxy.topo.k_lanes == 8
    assert scale == 256 / 16
    # regression regime: k_lanes > 16 must survive the proxy untouched
    m = Machine(topo=Topology(2, 64, 32), cost=cost)
    proxy, scale = selector._proxy_machine(m)
    assert proxy.topo.k_lanes == 32  # was min(32, 16) == 16 before the fix
    assert proxy.topo.procs_per_node == 32
    assert scale == 64 / 32
    # full-lane mesh: no shrink is possible without repricing — refuse
    m = Machine(topo=Topology(4, 64, 64), cost=cost)
    proxy, scale = selector._proxy_machine(m)
    assert proxy is m and scale == 1.0


# ---------------------------------------------------------------------------
# bench gate + CI workflow (satellites)
# ---------------------------------------------------------------------------


def _gate(tmp_path, base_cells, fresh_cells, *extra):
    bp, fp = tmp_path / "base.json", tmp_path / "fresh.json"
    bp.write_text(json.dumps({"cells": base_cells}))
    fp.write_text(json.dumps({"cells": fresh_cells}))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_gate.py"), str(fp),
         "--baseline", str(bp), *extra],
        capture_output=True, text=True, cwd=REPO,
    )
    return proc


def _cell(impl, sim_us, table="T", k=2, c=1):
    return {"table": table, "impl": impl, "k": k, "c": c,
            "sim_us": sim_us, "wall_s": 0.0}


def test_bench_gate_passes_within_tolerance(tmp_path):
    base = [_cell("a", 100.0), _cell("b", 50.0)]
    fresh = [_cell("a", 103.0), _cell("b", 49.0), _cell("new", 1.0)]
    proc = _gate(tmp_path, base, fresh)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_bench_gate_fails_on_10pct_regression(tmp_path):
    """ISSUE 3 acceptance: an injected 10% sim_us regression must fail."""
    base = [_cell("a", 100.0)]
    fresh = [_cell("a", 110.0)]
    proc = _gate(tmp_path, base, fresh)
    assert proc.returncode == 1
    assert "FAIL" in proc.stdout and "+10.0%" in proc.stdout


def test_bench_gate_zero_baseline_cell_uses_abs_tol(tmp_path):
    """ISSUE 4 satellite: a zero (or near-zero) baseline sim_us cell must
    neither crash the gate nor fail on float jitter — the relative ratio is
    clamped and the --abs-tol floor governs; tightening --abs-tol re-arms
    the check."""
    base = [_cell("a", 0.0), _cell("b", 1e-6)]
    fresh = [_cell("a", 0.01), _cell("b", 0.02)]
    proc = _gate(tmp_path, base, fresh)  # default --abs-tol 0.05 us
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout
    proc = _gate(tmp_path, base, fresh, "--abs-tol", "0.001")
    assert proc.returncode == 1
    assert "FAIL" in proc.stdout


def test_bench_gate_abs_tol_does_not_mask_real_regressions(tmp_path):
    proc = _gate(tmp_path, [_cell("a", 100.0)], [_cell("a", 110.0)],
                 "--abs-tol", "0.05")
    assert proc.returncode == 1 and "+10.0%" in proc.stdout


def test_bench_gate_fails_on_disappeared_cell_and_zero_cells(tmp_path):
    proc = _gate(tmp_path, [_cell("a", 100.0)], [_cell("b", 1.0)])
    assert proc.returncode == 1 and "disappeared" in proc.stdout
    proc = _gate(tmp_path, [_cell("a", 100.0)], [])
    assert proc.returncode == 1 and "zero cells" in proc.stdout


def test_bench_gate_update_baseline(tmp_path):
    base = [_cell("a", 100.0)]
    fresh = [_cell("a", 200.0)]  # would fail the gate...
    proc = _gate(tmp_path, base, fresh, "--update-baseline")
    assert proc.returncode == 0  # ...but blessing is explicit and allowed
    blessed = json.loads((tmp_path / "base.json").read_text())
    assert blessed["cells"][0]["sim_us"] == 200.0
    # and the gate now passes against the blessed baseline
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_gate.py"),
         str(tmp_path / "fresh.json"), "--baseline", str(tmp_path / "base.json")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_ci_workflow_parses_and_runs_both_modes():
    """Dry-parse .github/workflows/ci.yml (the actionlint-unavailable
    fallback) and pin the ISSUE 3 contract: two jobs, check.sh in both,
    CHECK_FULL=1 on the second, trajectory artifact uploads."""
    yaml = pytest.importorskip("yaml")
    wf = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    jobs = wf["jobs"]
    assert set(jobs) == {"fast", "full"}
    # the `on:` trigger (YAML may parse the key as boolean True)
    trigger = wf.get("on", wf.get(True))
    assert "push" in trigger and "pull_request" in trigger
    fast_cmds = " ".join(
        step.get("run", "") for step in jobs["fast"]["steps"]
    )
    full_cmds = " ".join(
        step.get("run", "") for step in jobs["full"]["steps"]
    )
    assert "check.sh" in fast_cmds and "check.sh" in full_cmds
    full_env = {}
    for step in jobs["full"]["steps"]:
        full_env.update(step.get("env", {}))
    assert full_env.get("CHECK_FULL") == "1"
    assert any(
        "upload-artifact" in step.get("uses", "")
        for j in jobs.values()
        for step in j["steps"]
    )
    # pip caching on both jobs (satellite requirement)
    for j in jobs.values():
        assert any(
            step.get("with", {}).get("cache") == "pip"
            for step in j["steps"]
            if "setup-python" in step.get("uses", "")
        )
