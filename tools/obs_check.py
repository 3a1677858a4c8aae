#!/usr/bin/env python3
"""Observability smoke (ISSUE 7 CI satellite): prove the flight recorder
actually records the pipeline, end to end, in under a second.

Four contracts, each of which has silently rotted in other projects'
"optional tracing" layers and which ``tools/check.sh`` therefore gates as
a named ``obs-smoke`` step:

1. **Nesting** — a traced ``compiled_schedule(..., optimize="color")``
   cache miss produces a ``compile`` span that *contains* the ``optimize``
   span, which contains the ``pass:*`` span, and the ``oracle`` span that
   checks the recorded rewrite (parent/sid links and depths consistent).
2. **Exports** — the JSONL export round-trips line by line and the Chrome
   trace-event export is valid (one JSON document, every complete event
   carries integer ``ts``/``dur``, instants carry a scope) so Perfetto
   loads it.
3. **Decisions** — ``repro.api.explain(PlanRequest(...))`` returns a
   decision record in which every raced candidate is named with a finite
   price (status ``priced``) and the winner matches the cached-path
   ``plan()`` choice.
4. **Metrics** — the run left the expected counters behind
   (``schedule_cache.*``, ``schedule_recipes.*``) and the snapshot is
   JSON-serializable.

``--check-trace FILE`` additionally validates an existing trace JSONL
(e.g. the ``paper_opt.trace.jsonl`` the check script just exported):
parseable lines, monotone-consistent span records, and at least one
``pass:*`` span and one ``oracle`` span nested under a ``compile`` span.

Exit 0 on success, 1 with a named failure otherwise::

    PYTHONPATH=src python -m tools.obs_check [--check-trace FILE]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile


def _fail(msg: str) -> None:
    raise AssertionError(msg)


def _spans_by_sid(records: list[dict]) -> dict[int, dict]:
    return {r["sid"]: r for r in records if r.get("ph") == "X"}


def _has_ancestry(records: list[dict], chain: tuple[str, ...]) -> bool:
    """True when some span matches ``chain[-1]`` with ancestors matching
    the rest of ``chain`` (outermost first).  Prefix-matches ``pass:``."""

    def matches(rec, want):
        return rec["name"] == want or rec["name"].startswith(want)

    by_sid = _spans_by_sid(records)
    for rec in by_sid.values():
        if not matches(rec, chain[-1]):
            continue
        cur, ok = rec, True
        for want in reversed(chain[:-1]):
            cur = by_sid.get(cur.get("parent"))
            if cur is None or not matches(cur, want):
                ok = False
                break
        if ok:
            return True
    return False


def check_pipeline_trace() -> list[dict]:
    """Contract 1: run one traced cache-miss compile+optimize and verify
    the span tree.  Returns the recorded spans for the export checks."""
    from repro.core.schedule_ir import (
        compiled_schedule,
        schedule_cache_clear,
    )
    from repro.core.topology import Topology
    from repro.obs.trace import TRACER

    TRACER.enable()
    schedule_cache_clear()  # force the miss -> compile span path
    mark = TRACER.mark()
    topo = Topology(3, 4, 2)
    cs = compiled_schedule("alltoall", "klane", topo, 2, 5, optimize="color")
    assert cs.num_rounds > 0, "optimized schedule is empty"
    recs = TRACER.records_since(mark)
    spans = [r for r in recs if r.get("ph") == "X"]
    assert spans, "traced compile produced no spans"
    for chain in (
        ("compile", "optimize"),
        ("compile", "optimize", "pass:"),
        ("compile", "oracle"),
    ):
        if not _has_ancestry(recs, chain):
            _fail(f"missing span ancestry {' > '.join(chain)}")
    # the optimized build recursively compiles its unoptimized base, so
    # there are two compile spans: the outer one must be a root
    assert any(r["name"] == "compile" and r["depth"] == 0
               and r["parent"] is None for r in spans), (
        "no root compile span"
    )
    assert all(isinstance(r["ts"], int) and isinstance(r["dur"], int)
               for r in spans), "span ts/dur must be integer microseconds"
    return recs


def check_exports(tmpdir: str) -> None:
    """Contract 2: JSONL and Chrome exports round-trip and validate."""
    from repro.obs.trace import TRACER

    jsonl = os.path.join(tmpdir, "smoke.trace.jsonl")
    chrome = os.path.join(tmpdir, "smoke.trace.json")
    n_jsonl = TRACER.export_jsonl(jsonl)
    n_chrome = TRACER.export_chrome(chrome)
    with open(jsonl) as f:
        lines = [json.loads(line) for line in f]
    assert len(lines) == n_jsonl, "JSONL line count != reported count"
    validate_trace_jsonl(jsonl)
    with open(chrome) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert len(evs) == n_chrome, "Chrome event count != reported count"
    for ev in evs:
        assert ev["ph"] in ("X", "i"), f"unexpected ph {ev['ph']!r}"
        assert isinstance(ev["ts"], int), "Chrome ts must be integer us"
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], int) and ev["dur"] >= 0
        else:
            assert ev.get("s") in ("t", "p", "g"), "instant needs a scope"


def check_decision() -> None:
    """Contract 3: explain() names every raced candidate with a price
    and agrees with the cached fast path."""
    from repro.api import PlanRequest, explain, plan
    from repro.core.selector import last_decision

    req = PlanRequest("alltoall", 869, num_nodes=3, procs_per_node=4,
                      k_lanes=2)
    dec = explain(req)
    assert dec.candidates, "decision raced no candidates"
    raced = [c for c in dec.candidates if c.status == "priced"]
    assert raced, "no candidate was priced"
    for c in raced:
        assert c.est_us is not None and math.isfinite(c.est_us), (
            f"raced candidate {c.algorithm} has no finite price"
        )
    assert dec.winner in {c.algorithm for c in raced}, (
        "winner is not one of the priced candidates"
    )
    choice = plan(req)
    assert choice.algorithm == dec.winner, (
        "cached-path plan() disagrees with explain() winner"
    )
    last = last_decision()
    assert last is not None and last.winner == dec.winner
    json.dumps(dec.as_dict())  # must be export-safe


def check_metrics() -> None:
    """Contract 4: the smoke run left its counters and the snapshot is
    JSON-serializable."""
    from repro.obs import metrics as obs_metrics

    snap = obs_metrics.snapshot()
    for key in ("schedule_cache.misses", "schedule_recipes.misses"):
        assert key in snap and snap[key]["value"] > 0, (
            f"expected metric {key!r} missing/zero after the smoke run"
        )
    json.dumps(snap, default=str)


def validate_trace_jsonl(path: str) -> int:
    """Validate an exported trace JSONL file (``--check-trace``): every
    line parses, span records are well-formed, the pipeline stages are
    present (a ``compile`` span), and a ``compile`` span holds at least
    one ``pass:*`` span and one ``oracle`` span.  Returns the record
    count."""
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert recs, f"{path}: empty trace"
    for r in recs:
        assert r["ph"] in ("X", "i"), f"{path}: unexpected ph {r['ph']!r}"
        assert isinstance(r["ts"], int) and r["ts"] >= 0
        if r["ph"] == "X":
            assert isinstance(r["dur"], int) and r["dur"] >= 0
            assert r["depth"] >= 0
            # a child span must sit inside its parent's [ts, ts+dur]
            par = _spans_by_sid(recs).get(r.get("parent"))
            if par is not None:
                assert par["ts"] <= r["ts"] and (
                    r["ts"] + r["dur"] <= par["ts"] + par["dur"]
                ), f"{path}: span {r['sid']} escapes its parent"
    if not any(r["name"] == "compile" and r["ph"] == "X" for r in recs):
        _fail(f"{path}: no compile span recorded")
    for chain in (("compile", "optimize", "pass:"), ("compile", "oracle")):
        if not _has_ancestry(recs, chain):
            _fail(f"{path}: missing span ancestry {' > '.join(chain)}")
    return len(recs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="observability smoke: tracer nesting, exports, "
        "selector decisions, metrics"
    )
    ap.add_argument(
        "--check-trace", metavar="FILE", default=None,
        help="additionally validate an existing trace JSONL export",
    )
    args = ap.parse_args(argv)

    steps = []
    try:
        check_pipeline_trace()
        steps.append("nesting")
        with tempfile.TemporaryDirectory() as tmpdir:
            check_exports(tmpdir)
        steps.append("exports")
        check_decision()
        steps.append("decisions")
        check_metrics()
        steps.append("metrics")
        if args.check_trace:
            n = validate_trace_jsonl(args.check_trace)
            steps.append(f"trace-file({n} records)")
    except AssertionError as e:
        done = ", ".join(steps) or "none"
        print(f"obs_check: FAIL — {e} (steps passed: {done})")
        return 1
    print(f"obs_check: OK — {', '.join(steps)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
