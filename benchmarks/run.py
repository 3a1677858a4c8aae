"""Benchmark harness entry point: one section per paper table plus the
optimizer delta table, TPU projection, gradient-sync HLO comparison, and
the roofline summary.

Prints ``name,impl,k,c,sim_us,paper_us`` CSV rows (and roofline rows from
the dry-run artifacts when present); the paper section ends with the
``# optimizer:`` optimized-vs-paper delta lines.  ``--json FILE``
additionally writes every simulator cell as machine-readable
``{table, impl, k, c, sim_us, wall_s}`` records — OPT3 cells (the
planner's ``"color"`` optimizer pipeline) carry ``{base_us, rounds_before,
rounds_after, opt_wall_s}`` — so the perf story is tracked across PRs
(``BENCH_schedules.json`` by convention).  ``tools/bench_gate.py``
compares a fresh ``--json`` dump against the committed baseline and fails
CI on any >5% ``sim_us`` regression or disappeared cell.

ISSUE 7 observability: ``--trace``/``--trace-jsonl`` export the run's
flight-recorder spans (Chrome trace-event JSON / raw JSONL) and
``--metrics`` snapshots the metrics registry; any of them — and
``--deltas``, whose per-pass breakdown column is flight-recorder
sourced — enables the tracer for the run.

  PYTHONPATH=src python -m benchmarks.run [--skip-hlo] \
      [--only paper|tpu|hlo|roofline] [--json BENCH_schedules.json] \
      [--trace paper.trace.json] [--metrics paper.metrics.json]
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only",
                    choices=["paper", "paper-opt", "tpu", "hlo", "roofline"],
                    default=None)
    ap.add_argument("--skip-hlo", action="store_true")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="write per-cell {table,impl,k,c,sim_us,wall_s} JSON")
    ap.add_argument("--deltas", metavar="FILE", default=None,
                    help="also write the OPT3 optimized-vs-paper "
                    "delta table to FILE (CI uploads it as an artifact); "
                    "enables the tracer so the per-pass breakdown column "
                    "is flight-recorder sourced")
    ap.add_argument("--trace", metavar="FILE", default=None,
                    help="export the run's spans as a Chrome trace-event "
                    "file (load in Perfetto / chrome://tracing)")
    ap.add_argument("--trace-jsonl", metavar="FILE", default=None,
                    help="export the run's spans as raw JSONL, one record "
                    "per line")
    ap.add_argument("--metrics", metavar="FILE", default=None,
                    help="write the pipeline metrics snapshot (counters, "
                    "gauges, histograms) as JSON")
    args = ap.parse_args()

    trace_requested = bool(
        args.trace or args.trace_jsonl or args.metrics or args.deltas
    )
    if trace_requested:
        from repro.obs import trace as obs_trace
        obs_trace.enable()

    cells: list[dict] = []
    print("table,impl,k,c,sim_us,paper_us")
    if args.only in (None, "paper"):
        from benchmarks.paper_tables import (
            ALL_TABLES,
            csv_row,
            render_optimizer_deltas,
        )
        for fn in ALL_TABLES:
            for cell in fn():
                cells.append(cell)
                print(csv_row(cell), flush=True)
        delta_lines = render_optimizer_deltas(cells)
        for line in delta_lines:
            print(line, flush=True)
        if args.deltas:
            with open(args.deltas, "w") as f:
                f.write("\n".join(delta_lines) + "\n")
            print(f"# wrote optimizer delta table to {args.deltas}",
                  flush=True)
    elif args.only == "paper-opt":
        # ISSUE 5 CI satellite: one paper-scale (p=1152) alltoall OPT cell,
        # CHECK_TIMEOUT-bounded in tools/check.sh, so the optimizer's
        # scalability cannot silently regress in the fast job.
        from benchmarks.paper_tables import (
            csv_row,
            render_optimizer_deltas,
            table_paper_opt_smoke,
        )
        for cell in table_paper_opt_smoke():
            cells.append(cell)
            print(csv_row(cell), flush=True)
        for line in render_optimizer_deltas(cells):
            print(line, flush=True)
        if args.deltas:
            print(f"# optimizer deltas only written for --only paper; "
                  f"{args.deltas} not written", flush=True)
    elif args.deltas:
        # the OPT tables only run in the paper selection; stay loud rather
        # than silently skipping a requested output file
        print(f"# optimizer deltas only exist for --only paper; "
              f"{args.deltas} not written", flush=True)
    if args.only in (None, "tpu"):
        from benchmarks.collective_bench import tpu_projection
        from benchmarks.paper_tables import csv_row
        for cell in tpu_projection():
            cells.append(cell)
            print(csv_row(cell), flush=True)
    if args.only in (None, "hlo") and not args.skip_hlo:
        from benchmarks.collective_bench import grad_sync_hlo
        for row in grad_sync_hlo():
            print(row, flush=True)
    if args.only in (None, "roofline"):
        import os
        from benchmarks.roofline import csv_rows, roofline_table
        emitted = False
        # complete baseline table first, then the optimized cells
        for label, d in (("baseline", "experiments/dryrun_baseline"),
                         ("optimized", "experiments/dryrun")):
            if os.path.isdir(d):
                for row in csv_rows(roofline_table(d)):
                    print(f"{label}_{row}", flush=True)
                emitted = True
        if not emitted:
            print("roofline,,,no dry-run artifacts (run repro.launch.dryrun),,,")

    if args.json and not cells:
        # --only hlo/roofline collect no simulator cells; don't clobber a
        # previously written trajectory file with an empty one.
        print(f"# no simulator cells in this selection; {args.json} not written",
              flush=True)
    elif args.json:
        # OPT3 cells additionally carry the optimizer trajectory: the
        # unoptimized baseline, the round delta and the optimizer's own
        # wall-clock (opt_wall_s; the gate stays on sim_us).
        # DEG cells (ISSUE 6) carry the graceful-degradation context: the
        # healthy-machine time, the natively regenerated fallback where one
        # exists, and the fault fingerprint that keyed the repaired entry.
        # LB cells (ISSUE 9) carry the certificate context: the analytic
        # bound, the optimized time it certifies, and the round bound
        # (sim_us on an LB cell IS gap_vs_lb — the gated ratio).
        opt_keys = ("base_us", "rounds_before", "rounds_after",
                    "opt_wall_s",
                    "healthy_us", "native_us", "scenario", "fingerprint",
                    "lb_us", "opt_us", "rounds_lb", "gap_vs_lb")
        payload = {
            "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "cells": [
                {
                    "table": c["table"],
                    "impl": c["impl"],
                    "k": c["k"],
                    "c": c["c"],
                    "sim_us": c["sim_us"],
                    "wall_s": c["wall_s"],
                    **{k: c[k] for k in opt_keys if k in c},
                }
                for c in cells
            ],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload['cells'])} cells to {args.json}", flush=True)

    if trace_requested:
        from repro.obs import metrics as obs_metrics
        from repro.obs.trace import TRACER
        if args.trace:
            TRACER.export_chrome(args.trace)
            print(f"# wrote Chrome trace ({TRACER.total} spans, "
                  f"{TRACER.dropped} dropped) to {args.trace}", flush=True)
        if args.trace_jsonl:
            TRACER.export_jsonl(args.trace_jsonl)
            print(f"# wrote trace JSONL to {args.trace_jsonl}", flush=True)
        if args.metrics:
            with open(args.metrics, "w") as f:
                json.dump(obs_metrics.snapshot(), f, indent=1, default=str)
            print(f"# wrote metrics snapshot to {args.metrics}", flush=True)


if __name__ == "__main__":
    main()
