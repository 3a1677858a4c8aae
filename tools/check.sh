#!/usr/bin/env bash
# One-command verify recipe (ISSUE 2 CI satellite; CI-hardened in ISSUE 3).
#
# Default (fast) mode:
#   * the schedule/IR/optimizer/oracle/scheduling-pass test files (the
#     paper-reproduction core, no jax compilation in the loop),
#   * a lint step (ruff when available, else a bytecode compile check),
#   * a chaos smoke (seeded fault injection -> repair -> oracle, ISSUE 6),
#   * a paper-tables benchmark smoke writing the fresh trajectory to
#     BENCH_schedules.fresh.json, and
#   * tools/bench_gate.py comparing it against the committed
#     BENCH_schedules.json — zero cells, a disappeared cell, or any >5%
#     sim_us regression exits non-zero.
#
# CHECK_FULL=1 tools/check.sh runs the whole tier-1 suite instead of the
# fast file list (ROADMAP: PYTHONPATH=src python -m pytest -x -q).
#
# Per-step wall-clock guards default to CHECK_TIMEOUT=600 seconds; shared
# CI runners are slower than the dev box, so export a larger value — or
# CHECK_TIMEOUT=0 to disable (GNU timeout treats 0 as "no timeout").
# A step killed by the timeout is *named* on stderr (ISSUE 6 satellite) —
# "check.sh failed" with no culprit cost a CI round-trip to diagnose.
#
# To bless a new trajectory baseline after an intentional change:
#   python tools/bench_gate.py BENCH_schedules.fresh.json --update-baseline
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

T="${CHECK_TIMEOUT:-600}"

# run_step <name> <cmd...>: timeout-bounded named step.  GNU timeout exits
# 124 (or 128+9 after KILL escalation) when it fired — report WHICH step
# died instead of letting `set -e` end the script anonymously.
run_step() {
    local name="$1"; shift
    local rc=0
    timeout "$T" "$@" || rc=$?
    if [[ $rc -ge 124 ]]; then
        echo "check.sh: step '$name' killed by CHECK_TIMEOUT=${T}s" >&2
        exit $rc
    elif [[ $rc -ne 0 ]]; then
        echo "check.sh: step '$name' failed (exit $rc)" >&2
        exit $rc
    fi
}

if [[ "${CHECK_FULL:-0}" == "1" ]]; then
    run_step "pytest-full" python -m pytest -x -q
else
    run_step "pytest-fast" python -m pytest -x -q \
        tests/test_schedules.py \
        tests/test_schedule_ir.py \
        tests/test_simulator.py \
        tests/test_passes.py \
        tests/test_validate.py \
        tests/test_reorder_split.py \
        tests/test_color_pack.py \
        tests/test_issue5.py \
        tests/test_faults.py \
        tests/test_obs.py \
        tests/test_store.py \
        tests/test_api.py \
        tests/test_resilience.py
fi

# lint (CI-fast-job parity): ruff when installed, else a compile check.
# The CI fast job runs its own dedicated lint step first, so it sets
# CHECK_SKIP_LINT=1 to avoid linting the same paths twice.  ISSUE 9
# widened the surface: store, api, serving, and benchmarks are covered too.
LINT_PATHS=(src/repro/core src/repro/obs src/repro/store src/repro/api.py
            src/repro/serving benchmarks tools)
if [[ "${CHECK_SKIP_LINT:-0}" != "1" ]]; then
    if command -v ruff >/dev/null 2>&1; then
        ruff check "${LINT_PATHS[@]}"
    else
        python -m compileall -q "${LINT_PATHS[@]}"
    fi
fi

# custom lint (ISSUE 9 tentpole): the AST discipline rules ruff cannot
# express — lock-guarded shared-state mutation, tracer-span closure on
# all paths, and recipe_safe declarations on every scheduling pass.
run_step "lint-custom" python -m tools.repro_lint

# static-analyzer smoke (ISSUE 9 tentpole): healthy schedules must carry
# zero error-severity diagnostics, seeded corruptions must be caught, and
# every lower-bound certificate must be finite and >= 1.  Writes the
# diagnostics report artifact both CI jobs upload.
run_step "analyze-smoke" python -m tools.analyze_check \
    --report analyze_report.json

# chaos smoke (ISSUE 6 CI satellite): seeded fault injection on a small
# topology — sample faults, repair every alltoall family, oracle-check,
# and exercise the selector's degraded ladder.  Deterministic and < 30 s.
run_step "chaos-smoke" python -m tools.chaos --seed 0 \
    --nodes 3 --procs 4 --lanes 2 --out chaos_report.json

# resilience smoke (ISSUE 10): chaos phase 2 — a writer SIGKILLed
# mid-store-publish must leave zero torn/duplicate artifacts on restart,
# seeded flaky-IO injection must complete every query via retry/recompute
# (quarantining repeat offenders), and fault-event replanning must trip
# the breaker into the deadline-exempt base rung and heal.  Appends to
# chaos_report.json (the extended report both CI jobs upload).  The L001
# lock lint runs first over the new resilience surface explicitly: the
# store's race counters and quarantine sets are exactly the shared-state
# class that rule exists for.
run_step "resilience-smoke" bash -c \
    "python -m tools.repro_lint src/repro/core/resilience.py \
        src/repro/store/artifacts.py src/repro/serving && \
     python -m tools.chaos --resilience --seed 0 \
        --append --out chaos_report.json"

# paper-scale OPT smoke (ISSUE 5 CI satellite): a single p=1152 alltoall
# cell through the planner's optimize-validate pipeline, CHECK_TIMEOUT-bounded,
# so the optimizer's scalability cannot silently regress in the fast job.
# ISSUE 7: the smoke runs traced and exports the flight recorder (Chrome
# trace + JSONL) and the metrics snapshot — CI uploads all three.
run_step "paper-opt-smoke" bash -c \
    "set -o pipefail; python -m benchmarks.run --only paper-opt \
        --trace paper_opt.trace.json --trace-jsonl paper_opt.trace.jsonl \
        --metrics paper_opt.metrics.json | tail -n 8"

# observability smoke (ISSUE 7 CI satellite): tracer span nesting
# (compile -> optimize -> pass, compile -> oracle), export validity, selector
# decision records, metrics counters — plus validation of the paper-opt
# trace just exported above.
run_step "obs-smoke" python -m tools.obs_check \
    --check-trace paper_opt.trace.jsonl

# store smoke (ISSUE 8 CI satellite): build + persist schedules/recipes,
# then warm-start a *subprocess* from the on-disk store and verify
# bit-identical schedules, recipe replay, and zero store recompiles — the
# real cross-process round-trip, not an in-process simulation.
run_step "store-smoke" python -m tools.store_check

# load smoke (ISSUE 8 tentpole): bounded cold->persist->restart->warm
# concurrent load test; writes load_report.json (CI uploads it) and fails
# on a hit-rate/store-recompile contract breach.
run_step "load-smoke" python -m benchmarks.load --smoke \
    --report load_report.json

# benchmark smoke -> fresh trajectory + the OPT3 delta table (the
# delta file is the CI artifact reviewers diff); the gate fails on zero
# cells, a disappeared cell, or any >5% sim_us regression vs the committed
# baseline (with the --abs-tol floor guarding near-zero cells).  The
# ISSUE 8 SVC/SVC-WALL service cells carry percentages and wall-clock
# values, so they get per-table absolute slack instead of the simulator
# tables' tight floor.
FRESH="BENCH_schedules.fresh.json"
DELTAS="BENCH_deltas.fresh.txt"
rm -f "$FRESH" "$DELTAS"
run_step "bench-smoke" bash -c \
    "set -o pipefail; python -m benchmarks.run --only paper --json '$FRESH' \
        --deltas '$DELTAS' | tail -n 30"
# RES counts are seeded-deterministic (small absolute slack only);
# RES-WALL carries the replan-latency p99 in us, so it gets wall-clock
# slack like SVC-WALL.
python tools/bench_gate.py "$FRESH" --baseline BENCH_schedules.json \
    --table-abs-tol SVC=10 --table-abs-tol SVC-WALL=100000 \
    --table-abs-tol RES=2 --table-abs-tol RES-WALL=1000000
echo "check.sh: OK"
