"""Readings that a cell's correctness limits are set from, in one process:

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out FILE]

The program's numbers on every seed, and on the control seeds those of the
control (the plain reference put in the program's place one precision
lower, or with one guarantee broken) and of each planted fault the entry
knows.  Each reading is then held to the cell's limits by the same
comparison that decides a run's ``correct``.  The benchmark's own runs never
run this.
"""

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def verdicts(readings: dict, limits: dict) -> dict:
    """Per kind of reading and seed: whether a run that read it would be
    correct, and the numbers that would fail.  A reading that is one number
    is held to the cell's one limit."""
    out = {}
    for kind, by_seed in readings.items():
        out[kind] = {}
        for seed, value in by_seed.items():
            if not isinstance(value, dict):
                (name,) = limits
                value = {name: value}
            failed = [c.name for c in harness.compare(value, limits)
                      if not c.ok]
            out[kind][seed] = {"correct": not failed, "failed": failed}
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.SRC))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    spec = harness.load_cell(args.workload)
    devs = harness.devices_for(spec["cell"]["chips"], require_tpu=True)
    entry = harness.load_entry(spec["traffic"]["entry"])
    cell = entry.build(spec["config"], spec["traffic"], seeds[0], devs)
    readings = entry.calibrate(cell, seeds, control)
    out = {"workload": args.workload, "readings": readings,
           "verdicts": verdicts(readings, spec["traffic"]["limits"])}
    text = json.dumps(out, indent=1, default=float)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
