"""The controls, tiny, on the CPU: the plain reference put in the
program's place one precision lower (float8 under the configuration's
bfloat16), or with one guarantee broken, reads above what the program reads
and fails the cell's limits, judged by the comparison that decides a run's
``correct`` (``calibrate.verdicts``).  On the chip the same readings set the
limits (``chipbench/calibrate.py``)."""

import jax

from chipbench import calibrate, harness
from chipbench.refs import dense_lm
from chipbench.tests import _tiny

SEED = 2**31 + 7


def _train_verdicts(cell_name):
    spec = _tiny.spec(cell_name)
    entry = harness.load_entry(spec["traffic"]["entry"])
    cell = entry.build(spec["config"], spec["traffic"], SEED,
                       jax.devices()[:spec["cell"]["chips"]])
    cell.setup()
    cell.free()
    batches = cell.ref_batches(SEED)
    want = cell.reference().run(SEED, batches)
    prog = dense_lm.gaps(cell.readings, want)
    ctrl = dense_lm.gaps(cell.reference(dense_lm.to_fp8).run(SEED, batches),
                         want)
    readings = {"program": {SEED: prog}, "control": {SEED: ctrl}}
    return readings, calibrate.verdicts(readings, spec["traffic"]["limits"])


def test_train_control_reads_above_the_program():
    readings, verdicts = _train_verdicts("train.h2o-danube-3-4b.1chip")
    prog, ctrl = readings["program"][SEED], readings["control"][SEED]
    assert verdicts["program"][SEED] == {"correct": True, "failed": []}
    assert not verdicts["control"][SEED]["correct"]
    assert max(ctrl[k] / prog[k] for k in prog) >= 3


def test_train_control_fails_the_limits_on_2x2():
    _, verdicts = _train_verdicts("train.h2o-danube-3-4b.2x2")
    assert verdicts["program"][SEED]["correct"]
    assert not verdicts["control"][SEED]["correct"]


def _calibrate(cell_name):
    spec = _tiny.spec(cell_name)
    entry = harness.load_entry(spec["traffic"]["entry"])
    devs = jax.devices()[:spec["cell"]["chips"]]
    cell = entry.build(spec["config"], spec["traffic"], SEED, devs)
    return entry.calibrate(cell, [SEED, SEED + 1], [SEED]), spec


def test_alltoall_control_fails_the_exact_comparison():
    out, spec = _calibrate("a2a.deepseek-v2-prefill.2x2")
    limit = spec["traffic"]["limits"]["wrong_elements"]
    assert all(v <= limit for v in out["program"].values())
    assert out["control"][SEED] > limit
    verdicts = calibrate.verdicts(out, spec["traffic"]["limits"])
    assert verdicts["control"][SEED] == {"correct": False,
                                         "failed": ["wrong_elements"]}


def test_planner_control_fails_the_delivery_check():
    out, spec = _calibrate("plan.deepseek-v2-ep")
    limit = spec["traffic"]["limits"]["schedule_defects"]
    assert all(v <= limit for v in out["program"].values())
    assert out["control"][SEED] > limit
    verdicts = calibrate.verdicts(out, spec["traffic"]["limits"])
    assert all(v["correct"] for v in verdicts["program"].values())
    assert not verdicts["control"][SEED]["correct"]
