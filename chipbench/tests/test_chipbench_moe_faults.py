"""The MoE training cell, tiny, on the CPU, expert-parallel on the 2x2 of
its EP group, with the timed path broken underneath: the check reads
``correct`` false for an unchanged state, half of each chip's batch, an
exchange that moves nothing and one parameter altered, and the
calibration's float8 control and faults planted in the reference fail the
cell's limits too."""

import jax
import pytest

from chipbench import calibrate, harness
from chipbench.tests.test_chipbench_moe_train import EP, SEED, run, spec
from chipbench.tests.test_chipbench_train_faults import (_altered,
                                                         _half_batch,
                                                         _unchanged)


def _no_exchange(monkeypatch):
    from repro.core import collectives

    monkeypatch.setattr(collectives, "fulllane_all_to_all",
                        lambda x, outer, inner: x)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange,
                                   _altered], ids=lambda f: f.__name__[1:])
def test_broken_step_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run(EP)
    assert not out["correct"], out["compared"]


def test_control_and_planted_faults_fail_the_limits():
    """The calibration's readings, judged as a run is judged: the program
    correct, the float8 control and each fault planted in the reference
    not."""
    s = spec(EP)
    entry = harness.load_entry(s["traffic"]["entry"])
    cell = entry.build(s["config"], s["traffic"], SEED, jax.devices()[:4])
    out = entry.calibrate(cell, [SEED], [SEED])
    verdicts = calibrate.verdicts(out, s["traffic"]["limits"])
    assert verdicts["program"][SEED]["correct"], out["program"]
    for kind in ("control", "half_batch", "no_exchange", "altered"):
        assert not verdicts[kind][SEED]["correct"], (kind, out[kind])


