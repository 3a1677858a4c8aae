"""Runs of the alltoall and planner cells, tiny, with the timed path
broken underneath: ``correct`` false for every fault each can have."""

import dataclasses

import numpy as np
import pytest

from chipbench.tests import _tiny

A2A = "a2a.deepseek-v2-prefill.2x2"
PLAN = "plan.deepseek-v2-ep"


def _a2a_unchanged(real):
    return lambda v, outer, inner: v


def _a2a_half(real):
    def half(v, outer, inner):
        out = real(v, outer, inner)
        h = v.shape[0] // 2
        return out.at[h:].set(v[h:])
    return half


def _a2a_altered(real):
    return lambda v, outer, inner: real(v, outer, inner).at[0, 0, 0].add(1)


@pytest.mark.parametrize("cell", [A2A, PLAN])
def test_sound_cell_is_correct(cell):
    out = _tiny.run(cell)
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("fault", [_a2a_unchanged, _a2a_half, _a2a_altered],
                         ids=lambda f: f.__name__[5:])
def test_broken_alltoall_is_not_correct(monkeypatch, fault):
    from repro.core import collectives

    monkeypatch.setattr(collectives, "fulllane_all_to_all",
                        fault(collectives.fulllane_all_to_all))
    out = _tiny.run(A2A)
    assert not out["correct"], out["compared"]


def _plan_altered(cs):
    dst = cs.dst.copy()
    dst[-1] = (dst[-1] + 1) % cs.p
    return dataclasses.replace(cs, dst=dst, _stats={})


def _plan_half(cs):
    half = cs.num_rounds // 2 or 1
    ptr = cs.round_ptr[: half + 1]
    m = int(ptr[-1])
    return dataclasses.replace(
        cs, src=cs.src[:m], dst=cs.dst[:m], elems=cs.elems[:m],
        round_ptr=ptr, blk_ptr=cs.blk_ptr[: m + 1],
        blk_ids=cs.blk_ids[: cs.blk_ptr[m]], _stats={})


@pytest.mark.parametrize("fault", [_plan_altered, _plan_half],
                         ids=lambda f: f.__name__[6:])
def test_broken_plan_is_not_correct(monkeypatch, fault):
    from repro import api

    real = api.Plan.schedule
    monkeypatch.setattr(api.Plan, "schedule", lambda self: fault(real(self)))
    out = _tiny.run(PLAN)
    assert not out["correct"], out["compared"]
    assert np.isfinite(out["compared"]["schedule_defects"]["value"])
