"""The readers of the program's own spans: ``plan_race_ms``,
``plan_compiles_per_req`` and ``data_wait_ms.train``, on synthetic span
records, and on tiny traced runs of the program."""

import types

import pytest

from chipbench import harness, spanset
from chipbench.tests import _tiny

READERS = ("plan_race_ms", "plan_compiles_per_req", "data_wait_ms.train")


def rec(name, ts, dur, sid, parent=None, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "sid": sid,
            "parent": parent, "depth": 0, "tid": tid, "args": args}


def ctx(spans, steps=0):
    return types.SimpleNamespace(spans=spans, info={"steps": steps},
                                 trace=None)


def read(metric, c):
    return harness.load_reader(metric)(c)


def plan_spans():
    """Two requests: the first races in ``select.batch`` [10, 60) with a
    ``select`` nested in it and two compiles, one holding a nested compile
    and one overlapping the batch's end; the second in a ``select`` [210,
    240) holding one compile.  ``plan.schedule`` compiles sit outside the
    race."""
    return [
        rec("plan", 0, 100, 1, requests=1),
        rec("select.batch", 10, 50, 2, 1, queries=1, groups=1),
        rec("select", 20, 20, 3, 2),
        rec("compile", 22, 10, 4, 3),
        rec("compile", 24, 4, 5, 4),            # the base of 4, nested
        rec("compile", 55, 20, 6, 2),           # runs past the race's end
        rec("plan.schedule", 100, 40, 7),
        rec("compile", 105, 30, 8, 7),
        rec("plan", 200, 50, 9, requests=1),
        rec("select", 210, 30, 10, 9),
        rec("compile", 215, 10, 11, 10),
        {"name": "cache.hit", "ph": "i", "ts": 241, "sid": 12, "parent": 9,
         "depth": 1, "tid": 1, "args": {}},
    ]


def test_union_merges_nested_and_overlapping_spans():
    spans = [rec("a", 0, 10, 1), rec("a", 2, 3, 2), rec("a", 8, 10, 3),
             rec("a", 30, 5, 4), rec("b", 0, 100, 5)]
    got = spanset.union(spanset.closed(spans, "a"))
    assert got == [(0, 18), (30, 35)]
    assert spanset.length(got) == 23
    assert spanset.union([]) == []


def test_plan_race_ms_takes_compiles_out_of_the_race():
    # race: [10, 60) and [210, 240) = 80 us; compiles inside it:
    # [22, 32) + [55, 60) + [215, 225) = 25 us; two requests
    assert read("plan_race_ms", ctx(plan_spans())) == pytest.approx(
        (80 - 25) / 2 / 1e3)


def test_plan_compiles_per_req_counts_outermost_compiles():
    # 4, 6, 8 and 11; 5 is nested in 4
    assert read("plan_compiles_per_req", ctx(plan_spans())) == 2.0


def test_data_wait_ms_per_step():
    spans = [rec("data.wait", 0, 4000, 1), rec("data.batch", 0, 9000, 2,
                                                tid=2),
             rec("data.wait", 10000, 1000, 3),
             rec("data.wait", 10500, 1000, 4, tid=3)]  # overlaps the last
    assert read("data_wait_ms.train", ctx(spans, steps=3)) == pytest.approx(
        (4000 + 1500) / 3 / 1e3)


@pytest.mark.parametrize("metric", READERS)
def test_empty_input_reads_nothing(metric):
    assert read(metric, ctx([], steps=5)) is None
    assert read(metric, ctx(None, steps=5)) is None
    # a program with none of the spans the reader needs
    assert read(metric, ctx([rec("compile", 0, 5, 1)], steps=5)) is None


@pytest.mark.parametrize("cell,metrics", [
    ("plan.deepseek-v2-ep", ("plan_race_ms", "plan_compiles_per_req")),
    ("train.h2o-danube-3-4b.1chip", ("data_wait_ms.train",)),
])
def test_traced_tiny_run_reports_span_metrics(cell, metrics):
    spec = _tiny.spec(cell)
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] in metrics]
    out = harness.run_cell(cell, 2**31 + 7, 0.5, True, require_tpu=False,
                           spec=spec)
    assert out["correct"]
    for m in metrics:
        assert out["metrics"][m]["value"] >= 0, m
