"""Program spans where the host work happens, and on the profiler's clock:
the tracer mirrors each span into a ``jax.profiler.TraceAnnotation``; the
planner records ``plan`` > ``select.batch`` > ``compile`` and
``plan.schedule``; the prefetcher records ``data.wait`` and
``data.batch``."""

import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import schedule_ir as IR
from repro.core.selector import selector_cache_reset
from repro.obs.trace import TRACER, Tracer
from repro.training.data import Prefetcher


@pytest.fixture(autouse=True)
def _clean_tracer():
    TRACER.disable()
    TRACER.clear()
    yield
    TRACER.disable()
    TRACER.clear()


def _host_events(log_dir) -> dict[str, list[tuple[float, float]]]:
    """``name -> [(start_ns, end_ns)]`` of the host planes' events, read as
    ``chipbench/devtrace.load`` reads them."""
    from jax.profiler import ProfileData

    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    out: dict[str, list[tuple[float, float]]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_spans_mirrored_into_the_profiler_trace(tmp_path):
    t = Tracer(capacity=64)
    t.enable()
    off = Tracer(capacity=64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            outer = t.start("test.outer", attr=1)
            inner = t.start("test.inner")
            jnp.ones(8).block_until_ready()
            t.finish(inner)
            t.event("test.instant")
            t.finish(outer)
            with off.span("test.never"):
                pass
            sp = off.start("test.never.started")  # started while disabled
            off.finish(sp)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    assert len(ev["test.outer"]) == len(ev["test.inner"]) == 1
    (w0, w1), = ev["test.window"]
    (o0, o1), = ev["test.outer"]
    (i0, i1), = ev["test.inner"]
    # nested as the program opened them, on the trace's own clock
    assert w0 <= o0 <= i0 < i1 <= o1 <= w1
    # the ring keeps the attributes; the trace carries the name alone
    assert {r["name"] for r in t.records()} >= {"test.outer", "test.inner"}
    assert "test.instant" not in ev
    assert "test.never" not in ev and "test.never.started" not in ev
    assert outer.annotation is None and inner.annotation is None


def test_no_mirror_without_jax(monkeypatch):
    """A process that has not imported jax records to the ring alone."""
    monkeypatch.delitem(sys.modules, "jax")
    t = Tracer(capacity=8)
    t.enable()
    sp = t.start("x")
    assert sp.annotation is None
    t.finish(sp)
    assert [r["name"] for r in t.records()] == ["x"]


def test_plan_batch_spans_nest():
    IR.schedule_cache_clear()
    selector_cache_reset()
    TRACER.enable()
    mark = TRACER.mark()
    req = api.PlanRequest("alltoall", 4096, num_nodes=3, procs_per_node=4,
                          k_lanes=2)
    plan = api.plan_batch([req])[0]
    recs = [r for r in TRACER.records_since(mark) if r["ph"] == "X"]
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    (top,) = by_name["plan"]
    assert top["parent"] is None and top["args"]["requests"] == 1
    (sel,) = by_name["select.batch"]
    assert sel["parent"] == top["sid"]
    assert sel["args"] == {"queries": 1, "groups": 1}
    assert any(c["parent"] == sel["sid"] for c in by_name["compile"])

    mark = TRACER.mark()
    plan.schedule()
    recs = [r for r in TRACER.records_since(mark) if r["ph"] == "X"]
    (sched,) = [r for r in recs if r["name"] == "plan.schedule"]
    assert sched["parent"] is None
    assert sched["args"]["algorithm"] == plan.algorithm
    assert all(r["parent"] is not None for r in recs if r is not sched)


def test_prefetcher_spans_per_thread():
    TRACER.enable()
    mark = TRACER.mark()
    pf = Prefetcher(iter(range(6)), depth=2)
    got = [next(pf) for _ in range(4)]
    assert got == [0, 1, 2, 3]
    recs = TRACER.records_since(mark)
    me = threading.get_ident()
    waits = [r for r in recs if r["name"] == "data.wait"]
    assert len(waits) == 4 and all(r["tid"] == me for r in waits)
    made = [r for r in recs if r["name"] == "data.batch"]
    assert len(made) >= 4 and all(r["tid"] != me for r in made)
    assert list(pf) == [4, 5]
