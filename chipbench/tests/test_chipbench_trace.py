"""The reduction from a device trace to busy time, idle share, per-op and
per-program device time, on synthetic traces."""

import pytest

from chipbench import devtrace


def make(ops, modules=None, host=(), window=(0.0, 100.0)):
    return devtrace.Trace(ops=ops, modules=modules or {}, host=list(host),
                          window=window)


def test_union_merges_overlaps_and_nesting():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 6, 2), ("d", 20, 5),
           ("e", 25, 5)]
    # [0, 15) and [20, 30): 15 + 10
    assert devtrace.union_ns(evs) == 25
    assert devtrace.union_ns([]) == 0


def test_clip_cuts_events_to_the_window():
    evs = [("a", -5, 10), ("b", 50, 100), ("c", 200, 5)]
    assert devtrace.clip(evs, (0, 100)) == [("a", 0, 5), ("b", 50, 50)]


def test_idle_share_averages_over_devices():
    # device 0 busy 60 of 100 ns, device 1 busy 20 (two overlapping ops)
    tr = make({0: [("fusion.1", 0, 60)],
               1: [("fusion.1", 10, 20), ("copy.2", 15, 10)]})
    assert devtrace.busy_s(tr) == pytest.approx(40e-9)
    assert devtrace.idle_share(tr) == pytest.approx(0.6)
    assert devtrace.idle_share(make({})) is None


def test_op_seconds_sums_per_name_inside_the_window():
    tr = make({0: [("all-reduce.1", 0, 10), ("all-reduce.1", 20, 10),
                   ("fusion.3", 30, 40), ("all-gather-start.2", 90, 20)],
               1: [("all-reduce.1", 0, 30)]}, window=(0, 100))
    sums = devtrace.op_seconds(tr)
    # per device on average: (10 + 10 + 30) / 2
    assert sums["all-reduce.1"] == pytest.approx(25e-9)
    assert sums["fusion.3"] == pytest.approx(20e-9)
    # clipped at the window's end: 10 of 20 ns, over two devices
    assert sums["all-gather-start.2"] == pytest.approx(5e-9)
    coll = devtrace.op_seconds(tr, devtrace.is_collective)
    assert set(coll) == {"all-reduce.1", "all-gather-start.2"}


@pytest.mark.parametrize("name,want", [
    ("all-reduce.12", True), ("all-gather-start", True),
    ("reduce-scatter.3", True), ("all-to-all.1", True),
    ("collective-permute-done.4", True), ("all_to_all.7", True),
    ("fusion.7", False),
    ("all-reduce-scatter-fusion", False), ("copy.1", False)])
def test_is_collective(name, want):
    assert devtrace.is_collective(name) is want


def test_module_calls_counts_programs_started_in_the_window():
    mods = {0: [("jit_chipbench_alltoall(1)", 10, 5),
                ("jit_chipbench_alltoall(1)", 30, 5),
                ("jit_other", 40, 50),
                ("jit_chipbench_alltoall(1)", 120, 5)],
            1: [("jit_chipbench_alltoall(1)", 10, 7),
                ("jit_chipbench_alltoall(1)", 30, 7)]}
    tr = make({0: [], 1: []}, modules=mods, window=(0, 100))
    calls, secs = devtrace.module_calls(tr, "chipbench_alltoall")
    assert calls == 2
    assert secs == pytest.approx((10 + 14) / 2 * 1e-9)


def test_device_p95_reads_every_call_on_every_chip():
    import types

    from chipbench import harness

    # 20 calls on each of two chips, 1..20 and 21..40 ns; one call after
    # the window, of 1000 ns, is left out
    mods = {0: [("jit_chipbench_alltoall(1)", 2 * i, i + 1) for i in range(20)]
            + [("jit_chipbench_alltoall(1)", 150, 1000)],
            1: [("jit_chipbench_alltoall(1)", 2 * i, 21 + i) for i in range(20)]}
    ctx = types.SimpleNamespace(
        trace=make({0: [], 1: []}, modules=mods, window=(0, 100)),
        info={"program": "chipbench_alltoall"})
    secs = devtrace.module_seconds(ctx.trace, "chipbench_alltoall")
    assert sorted(secs) == pytest.approx([n * 1e-9 for n in range(1, 41)])
    read = harness.load_reader("a2a_device_p95_us")
    # numpy's linear percentile of 1..40 ns: 38.05 ns
    assert read(ctx) == pytest.approx(38.05e-3)
    ctx.info["program"] = "absent"
    assert read(ctx) is None


def test_breakdown_names_the_host_span_over_each_gap():
    tr = make({0: [("fusion.1", 0, 10), ("fusion.2", 40, 50)]},
              host=[(devtrace.WINDOW, 0, 100), ("next(feed)", 12, 25),
                    ("log", 92, 4)])
    out = devtrace.breakdown(tr)
    assert out["device_ops"][0] == ["fusion.2", pytest.approx(50e-9)]
    # gaps [10, 40) and [90, 100): the feed span covers most of the first;
    # the log span covers less than half of the second
    assert out["idle_gaps"][0] == ["next(feed)", pytest.approx(30e-9)]
    assert out["idle_gaps"][1] == ["no host span", pytest.approx(10e-9)]
    assert len(out["device_ops"]) <= 10


def test_self_times_take_nested_ops_out_of_their_parent():
    # a while loop [0, 100) holding two fusions, one of them holding a copy
    evs = [("while.1", 0, 100), ("fusion.2", 10, 30), ("copy.3", 15, 5),
           ("fusion.4", 50, 20), ("fusion.5", 120, 10)]
    got = dict(devtrace.self_times(evs))
    assert got == {"while.1": 50, "fusion.2": 25, "copy.3": 5,
                   "fusion.4": 20, "fusion.5": 10}
    assert sum(got.values()) == devtrace.union_ns(evs)


def test_short_name_of_hlo_text():
    assert devtrace.short_name(
        "%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x)") == "all-reduce.7"
    assert devtrace.short_name("fusion.3") == "fusion.3"
