"""Run one benchmark cell once and print its result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: it names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); the traffic names the entry that
drives the program (``chipbench/entries/<entry>.py``).  Each per-layer metric
is read by ``chipbench/metrics/<metric>.py``.  The harness finds all of them
by name, so a new cell, mix or metric is a new file and no edit.

An entry module has ``build(config, traffic, seed, devices) -> cell``; the
cell has ``setup()``, ``window(seconds, traced) -> dict``, ``free()`` and
``check() -> list[Compared]``.  ``window`` returns ``attempted``,
``failed``, ``metrics`` (end-to-end values by name) and ``info`` (what the
per-layer readers need).
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
SRC = CHECKOUT / "src"
# records the program's tracer keeps over one traced window
TRACER_CAPACITY = 1 << 21


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Compared:
    """One number of the correctness check, beside its limit: the run is
    correct when every number is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def compare(values: dict, limits: dict) -> list[Compared]:
    """Every number the cell's limits name, beside its limit."""
    return [Compared(k, float(values[k]), limits[k]) for k in limits]


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry of BENCHMARK.json, with its configuration, traffic
    and metric entries resolved."""
    bench = bench or load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(CHECKOUT / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def load_entry(entry: str):
    return importlib.import_module(f"chipbench.entries.{entry}")


def load_reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    print(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
          f"jax {jax.__version__}", file=sys.stderr)
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def make_mesh(shape, axes, devices):
    """The mesh over the cell's devices, laid out by ``jax.make_mesh`` as
    the program's own meshes are."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def _marker():
    """A device op at each end of a traced window: it puts the window's
    bounds on the device timeline, and gives a host-only cell's trace the
    one device op that shows the chip was reached."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8, 128), jnp.float32)
    jax.block_until_ready(f(x))
    return lambda: jax.block_until_ready(f(x))


def _traced_window(cell, seconds: float, keep_trace: str | None):
    import jax

    from chipbench import devtrace
    from repro.obs import trace as obs_trace

    log_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-trace-")
    marker = _marker()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    obs_trace.enable(capacity=TRACER_CAPACITY)
    mark = obs_trace.TRACER.mark()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            marker()
            res = cell.window(seconds, traced=True)
            marker()
    finally:
        jax.profiler.stop_trace()
        spans = obs_trace.TRACER.records_since(mark)
        obs_trace.disable()
    try:
        tr = devtrace.load(devtrace.find_xplane(log_dir))
    finally:
        if not keep_trace:
            shutil.rmtree(log_dir, ignore_errors=True)
    return res, tr, spans


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, bench: dict | None = None,
             spec: dict | None = None, keep_trace: str | None = None) -> dict:
    """One run of one cell; returns the result object that is printed."""
    spec = spec or load_cell(name, bench)
    cell_cfg = spec["cell"]
    devs = devices_for(cell_cfg["chips"], require_tpu)
    entry = load_entry(spec["traffic"]["entry"])
    cell = entry.build(spec["config"], spec["traffic"], seed, devs)
    cell.setup()
    setup_s = process_age_s()
    print(f"[setup] {setup_s:.3f} s", file=sys.stderr)
    if trace:
        res, tr, spans = _traced_window(cell, seconds, keep_trace)
    else:
        res, tr, spans = cell.window(seconds, traced=False), None, None
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    cell.free()
    t_check = time.perf_counter()
    compared = cell.check()
    print(f"[check] {time.perf_counter() - t_check:.3f} s", file=sys.stderr)

    if trace:
        from chipbench import counts, devtrace

        ctx = types.SimpleNamespace(
            trace=tr, info=res["info"], spans=spans, chips=len(devs),
            peaks=counts.peaks(devs[0].device_kind) if require_tpu else None)
        metrics = {}
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        values = dict(res["metrics"], setup_s=setup_s)
        metrics = {k: {"value": values[k], "unit": u}
                   for k, u in units.items()}

    import jax

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": all(c.ok for c in compared) and res["failed"] == 0,
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = devtrace.busy_s(tr)
        device["window_s"] = tr.window_s
        out["breakdown"] = devtrace.breakdown(tr)
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in compared}
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profiler trace here and keep it")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chipbench: no program under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # without these the TPU library writes logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    # every program, however quick to compile, goes to the cache, so a
    # second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"[cache] {cache}", file=sys.stderr)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 3
    for name, c in out["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"compared {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
