#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: plan, kernel, train
    python chip_smoke.py --chips 4    # one 2x2 host: executed collectives
                                      # and the train step, each against XLA

One chip runs these phases in one process:

* plan   -- ``repro.api.plan_batch`` for broadcast, scatter and alltoall at
            1 KiB, 4 MiB and 64 MiB per chip, on the v5e 2x2 host and on the
            paper's 36x32 machine with k=2.  Every plan's schedule is
            materialised and checked by the data-flow oracle, first cold,
            then again after a warm start from a fresh artifact store.
* kernel -- the ``a2a_pack`` Pallas kernel compiled for the chip at 4 MiB
            and 64 MiB per chip, bit-exact against ``kernels/ref.py``.
* train  -- ``repro.launch.train.main`` on h2o-danube-3-4b at its published
            widths, depth cut to 2 layers, gradient sync through
            ``hierarchical_psum``.

``--chips 4`` runs only what exists across chips, on a 2x2 ("pod", "lane")
mesh at 4 MiB and 64 MiB per chip: ``hierarchical_psum``,
``fulllane_all_to_all``, ``fulllane_broadcast`` and the k-ported ppermute
broadcast and scatter against their references, then the 2-layer train step
with ``--backend fulllane`` against ``--backend xla`` on the same batches.

"Per chip" is the size of each chip's buffer.  The last line of output is one
JSON object naming the device; any failure exits non-zero without it, and a
host whose first device is not a TPU fails before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
import traceback
from collections import Counter
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
KIB, MIB = 1 << 10, 1 << 20
OPS = ("broadcast", "scatter", "alltoall")
# (num_nodes, procs_per_node, k_lanes): the v5e 2x2 host, the paper's machine
PLAN_MESHES = ((2, 2, 2), (36, 32, 2))
TRAIN_ARGS = ["--arch", "h2o_danube_3_4b", "--num-layers", "2",
              "--seq", "1024", "--steps", "5", "--log-every", "1"]
# How far the fulllane and xla trajectories may differ, relative.  The
# gradient norm of every step, step 0 included, is what the gradient sync
# produces; a sync that drops an axis or doubles its sum moves it by 30% or
# more, while AdamW's scale-free update hides both from the loss.
LOSS_RTOL = 1e-4
GNORM_RTOL = 1e-2
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


class SmokeError(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def collectives(hlo: str) -> dict:
    """Collective op names in compiled HLO text, with their counts."""
    return dict(sorted(Counter(_COLLECTIVE.findall(hlo)).items()))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_plan(sizes=(KIB, 4 * MIB, 64 * MIB), meshes=PLAN_MESHES) -> None:
    from repro import api
    from repro.core.schedule_ir import schedule_cache_clear
    from repro.core.selector import selector_cache_reset
    from repro.core.validate import validate_schedule
    from repro.store import ArtifactStore

    reqs = []
    for nn, ppn, k in meshes:
        for op in OPS:
            for nbytes in sizes:
                elems = nbytes // 4  # f32
                # broadcast plans the whole buffer; scatter and alltoall
                # plan the block each destination gets from it
                c = elems if op == "broadcast" else max(1, elems // (nn * ppn))
                reqs.append(api.PlanRequest(op, c, num_nodes=nn,
                                            procs_per_node=ppn, k_lanes=k))

    def plan_all():
        t0 = time.perf_counter()
        out = [(p, p.schedule()) for p in api.plan_batch(reqs)]
        return out, time.perf_counter() - t0

    root = tempfile.mkdtemp(prefix="chip_smoke_store_")
    try:
        store = ArtifactStore(root)
        schedule_cache_clear()
        selector_cache_reset()
        cold, cold_s = plan_all()
        for (plan, cs), nbytes in zip(cold, sizes * (len(reqs) // len(sizes))):
            r = plan.request
            validate_schedule(cs, raise_on_error=True)
            print(f"[plan] {r.num_nodes}x{r.procs_per_node} k={r.k_lanes} "
                  f"{r.op:9s} {nbytes:>9d} B/chip c={r.payload_elems:<8d} "
                  f"-> {plan.algorithm:16s} rounds={cs.num_rounds} "
                  f"msgs={cs.num_msgs}")
        store.persist_cache()
        schedule_cache_clear()
        selector_cache_reset()
        report = store.warm_start()
        warm, warm_s = plan_all()
        check([p.algorithm for p, _ in warm] == [p.algorithm for p, _ in cold],
              "warm plans differ from cold plans")
        for (_, a), (_, b) in zip(cold, warm):
            check(a.num_msgs == b.num_msgs and a.num_rounds == b.num_rounds,
                  "warm schedule differs from cold schedule")
        print(f"[plan] {len(reqs)} plans: cold {cold_s:.3f}s, warm "
              f"{warm_s:.3f}s after loading {report['schedules']} schedules")
    finally:
        shutil.rmtree(root, ignore_errors=True)


def phase_kernel(sizes=(4 * MIB, 64 * MIB), d=512) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    for i, nbytes in enumerate(sizes):
        shape = (2, 2, nbytes // (2 * 2 * d * 4), d)
        x = jax.random.normal(jax.random.key(i), shape, jnp.float32)
        compiled = ops.a2a_pack.lower(x).compile()
        check("tpu_custom_call" in compiled.as_text(),
              "a2a_pack did not compile to a Pallas TPU kernel")
        got = compiled(x)
        jax.block_until_ready(got)
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(x))
        dt = time.perf_counter() - t0
        exact = bool(jnp.array_equal(got, ref.a2a_pack_ref(x)))
        print(f"[kernel] a2a_pack {list(shape)} f32 ({nbytes} B/chip): "
              f"tpu_custom_call present, bit-exact={exact}, "
              f"one warm call {dt * 1e3:.3f} ms")
        check(exact, f"a2a_pack differs from a2a_pack_ref at {shape}")


def phase_train(dev, argv) -> None:
    from repro.launch import train

    out = train.main(argv)
    losses = out["losses"]
    stats = dev.memory_stats() or {}
    print(f"[train] losses {losses}")
    print(f"[train] compile {out['compile_s']:.3f}s, peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    check(len(losses) == 5 and all(math.isfinite(v) for v in losses),
          f"expected 5 finite losses, got {losses}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_collectives(mesh, sizes=(4 * MIB, 64 * MIB)) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import collectives as C

    axes = ("pod", "lane")
    spec = P(axes)
    sharding = NamedSharding(mesh, spec)
    p, ni = mesh.size, mesh.shape["lane"]

    def data(seed, shape):
        # small integers in f32: every sum is exact in any order, so each
        # comparison below must hold bit for bit
        make = jax.jit(
            lambda k: jax.random.randint(k, shape, -8, 8).astype(jnp.float32),
            out_shardings=sharding)
        return make(jax.random.key(seed))

    def run(f, x):
        fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                   out_specs=spec))
        compiled = fn.lower(x).compile()
        return compiled(x), collectives(compiled.as_text())

    def report(name, nbytes, got, want, hlo):
        err = float(jnp.max(jnp.abs(got - want)))
        print(f"[collectives] {name:28s} {nbytes:>9d} B/chip max|err|={err} "
              f"hlo={hlo}")
        check(err == 0.0, f"{name} at {nbytes} B/chip: max error {err}")

    for nbytes in sizes:
        n = nbytes // 4  # f32 elements per chip

        x = data(0, (p, n))
        got, hlo = run(lambda v: C.hierarchical_psum(v, *axes), x)
        want, _ = run(lambda v: jax.lax.psum(v, axes), x)
        report("hierarchical_psum/psum", nbytes, got, want, hlo)

        x = data(1, (p * p, n // p))
        got, hlo = run(lambda v: C.fulllane_all_to_all(v, *axes), x)
        want, _ = run(lambda v: jax.lax.all_to_all(v, axes, 0, 0, tiled=True),
                      x)
        report("fulllane_all_to_all/a2a", nbytes, got, want, hlo)

        # root pod 0 holds the payload, sharded over its lanes; the other
        # pods hold noise that must not leak into the result
        x = data(2, (p, n // ni))
        got, hlo = run(lambda v: C.fulllane_broadcast(v[0], *axes)[None], x)
        want = jnp.broadcast_to(x[:ni].reshape(1, n), (p, n))
        report("fulllane_broadcast/root", nbytes, got, want, hlo)

        for k in (1, 2):
            x = data(3, (p, n))
            got, hlo = run(
                lambda v: C.kported_broadcast_ppermute(v[0], axes, k=k)[None],
                x)
            want = jnp.broadcast_to(x[:1], (p, n))
            report(f"kported_broadcast k={k}/root", nbytes, got, want, hlo)

            x = data(4, (p * p, n // p))
            got, hlo = run(
                lambda v: C.kported_scatter_ppermute(v, axes, k=k)[None], x)
            report(f"kported_scatter k={k}/root", nbytes, got, x[:p], hlo)


def phase_train_backends(argv) -> None:
    from repro.launch import train

    runs = {}
    for backend in ("fulllane", "xla"):
        out = train.main(argv + ["--backend", backend])
        runs[backend] = out
        print(f"[train-4] {backend}: losses {out['losses']}, grad norms "
              f"{out['grad_norms']}, compile {out['compile_s']:.3f}s, "
              f"hlo={collectives(out['compiled'].as_text())}")
    a, b = runs["fulllane"], runs["xla"]
    limits = {"losses": LOSS_RTOL, "grad_norms": GNORM_RTOL}
    rel = {}
    for key, limit in limits.items():
        x, y = a[key], b[key]
        check(len(x) == len(y) == 5 and all(map(math.isfinite, x + y)),
              f"expected 5 finite {key} per backend")
        rel[key] = max(abs(u - v) / abs(v) for u, v in zip(x, y))
        print(f"[train-4] fulllane vs xla: max relative {key} difference "
              f"{rel[key]} (limit {limit})")
    for key, limit in limits.items():
        check(rel[key] <= limit, f"fulllane and xla {key} disagree")


# ---------------------------------------------------------------------------


def run_phases(phases) -> list[str]:
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
        else:
            print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # Without these the TPU library writes log files to fixed directories
    # under /tmp, outside the checkout; its errors still reach Python.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}; no phase run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    if args.chips == 1:
        phases = [
            ("plan", phase_plan),
            ("kernel", phase_kernel),
            ("train", lambda: phase_train(
                dev, TRAIN_ARGS + ["--backend", "fulllane", "--batch", "8"])),
        ]
    else:
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((2, 2), ("pod", "lane"))
        # 8 sequences per chip, as on one chip; "pod" x "data" is 2x2
        phases = [
            ("collectives", lambda: phase_collectives(mesh)),
            ("train-4", lambda: phase_train_backends(
                TRAIN_ARGS + ["--batch", "32", "--mesh", "2,2,1"])),
        ]
    failed = run_phases(phases)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
