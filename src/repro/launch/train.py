"""Training driver: config -> mesh -> fault-tolerant train loop.

Usage (CPU-scale example; without --mesh the mesh is (1, n, 1) over the
n devices present):

  PYTHONPATH=src python -m repro.launch.train --arch yi_6b --smoke \\
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ck --mesh 2,2,2

Features exercised end-to-end: deterministic resumable data stream,
prefetch, async checkpointing with keep-last GC, straggler monitoring,
resume-from-latest, and the collective-backend switch (--backend fulllane
routes gradient sync through the paper's hierarchical collectives).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_test_mesh
from repro.models import lm
from repro.training import checkpoint as ckpt
from repro.training.data import Prefetcher, SyntheticLM
from repro.training.elastic import StragglerMonitor
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_step import (
    batch_pspec,
    make_train_step_pjit,
    make_train_step_shardmap,
)


def _place(tree, specs, mesh):
    """Put each leaf of ``tree`` where its partition spec says, so the
    compiled step receives its arguments sharded as it expects."""
    return jax.device_put(tree, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P)))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--num-layers", type=int, default=0,
                    help=">0: cut the depth to this many layers, widths kept")
    ap.add_argument("--mesh", default="",
                    help="comma shape, e.g. 2,2,2 (pod,data,model); default "
                         "(1, n, 1) over the n devices present")
    ap.add_argument("--backend", default="xla", choices=["xla", "fulllane"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--corpus-size", type=int, default=0,
                    help=">0: cycle over a fixed corpus (learnable target)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.num_layers:
        print(f"[train] depth cut: {cfg.name} num_layers {cfg.num_layers} -> "
              f"{args.num_layers}")
        cfg = dataclasses.replace(cfg, num_layers=args.num_layers)
    if args.backend != "xla":
        cfg = dataclasses.replace(
            cfg, parallel=dataclasses.replace(cfg.parallel, fsdp=False)
        )

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        mesh = make_test_mesh(shape, axes)
    else:
        mesh = make_host_mesh()
    print(f"[train] mesh {dict(mesh.shape)} backend={args.backend}")

    opt_cfg = OptConfig(learning_rate=args.lr,
                        moment_dtype=cfg.parallel.optimizer_dtype)
    params = lm.init_model(cfg, jax.random.PRNGKey(args.seed))
    opt_state = init_opt_state(params, opt_cfg)

    start_step = 0
    if args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            like = jax.eval_shape(lambda: {"params": params, "opt": opt_state})
            restored, extra = ckpt.restore(args.ckpt_dir, latest, like)
            params, opt_state = restored["params"], restored["opt"]
            start_step = latest
            print(f"[train] resumed from step {latest}")

    stream = Prefetcher(
        SyntheticLM(cfg, args.batch, args.seq, seed=args.seed,
                    start_step=start_step,
                    corpus_size=args.corpus_size or None),
        depth=2,
    )
    sample_batch = next(iter(SyntheticLM(cfg, args.batch, args.seq)))[1]
    if args.backend == "xla":
        mk, (pspec, ospec) = make_train_step_pjit(cfg, mesh, opt_cfg)
    else:
        mk, (pspec, ospec) = make_train_step_shardmap(cfg, mesh, opt_cfg,
                                                      backend=args.backend)
    bspec = batch_pspec(mesh, sample_batch)
    params = _place(params, pspec, mesh)
    opt_state = _place(opt_state, ospec, mesh)
    t0 = time.time()
    step_fn = mk(sample_batch).lower(params, opt_state, sample_batch).compile()
    compile_s = time.time() - t0
    print(f"[train] step compiled in {compile_s:.2f}s")

    saver = ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    monitor = StragglerMonitor()
    history, grad_norms = [], []
    t_total = time.time()
    for step, batch in stream:
        if step >= args.steps:
            break
        t0 = time.time()
        # one step of XProf's step view: dispatch to the loss read
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 _place(batch, bspec, mesh))
            loss = float(metrics["loss"])  # sync point
            gnorm = float(metrics["grad_norm"])
        dt = time.time() - t0
        action = monitor.observe(dt)
        if action != "ok":
            print(f"[train] step {step}: straggler action={action} "
                  f"({dt:.2f}s vs ema {monitor.ema:.2f}s)")
        history.append(loss)
        grad_norms.append(gnorm)
        if step % args.log_every == 0:
            print(f"[train] step {step:5d} loss={loss:.4f} "
                  f"gnorm={gnorm:.3f} {dt:.2f}s")
        if saver and step > start_step and step % args.ckpt_every == 0:
            saver.save(step, {"params": params, "opt": opt_state},
                       extra={"arch": args.arch})
    if saver:
        saver.wait()
    out = {"first_loss": history[0], "last_loss": history[-1],
           "steps": len(history), "seconds": time.time() - t_total,
           "compile_s": compile_s}
    print(f"[train] done: {out}")
    return {**out, "losses": history, "grad_norms": grad_norms,
            "compiled": step_fn}


if __name__ == "__main__":
    main()
