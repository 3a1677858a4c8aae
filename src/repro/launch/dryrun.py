import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

For each cell this produces (and records to JSON under experiments/dryrun/):

* proof of compilation on the production mesh (16x16 single-pod AND
  2x16x16 multi-pod — the latter proves the ``pod`` axis shards),
* ``compiled.memory_analysis()``  — per-device bytes (fits-in-HBM check),
* ``compiled.cost_analysis()``    — per-device FLOPs / bytes accessed,
* collective bytes parsed from the compiled (post-SPMD) HLO, per op kind,

which are exactly the §Roofline inputs.

Usage:
  python -m repro.launch.dryrun --arch yi_6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--backend xla]
  python -m repro.launch.dryrun --all --skip-existing   # resumable sweep
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ModelConfig, ShapeSpec
from repro.launch import specs as SP
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hloanalysis import analyze_module
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_step import (
    make_train_step_pjit,
    make_train_step_shardmap,
    opt_pspecs,
    param_pspecs,
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b"
)
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes of every collective op in the (post-SPMD, per-device)
    HLO module, grouped by op kind."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "=" not in line:
            continue
        kind = m.group(1)
        if "-done" in line.split("=")[1][:60]:
            continue  # the -done op re-mentions shapes already counted at -start
        # operand shapes: everything after the op name's opening paren
        call = line.split(m.group(0), 1)[1]
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(call))
        if total:
            out[kind] = out.get(kind, 0) + total
    return out


# ---------------------------------------------------------------------------
# Cell construction: returns (jitted_fn, abstract_args).
# ---------------------------------------------------------------------------


def optimized_config(cfg: ModelConfig, mesh) -> ModelConfig:
    """The beyond-baseline ParallelConfig (EXPERIMENTS.md §Perf): group-local
    MoE dispatch sized to the DP world, bf16 gradient accumulation for the
    >=100B configs."""
    import dataclasses
    import math as _m
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ndp = _m.prod(sizes[a] for a in ("pod", "data") if a in sizes)
    pl = dataclasses.replace(
        cfg.parallel,
        moe_groups=ndp,
        grad_dtype="bfloat16" if cfg.param_count() > 1e11 else
        cfg.parallel.grad_dtype,
    )
    return dataclasses.replace(cfg, parallel=pl)


def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *, backend: str = "xla"):
    opt_cfg = OptConfig(moment_dtype=cfg.parallel.optimizer_dtype)
    params = lm.abstract_model(cfg)

    if shape.kind == "train":
        batch = SP.batch_structs(cfg, shape.global_batch, shape.seq_len)
        opt = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params)
        if backend == "xla":
            mk, _ = make_train_step_pjit(cfg, mesh, opt_cfg)
        else:
            import dataclasses
            cfg2 = dataclasses.replace(
                cfg, parallel=dataclasses.replace(cfg.parallel, fsdp=False)
            )
            mk, _ = make_train_step_shardmap(cfg2, mesh, opt_cfg, backend=backend)
        return mk(batch), (params, opt, batch)

    pspec = param_pspecs(cfg, mesh)
    ns = SP.named(mesh, pspec)
    from repro.training.train_step import make_act_shard
    act = make_act_shard(cfg, mesh)

    if shape.kind == "prefill":
        batch = SP.batch_structs(cfg, shape.global_batch, shape.seq_len)
        bspec = SP.named(mesh, SP.batch_pspecs(mesh, batch))
        cache_struct = lm.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cspec = SP.named(mesh, SP.cache_pspecs(cfg, mesh, cache_struct))
        fn = jax.jit(
            lambda p, b: lm.prefill(cfg, p, b, capacity=shape.seq_len,
                                    act_shard=act),
            in_shardings=(ns, bspec),
            out_shardings=(None, cspec),
        )
        return fn, (params, batch)

    # decode (decode_32k, long_500k): one token against a full cache
    B, S = shape.global_batch, shape.seq_len
    cache_struct = lm.abstract_cache(cfg, B, S)
    cspec = SP.named(mesh, SP.cache_pspecs(cfg, mesh, cache_struct))
    tok = SP.decode_token_struct(cfg, B)
    tspec = SP.named(mesh, SP.batch_pspecs(mesh, tok))
    # decode batch may be too small to shard over DP (long_500k B=1): the
    # act hook would conflict; only pin when divisible.
    import math as _math
    ndp = _math.prod(
        dict(zip(mesh.axis_names, mesh.devices.shape))[a]
        for a in ("pod", "data") if a in mesh.axis_names
    )
    dec_act = act if B % ndp == 0 else None
    fn = jax.jit(
        lambda p, t, c, i: lm.decode_step(cfg, p, t, c, i, act_shard=dec_act),
        in_shardings=(ns, tspec, cspec, None),
        out_shardings=(None, cspec),
        donate_argnums=(2,),
    )
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (params, tok, cache_struct, pos)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, backend: str = "xla",
             opt: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = SP.cell_eligible(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "backend": backend,
        "params": cfg.param_count(), "params_active": cfg.param_count(True),
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if opt:
        cfg = optimized_config(cfg, mesh)
        rec["opt"] = True
    t0 = time.time()
    fn, args = build_cell(cfg, shape, mesh, backend=backend)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    hc = analyze_module(hlo)  # trip-count-aware (see hloanalysis.py)
    rec.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        num_devices=int(mesh.devices.size),
        flops_per_device=hc.flops,
        hbm_bytes_per_device=hc.hbm_bytes,
        collective_bytes_per_device=hc.collective_bytes,
        collective_bytes_total=hc.collective_total,
        raw_cost_analysis={
            "flops_once": float(cost.get("flops", 0.0)),
            "bytes_accessed_once": float(cost.get("bytes accessed", 0.0)),
        },
        num_whiles=hc.num_whiles,
        unknown_trip_whiles=hc.unknown_trip_whiles,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + [a.replace("_", "-") for a in ARCH_IDS])
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--backend", default="xla", choices=["xla", "fulllane"])
    ap.add_argument("--opt", action="store_true",
                    help="optimized ParallelConfig (EXPERIMENTS.md §Perf)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    archs = ARCH_IDS if args.all else [args.arch.replace("-", "_")]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out_dir, exist_ok=True)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape}__{mesh_kind}"
                if args.backend != "xla":
                    tag += f"__{args.backend}"
                if args.opt:
                    tag += "__opt"
                path = os.path.join(args.out_dir, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] {tag}: exists, skipping")
                    continue
                try:
                    rec = run_cell(arch, shape, mesh_kind, backend=args.backend,
                                   opt=args.opt)
                except Exception as e:  # a failing cell is a bug — record it
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                    failures.append(tag)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    gb = (rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]) / 2**30
                    extra = (f" flops/dev={rec['flops_per_device']:.3g}"
                             f" coll={rec['collective_bytes_total']/2**20:.1f}MiB"
                             f" mem={gb:.2f}GiB"
                             f" compile={rec['compile_s']}s")
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")


if __name__ == "__main__":
    main()
