"""Train-step factories: the pjit (GSPMD-auto) path and the explicit
shard_map path that routes gradient synchronization through the paper's
collective families.

* ``make_train_step_pjit`` — the production default.  Parameters are
  sharded by the logical-axis rules (TP over ``model``; FSDP over ``data``
  when enabled); XLA inserts all collectives.  Handles every assigned
  architecture including the >=200B FSDP configs.

* ``make_train_step_shardmap`` — the paper-integrated path: manual over the
  data-parallel axes (``pod``, ``data``), GSPMD-auto over ``model``.
  Gradient sync is explicit and backend-switched:

    backend="xla"       : flat ``psum`` over the merged DP axes — the
                          single-phase k-ported-style baseline;
    backend="fulllane"  : ``hierarchical_psum`` — reduce-scatter intra-pod,
                          all-reduce across pods, all-gather intra-pod (the
                          paper's §2.2 problem splitting on the TPU mesh).
                          Requires a multi-pod mesh; on a single pod it
                          coincides with the flat form (documented).

  The AdamW moments stay sharded over ``data`` (ZeRO-1) through the step:
  each gradient leaf is reduced down to the moment tile this chip owns
  (``hierarchical_reduce_scatter`` on ``fulllane``), AdamW updates that
  tile, and the new parameters are all-gathered.  A model with MoE layers
  runs expert-parallel over the DP axes: each chip owns a share of the
  experts, the layers exchange tokens with ``fulllane_all_to_all``, and the
  expert leaves skip the sync, the tiles and the gather.

  The dry-run lowers both and diffs collective bytes (EXPERIMENTS.md §Perf).

Both support gradient accumulation (``parallel.microbatches``) via
``lax.scan`` with fp32 accumulators; remat comes from the model's
period-scan checkpoint policy.

Each step's stages run under ``jax.named_scope``s: ``grad`` (forward,
backward, the micro-batch scan), ``sync`` (the shard_map path's gradient
and metric reductions, and its ``param_gather``) and ``optimizer``
(``adamw_update``), so a device op's HLO ``op_name`` names its stage; ops
the partitioner inserts at the ``shard_map`` boundary fall outside all
three.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import collectives as C
from repro.models import lm
from repro.models import moe as moe_mod
from repro.models.params import ParamMeta, partition_specs
from repro.obs.trace import TRACER
from repro.training.optimizer import (OptConfig, adamw_update, clip_factor,
                                      init_opt_state, sum_squares)


__all__ = [
    "dp_axes",
    "mesh_axis_sizes",
    "batch_pspec",
    "param_pspecs",
    "opt_pspecs",
    "make_train_step_pjit",
    "make_train_step_shardmap",
]


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_pspec(mesh: Mesh, batch_tree) -> dict:
    """Shard every batch leaf's leading (batch) dim over the DP axes."""
    dp = dp_axes(mesh)
    return jax.tree.map(lambda _: P(dp), batch_tree)


def param_pspecs(cfg: ModelConfig, mesh: Mesh, ep: tuple[str, ...] = ()):
    """``ep``: the expert-parallel axes the ``experts`` dim shards over."""
    sizes = mesh_axis_sizes(mesh)
    return partition_specs(lm.model_meta(cfg), sizes, fsdp=cfg.parallel.fsdp,
                           ep=ep)


def opt_pspecs(cfg: ModelConfig, mesh: Mesh, ep: tuple[str, ...] = ()):
    """ZeRO-1: moments always use the FSDP rules regardless of param FSDP;
    an expert leaf's moments follow its ``ep`` sharding."""
    sizes = mesh_axis_sizes(mesh)
    mom = partition_specs(lm.model_meta(cfg), sizes, fsdp=True, ep=ep)
    return {"m": mom, "v": mom, "step": P()}


def _micro_split(batch, n: int):
    return jax.tree.map(lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def make_act_shard(cfg: ModelConfig, mesh: Mesh):
    """Activation-sharding hook: pins the leading (batch) dim of the
    residual stream to the DP axes.  Without it GSPMD drifts into
    feature-dim sharding inside the layer scan (replicating the microbatch
    across the whole data axis — observed 16x redundant compute and
    multi-hundred-GiB per-device all-reduces in the dry-run HLO).

    ``act(x)`` pins dim 0 to the DP axes; ``act(x, spec)`` pins an explicit
    spec (tuple of mesh-axis names / "dp" / None per dim) — used by the MoE
    layer to keep its group-local [G, E, C, D] dispatch buffers sharded
    G-over-DP, E-over-model (§Perf iteration 2)."""
    dp = dp_axes(mesh)

    def act(x, spec=None):
        if spec is None:
            pspec = P(dp, *([None] * (x.ndim - 1)))
        else:
            resolved = tuple(dp if s == "dp" else s for s in spec)
            pspec = P(*resolved)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))

    return act


def _grad_and_metrics(cfg: ModelConfig, params, batch, act_shard=None):
    """(grads fp32, metrics) with gradient accumulation if configured."""
    n = max(cfg.parallel.microbatches, 1)

    def loss_of(p, b):
        loss, metrics = lm.loss_fn(cfg, p, b, act_shard=act_shard)
        return loss, metrics

    gdt = jnp.dtype(cfg.parallel.grad_dtype)
    gfn = jax.value_and_grad(loss_of, has_aux=True)
    if n == 1:
        (_, metrics), grads = gfn(params, batch)
        return jax.tree.map(lambda g: g.astype(gdt), grads), metrics

    mb = _micro_split(batch, n)
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, gdt), params)
    counts = lm.counter_names(cfg)
    m0 = {"loss": 0.0, "nll": 0.0, "aux": 0.0, **{k: 0.0 for k in counts}}
    m0 = jax.tree.map(jnp.float32, m0)

    def body(carry, b):
        gacc, macc = carry
        (_, metrics), grads = gfn(params, b)
        gacc = jax.tree.map(lambda a, g: a + g.astype(gdt) / n, gacc, grads)
        # means over the micro-batches; the counters are summed
        macc = {k: macc[k] + (metrics[k] if k in counts else metrics[k] / n)
                for k in sorted(macc)}
        return (gacc, macc), None

    (grads, metrics), _ = jax.lax.scan(body, (g0, m0), mb)
    return grads, metrics


# ---------------------------------------------------------------------------
# pjit path.
# ---------------------------------------------------------------------------


def make_train_step_pjit(cfg: ModelConfig, mesh: Mesh, opt_cfg: OptConfig):
    """Returns (step_fn, shardings) where step_fn is jit-with-shardings and
    ``shardings = (params, opt, batch_fn)`` for placing real data."""
    if cfg.parallel.ep_axes:
        raise ValueError("parallel.ep_axes is the shard_map step's own: "
                         "the pjit step has no shard_map to exchange in")
    pspec = param_pspecs(cfg, mesh)
    ospec = opt_pspecs(cfg, mesh)
    ns = lambda spec_tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )

    act = make_act_shard(cfg, mesh)

    def step(params, opt_state, batch):
        with jax.named_scope("grad"):
            grads, metrics = _grad_and_metrics(cfg, params, batch,
                                               act_shard=act)
        with jax.named_scope("optimizer"):
            params, opt_state, info = adamw_update(grads, opt_state, params,
                                                   opt_cfg)
        return params, opt_state, {**metrics, **info}

    def jitted(batch_tree):
        bspec = batch_pspec(mesh, batch_tree)
        return jax.jit(
            step,
            in_shardings=(ns(pspec), ns(ospec), ns(bspec)),
            out_shardings=(ns(pspec), ns(ospec), None),
            donate_argnums=(0, 1),
        )

    return jitted, (pspec, ospec)


# ---------------------------------------------------------------------------
# shard_map (paper-collective) path.
# ---------------------------------------------------------------------------


def ep_axes(cfg: ModelConfig, mesh: Mesh) -> tuple[str, ...]:
    """The DP axes a MoE model's experts shard over in the shard_map step:
    all of them if the experts held divide over their chips, else ``data``
    or ``pod`` alone if they do; () keeps every expert on every chip (a
    dense model, one chip, or no DP axes that fit)."""
    if cfg.moe is None:
        return ()
    sizes = mesh_axis_sizes(mesh)
    dp = dp_axes(mesh)
    for axes in (dp, *((a,) for a in reversed(dp))):
        n = math.prod(sizes[a] for a in axes)
        if n > 1 and cfg.moe.experts_held % n == 0:
            return axes
    return ()


def _shard_dim(spec: P, axis: str | None) -> int | None:
    """The dim that ``spec`` shards over mesh axis ``axis``, or None."""
    if axis is not None:
        for d, a in enumerate(spec):
            if a == axis:
                return d
    return None


def make_train_step_shardmap(
    cfg: ModelConfig, mesh: Mesh, opt_cfg: OptConfig, *, backend: str = "fulllane"
):
    """Explicit DP with backend-switched gradient sync.  Params are
    replicated over the DP axes (TP over ``model`` still applies via the
    outer jit shardings); requires ``cfg.parallel.fsdp == False``.

    ZeRO-1 inside the step: a leaf whose moment spec shards a dim over
    ``data`` keeps its moments sharded through the ``shard_map``; its
    gradient is reduced only down to this chip's tile of that dim
    (``hierarchical_reduce_scatter`` on ``fulllane``, ``psum`` then a
    slice on ``xla``) and clipped by the whole gradient's norm, AdamW
    updates the tile of the parameter, and the new bf16 tiles are
    all-gathered over ``data`` (scope ``sync/param_gather``).  Any other
    leaf is synced, updated and kept whole.  The build records
    ``train_step.zero1`` with the leaf count, the leaves on the sharded
    path and their share of parameter bytes as an event of the tracer.

    Expert parallelism, for a config with MoE layers: the ``experts`` dim
    of every expert leaf is sharded over the EP axes of :func:`ep_axes`,
    inside the ``shard_map`` and out, and the layers exchange their tokens
    over those axes (:mod:`repro.models.moe`, through
    ``fulllane_all_to_all`` on ``fulllane`` over two axes).  An expert
    leaf's gradient is summed only over the DP axes left out of EP (none
    when EP takes them all): the exchange's transpose has already brought
    every EP chip's tokens to the chip that owns the expert.  Its moments
    stay local, its squares join the clip norm through a ``psum`` over the
    EP axes, and it is not gathered.  Where no EP axes fit, the experts
    are replicated and take the path of every other leaf.  Building the
    step for a batch records ``train_step.ep`` (``expert_leaves``,
    ``experts_per_chip``, ``capacity`` and the ``dispatch_bytes`` of one
    exchange per chip); ``train_step.zero1`` counts no expert leaf of the
    EP path as sharded.  The step's metrics then carry the
    layers' ``moe_dropped`` and ``moe_routed``, summed over the DP axes."""
    if cfg.parallel.fsdp:
        raise ValueError("shard_map path requires fsdp=False (replicated DP params)")
    dp = dp_axes(mesh)
    ndp = 1
    for a in dp:
        ndp *= mesh_axis_sizes(mesh)[a]
    ep = ep_axes(cfg, mesh)
    # DP axes the experts are replicated over: their gradients sum there
    rest = tuple(a for a in dp if a not in ep)
    nep = math.prod(mesh_axis_sizes(mesh)[a] for a in ep)
    cfg = dataclasses.replace(cfg, parallel=dataclasses.replace(
        cfg.parallel, ep_axes=ep, collective_backend=backend))
    pspec = param_pspecs(cfg, mesh, ep=ep)  # model-axis sharding via outer jit
    ospec = opt_pspecs(cfg, mesh, ep=ep)
    is_spec = lambda x: isinstance(x, P)
    # The step clips by the whole gradient's norm, which tiles do not give.
    no_clip = dataclasses.replace(opt_cfg, grad_clip=math.inf)
    # Per leaf, the dim sharded over the EP axes (None: no expert leaf) and
    # the dim its moments shard over ``data`` (None: whole leaf).
    # a PartitionSpec entry: one axis by its name, several as a tuple
    ep_entry = ep[0] if len(ep) == 1 else ep
    ep_dims = [_shard_dim(s, ep_entry) if ep else None
               for s in jax.tree.leaves(pspec, is_leaf=is_spec)]
    shard_axis = "data" if "data" in dp else None
    dims = [None if e is not None else _shard_dim(s, shard_axis)
            for s, e in zip(jax.tree.leaves(ospec["m"], is_leaf=is_spec),
                            ep_dims)]
    sizes = [math.prod(m.shape) for m in jax.tree.leaves(
        lm.model_meta(cfg), is_leaf=lambda x: isinstance(x, ParamMeta))]
    TRACER.event("train_step.zero1", leaves=len(dims),
                 sharded_leaves=sum(d is not None for d in dims),
                 sharded_bytes_share=sum(
                     n for n, d in zip(sizes, dims) if d is not None)
                 / sum(sizes))
    counts = lm.counter_names(cfg)

    def sync(g):
        if backend == "fulllane" and len(dp) == 2:
            return C.hierarchical_psum(g, dp[0], dp[1])
        if backend == "fulllane" and len(dp) == 1:
            # single-pod: RS+AG over the one axis == flat psum; keep explicit
            return jax.lax.psum(g, dp)
        return jax.lax.psum(g, dp)

    def local(x, d):
        """This chip's tile of a replicated leaf along dim ``d``."""
        n = x.shape[d] // jax.lax.axis_size(shard_axis)
        return jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(shard_axis) * n, n, axis=d)

    def sync_to_shard(g, d):
        if d is None:
            return sync(g)
        if backend == "fulllane" and len(dp) == 2:
            return C.hierarchical_reduce_scatter(g, dp[0], dp[1], d)
        if backend == "fulllane":
            return jax.lax.psum_scatter(g, shard_axis, scatter_dimension=d,
                                        tiled=True)
        return local(jax.lax.psum(g, dp), d)

    def sync_expert(g):
        return jax.lax.psum(g, rest) if rest else g

    def step(params, opt_state, batch):
        with jax.named_scope("grad"):
            grads, metrics = _grad_and_metrics(cfg, params, batch)
        flat_p, treedef = jax.tree.flatten(params)
        with jax.named_scope("sync"):
            flat_g = [(sync_expert(g) if e is not None
                       else sync_to_shard(g, d)) / ndp
                      for g, d, e in zip(jax.tree.leaves(grads), dims,
                                         ep_dims)]
            summed = {k: metrics.pop(k) for k in counts}
            metrics = jax.tree.map(lambda v: jax.lax.psum(v, dp) / ndp,
                                   metrics)
            metrics.update({k: jax.lax.psum(v, dp) for k, v in summed.items()})
        with jax.named_scope("optimizer"):
            flat_p = [p if d is None else local(p, d)
                      for p, d in zip(flat_p, dims)]
            # The clip's norm is the whole gradient's: a tile's squares are
            # summed over ``data`` with the other tiles, an expert leaf's
            # over the EP axes with the other chips' experts, a whole
            # leaf's counted once.  The tiles are clipped here, and AdamW
            # updates them with no clip of its own.
            parts = []
            tiles = [g for g, d in zip(flat_g, dims) if d is not None]
            if tiles:
                parts.append(jax.lax.psum(sum_squares(tiles), shard_axis))
            whole = [g for g, d, e in zip(flat_g, dims, ep_dims)
                     if d is None and e is None]
            if whole:
                parts.append(sum_squares(whole))
            experts = [g for g, e in zip(flat_g, ep_dims) if e is not None]
            if experts:
                parts.append(jax.lax.psum(sum_squares(experts), ep))
            gnorm = jnp.sqrt(sum(parts))
            clip = clip_factor(opt_cfg, gnorm)
            flat_g = [g * clip for g in flat_g]
            params, opt_state, info = adamw_update(
                jax.tree.unflatten(treedef, flat_g), opt_state,
                jax.tree.unflatten(treedef, flat_p), no_clip)
            info = {**info, "grad_norm": gnorm}
        with jax.named_scope("sync"), jax.named_scope("param_gather"):
            params = jax.tree.unflatten(treedef, [
                p if d is None else jax.lax.all_gather(
                    p, shard_axis, axis=d, tiled=True)
                for p, d in zip(jax.tree.leaves(params), dims)])
        return params, opt_state, {**metrics, **info}

    ns = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=is_spec
    )

    def ep_spec(e):
        return P(*[None] * e, ep_entry)

    spec_tree = jax.tree.structure(ospec["m"], is_leaf=is_spec)
    # Inside the shard_map the parameters are whole but for the expert
    # leaves, and the moments keep their ``data`` or EP dim sharded.
    pspec_in = jax.tree.unflatten(
        spec_tree, [P() if e is None else ep_spec(e) for e in ep_dims])
    mom = jax.tree.unflatten(spec_tree, [
        ep_spec(e) if e is not None
        else P() if d is None else P(*[None] * d, shard_axis)
        for d, e in zip(dims, ep_dims)])
    ospec_in = {"m": mom, "v": mom, "step": P()}
    metric_spec = {k: P() for k in ("loss", "nll", "aux", "grad_norm", "lr")
                   + counts}

    def jitted(batch_tree):
        if ep:
            labels = batch_tree["labels"]
            rows = labels.shape[0] // ndp // max(cfg.parallel.microbatches, 1)
            cap = moe_mod.capacity(rows * labels.shape[1], cfg.moe)
            TRACER.event(
                "train_step.ep",
                expert_leaves=sum(e is not None for e in ep_dims),
                experts_per_chip=cfg.moe.experts_held // nep, capacity=cap,
                dispatch_bytes=cfg.moe.experts_held * cap * cfg.d_model
                * jnp.dtype(cfg.dtype).itemsize)
        bspec_in = jax.tree.map(lambda _: P(dp), batch_tree)
        inner = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(pspec_in, ospec_in, bspec_in),
            out_specs=(pspec_in, ospec_in, metric_spec),
            axis_names=set(dp),
            check_vma=False,
        )
        return jax.jit(
            inner,
            in_shardings=(ns(pspec), ns(ospec), ns(bspec_in)),
            out_shardings=(ns(pspec), ns(ospec), None),
            donate_argnums=(0, 1),
        )

    return jitted, (pspec, ospec)
