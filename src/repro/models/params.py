"""Parameter metadata: single source of truth for shapes, logical axes and
initialization, consumed three ways:

* ``init_params``       — materialize arrays (smoke tests, real training),
* ``abstract_params``   — ShapeDtypeStructs (dry-run, AOT lowering),
* ``partition_specs``   — PartitionSpec pytree from logical-axis rules.

A parameter is described by :class:`ParamMeta` with per-dimension *logical
axis* names; sharding rules map logical axes to mesh axes, first-come
first-served (a mesh axis is used at most once per param) and only when the
dimension is divisible by the mesh axis size.  A rule's mesh axis may be a
tuple of axes, which shards the dim over their product (the expert-parallel
rule: ``experts`` over the data-parallel axes, see :func:`partition_specs`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

__all__ = [
    "ParamMeta",
    "init_params",
    "abstract_params",
    "partition_specs",
    "TP_RULES",
    "FSDP_RULES",
]


@dataclasses.dataclass(frozen=True)
class ParamMeta:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]  # logical axis name per dim
    init: str = "normal"  # normal | zeros | ones | conv
    scale: float | None = None  # None -> 1/sqrt(fan_in)

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def _is_meta(x) -> bool:
    return isinstance(x, ParamMeta)


def _tree_map_meta(fn: Callable, tree):
    return jax.tree.map(fn, tree, is_leaf=_is_meta)


def _init_one(meta: ParamMeta, key, dtype) -> jax.Array:
    if meta.init == "zeros":
        return jnp.zeros(meta.shape, dtype)
    if meta.init == "ones":
        return jnp.ones(meta.shape, dtype)
    if meta.init == "a_log":
        # mamba: A_log init = log(1..d_state) broadcast over channels
        d_state = meta.shape[-1]
        a = jnp.broadcast_to(
            jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32)), meta.shape
        )
        return a.astype(dtype)
    fan_in = meta.shape[0] if len(meta.shape) == 1 else int(np.prod(meta.shape[:-1]))
    scale = meta.scale if meta.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, meta.shape, jnp.float32) * scale).astype(dtype)


def init_params(meta_tree, rng: jax.Array, dtype=jnp.bfloat16):
    """Materialize a parameter pytree from its metadata tree."""
    leaves, treedef = jax.tree.flatten(meta_tree, is_leaf=_is_meta)
    keys = jax.random.split(rng, len(leaves))
    arrays = [_init_one(m, k, dtype) for m, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrays)


def abstract_params(meta_tree, dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree — no allocation; feeds .lower()."""
    return _tree_map_meta(
        lambda m: jax.ShapeDtypeStruct(m.shape, dtype), meta_tree
    )


# Logical-axis -> mesh-axis preferences, in priority order per axis.
# "model" = tensor-parallel axis; "data" = FSDP axis (params only).
TP_RULES: dict[str, tuple[str, ...]] = {
    "vocab": ("model",),
    "heads_flat": ("model",),  # flattened num_heads*head_dim projections
    "ff": ("model",),
    "experts": ("model",),
    "router": ("model",),  # the router's per-expert outputs
    "d_inner": ("model",),
    "lora": (),
    "d_model": (),
    "layers": (),  # stacked period dim never sharded
}

FSDP_RULES: dict[str, tuple[str, ...]] = {
    **TP_RULES,
    "d_model": ("data",),
    "lora": ("data",),
}


def _spec_for(meta: ParamMeta, rules: dict, mesh_axis_sizes: dict) -> PartitionSpec:
    used: set[str] = set()
    out: list[str | tuple[str, ...] | None] = []
    for dim, axis in zip(meta.shape, meta.axes):
        chosen = None
        for mesh_axis in rules.get(axis, ()) if axis else ():
            names = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
            sizes = [mesh_axis_sizes.get(a) for a in names]
            if (all(sizes) and used.isdisjoint(names)
                    and dim % math.prod(sizes) == 0):
                chosen = mesh_axis
                used.update(names)
                break
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def partition_specs(meta_tree, mesh_axis_sizes: dict[str, int], *,
                    fsdp: bool = True, ep: tuple[str, ...] = ()):
    """PartitionSpec pytree for the parameter tree.

    ``mesh_axis_sizes`` maps mesh axis name -> size, e.g. {"data": 16,
    "model": 16} (the "pod" axis never shards parameters: pods are pure DP
    replicas, which is what makes the paper's cross-pod collectives the
    interesting traffic).  ``ep`` names the data-parallel axes of an
    expert-parallel step: the ``experts`` dim is then sharded over all of
    them at once, in place of ``model``, and no other dim of an expert
    leaf takes those axes."""
    rules = FSDP_RULES if fsdp else TP_RULES
    if ep:
        rules = {**rules, "experts": (ep[0] if len(ep) == 1 else tuple(ep),)}
    return _tree_map_meta(lambda m: _spec_for(m, rules, mesh_axis_sizes), meta_tree)
