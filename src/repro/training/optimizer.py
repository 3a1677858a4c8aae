"""AdamW with ZeRO-1-style sharded moments.

Pure-functional: state is a pytree mirroring params.  Moment dtype is
configurable (bf16 moments for the >=200B configs keep the optimizer under
the v5e HBM budget; see DESIGN.md §5).  Sharding of the moments is applied
by the caller via ``partition_specs(..., fsdp=True)`` — the moments always
use the FSDP rules even when the params do not (that *is* ZeRO-1: optimizer
state sharded over the data axis).  The pjit path leaves the gathers
around the update to XLA; the shard_map step updates each chip's shard of
the leaves and gathers the new parameters."""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["OptConfig", "init_opt_state", "adamw_update", "clip_factor",
           "global_norm", "sum_squares"]


@dataclasses.dataclass(frozen=True)
class OptConfig:
    learning_rate: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    moment_dtype: str = "float32"


def init_opt_state(params, cfg: OptConfig) -> dict:
    dt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def sum_squares(leaves) -> jax.Array:
    """Sum of the squares of every element of ``leaves``, in f32."""
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum_squares(jax.tree.leaves(tree)))


def _schedule(cfg: OptConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    return cfg.learning_rate * warm


def clip_factor(cfg: OptConfig, gnorm: jax.Array) -> jax.Array:
    """What scales a gradient of global norm ``gnorm`` to a norm of at
    most ``cfg.grad_clip``."""
    if cfg.grad_clip == math.inf:
        return 1.0  # clips nothing, so nothing needs the norm
    return jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))


def adamw_update(grads, state, params, cfg: OptConfig):
    """Returns (new_params, new_state, info)."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    clip = clip_factor(cfg, gnorm)
    lr = _schedule(cfg, state["step"])
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    mdt = jnp.dtype(cfg.moment_dtype)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        m32 = m.astype(jnp.float32) * b1 + g * (1 - b1)
        v32 = v.astype(jnp.float32) * b2 + g * g * (1 - b2)
        mhat = m32 / bc1
        vhat = v32 / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(
            jnp.float32
        )
        newp = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return newp, m32.astype(mdt), v32.astype(mdt)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    info = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, info
