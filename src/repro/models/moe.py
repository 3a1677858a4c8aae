"""Mixture-of-Experts FFN with capacity-based token dispatch, data-parallel
or expert-parallel.

The dispatch/combine data movement here is *the paper's alltoall*.  Two
layouts share one layer:

* Data-parallel (``parallel.ep_axes == ()``; the pjit path, and the
  shard_map step where no EP axes fit): every expert lives on every chip,
  or is sharded over ``model`` by GSPMD, which partitions the group-local
  ``[G, E, C, D]`` buffers itself.
* Expert-parallel (``parallel.ep_axes``, which only the shard_map train
  step of :mod:`repro.training.train_step` sets, for its ``shard_map``,
  to axes of more than one chip): each chip holds ``experts_held / P``
  experts of the ``P`` chips of the EP axes, routes its own tokens over
  all ``num_experts``, packs a ``[P, E_local, C, D]`` buffer by
  destination chip, exchanges it with
  ``repro.core.collectives.fulllane_all_to_all`` (``lax.all_to_all`` on
  the ``xla`` backend or a one-axis group), runs its experts on what it
  received from every chip, and sends the results back the same way for
  the combine.

Assignments to experts the model does not hold (``num_experts_held <
num_experts``: the one-chip share of an EP group) are left out of the
result.

Routing (DeepSeek-V2 and the rest alike): the gate in float32, softmax,
greedy top-k; the weights renormalised only where ``norm_topk_prob``, then
scaled by ``routed_scaling_factor``.  Capacity ``C = max(ceil(T * k / E *
cf), k)`` per source group (a chip, under EP) and expert; assignments past
``C`` are dropped in token-major order and fall back to the residual
stream.  The balance loss is Switch-style on the top-1 choice, or, where
``seq_aux``, DeepSeek's expert-level loss per sequence over all k choices.

Stages run under ``jax.named_scope``s ``moe/{route,dispatch,experts,
combine,shared}``; the exchange's ``fulllane_all_to_all/{intra,cross_pod}``
scopes sit inside ``dispatch`` and ``combine``.  The layer returns, beside
its output, the balance loss and two counts: routed assignments to held
experts (``moe_routed``) and those of them dropped by capacity
(``moe_dropped``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core import collectives as C
from repro.models.params import ParamMeta

__all__ = ["moe_meta", "moe", "capacity", "dense_ffn_flops"]


def moe_meta(cfg: ModelConfig) -> dict:
    e = cfg.moe
    d = cfg.d_model
    f = e.d_ff_expert
    n = e.experts_held
    out = {
        # the router's outputs cover every expert, held or not: never sharded
        # with the experts' weights
        "router": ParamMeta((d, e.num_experts), ("d_model", "router")),
        "w_gate": ParamMeta((n, d, f), ("experts", "d_model", "ff")),
        "w_up": ParamMeta((n, d, f), ("experts", "d_model", "ff")),
        "w_down": ParamMeta((n, f, d), ("experts", "ff", "d_model")),
    }
    if e.num_shared_experts:
        fs = f * e.num_shared_experts
        out["shared_gate"] = ParamMeta((d, fs), ("d_model", "ff"))
        out["shared_up"] = ParamMeta((d, fs), ("d_model", "ff"))
        out["shared_down"] = ParamMeta((fs, d), ("ff", "d_model"))
    return out


def capacity(tokens: int, e: MoEConfig) -> int:
    """Slots per expert for ``tokens`` tokens of one source group."""
    return max(math.ceil(tokens * e.top_k / e.num_experts * e.capacity_factor),
               e.top_k)


def _balance_loss(e: MoEConfig, probs, gate_i, B: int, S: int):
    E, K = e.num_experts, e.top_k
    if e.seq_aux:
        # DeepSeek-V2: per sequence, each expert's share of the S*K
        # selections (times E) against its mean gate probability
        counts = jax.nn.one_hot(gate_i.reshape(B, S * K), E,
                                dtype=jnp.float32).sum(1)
        ce = counts / (S * K / E)
        return jnp.mean(jnp.sum(ce * probs.reshape(B, S, E).mean(1), -1)) \
            * e.router_aux_weight
    # Switch: E * sum_e f_e * P_e on the top-1 choice
    f_e = jax.nn.one_hot(gate_i[..., 0], E, dtype=jnp.float32).mean((0, 1))
    P_e = probs.mean((0, 1))
    return E * jnp.sum(f_e * P_e) * e.router_aux_weight


def _exchange(buf, cfg: ModelConfig):
    """``buf[d]`` of each chip to chip ``d`` of the EP axes; block ``s`` of
    the result came from chip ``s``."""
    axes = cfg.parallel.ep_axes
    if cfg.parallel.collective_backend == "fulllane" and len(axes) == 2:
        return C.fulllane_all_to_all(buf, axes[0], axes[1])
    return jax.lax.all_to_all(buf, axes, 0, 0, tiled=True)


def moe(cfg: ModelConfig, p: dict, x: jax.Array,
        act_shard=None) -> tuple[jax.Array, dict]:
    """x: [B, S, D] -> (out [B, S, D], {"aux", "moe_dropped", "moe_routed"}).

    Data-parallel, dispatch is *group-local*: tokens are split into
    ``parallel.moe_groups`` groups (set to the DP world size by the step
    factories) and capacity slots are computed within each group, so the
    [G, E, C_g, D] buffers are sharded G-over-DP and E-over-model with no
    cross-shard scatter.  The global-cumsum formulation (groups=1) made
    GSPMD all-reduce the whole [E, C, D] buffer across the data axis — the
    dominant collective in the baseline deepseek dry-run (EXPERIMENTS.md
    §Perf iteration 1).  Expert-parallel, the group is this chip's tokens.
    """
    e = cfg.moe
    ep = cfg.parallel.ep_axes
    B, S, D = x.shape
    T = B * S
    G = 1 if ep else max(1, cfg.parallel.moe_groups)
    if T % G:
        G = 1
    Tg = T // G
    xt = x.reshape(G, Tg, D)
    E, K, Eh = e.num_experts, e.top_k, e.experts_held
    Cap = capacity(Tg, e)
    # NOTE (§Perf iteration 2, refuted): explicit sharding hints on the
    # dispatch buffers ([G,E,C,D] G-over-DP, E-over-model with D replicated)
    # force f32 gradient all-reduces of the un-sharded D dimension — 13x
    # worse collective volume than GSPMD's own propagation.  Hints removed.

    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)  # [G, Tg, E]
            gate_w, gate_i = jax.lax.top_k(probs, K)  # [G, Tg, K]
            if e.norm_topk_prob:
                gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True),
                                              1e-9)
            gate_w = gate_w * e.routed_scaling_factor
            aux = _balance_loss(e, probs, gate_i, B, S)

            # capacity slot: position among the expert's assignments within
            # the group, token-major over its Tg*K assignments
            flat_e = gate_i.reshape(G, Tg * K)
            onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=1) - 1
            slot = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
            held = flat_e < Eh
            keep = held & (slot < Cap)
            # row of the flat [Eh * C] buffer; past its end for the rest
            dest = jnp.where(keep, flat_e * Cap + slot, Eh * Cap)
            stats = {"aux": aux,
                     "moe_dropped": jnp.sum(held & ~keep, dtype=jnp.float32),
                     "moe_routed": jnp.sum(held, dtype=jnp.float32)}

        gidx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, Tg * K))
        with jax.named_scope("dispatch"):
            xk = jnp.repeat(xt, K, axis=1)  # [G, Tg*K, D]
            buf = jnp.zeros((G, Eh * Cap, D), x.dtype).at[gidx, dest].add(
                xk, mode="drop")
            if ep:
                P = C.axis_size(ep)
                # [P, E_local, C, D] by destination chip, then by source
                buf = _exchange(buf.reshape(P, Eh // P, Cap, D), cfg)
            else:
                buf = buf.reshape(G, Eh, Cap, D)

        with jax.named_scope("experts"):
            if p["w_gate"].shape[0] != buf.shape[1]:
                raise ValueError(
                    f"{p['w_gate'].shape[0]} experts on this chip, "
                    f"{buf.shape[1]} routed to it")
            g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
            u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
            y = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u, p["w_down"])

        with jax.named_scope("combine"):
            if ep:
                y = _exchange(y, cfg)
            y = y.reshape(G, Eh * Cap, D)
            yk = y.at[gidx, dest].get(mode="fill", fill_value=0)
            # weighted sum over the k choices in f32, as the gate computes
            yk = yk.astype(jnp.float32) * gate_w.reshape(G, Tg * K)[..., None]
            out = yk.reshape(G, Tg, K, D).sum(axis=2).astype(x.dtype)

        # always-on shared experts (DeepSeek), computed alike on every chip
        if e.num_shared_experts:
            with jax.named_scope("shared"):
                sg = jax.nn.silu(xt @ p["shared_gate"]) * (xt @ p["shared_up"])
                out = out + sg @ p["shared_down"]
    return out.reshape(B, S, D), stats


def dense_ffn_flops(cfg: ModelConfig, tokens: int) -> int:
    """Active-parameter matmul FLOPs of one MoE layer (roofline bookkeeping)."""
    e = cfg.moe
    per_tok = (e.top_k + e.num_shared_experts) * 3 * cfg.d_model * e.d_ff_expert
    return 2 * tokens * per_tok
