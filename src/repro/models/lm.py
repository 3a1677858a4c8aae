"""Decoder LM assembly: embedding -> scan over pattern periods -> head.

Parameters for the repeating ``layer_pattern`` are stacked on a leading
``periods`` axis and consumed by ``jax.lax.scan`` (HLO size O(period), not
O(depth) — a 72-layer Jamba lowers as one 8-layer period body).  The first
``first_k_dense`` layers (DeepSeek) are unrolled as a prelude with dense FFN.

Three entry points, one per assigned shape kind:

* ``loss_fn``      — training forward + cross-entropy (train_4k),
* ``prefill``      — forward returning last-position logits + filled caches
  (prefill_32k),
* ``decode_step``  — one-token step against caches (decode_32k, long_500k).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models.params import ParamMeta, abstract_params, init_params

__all__ = [
    "model_meta",
    "counter_names",
    "init_model",
    "abstract_model",
    "loss_fn",
    "prefill",
    "decode_step",
    "init_cache",
    "abstract_cache",
]


# ---------------------------------------------------------------------------
# Parameter metadata.
# ---------------------------------------------------------------------------


def _slot_meta(cfg: ModelConfig, spec: LayerSpec) -> dict:
    d = cfg.d_model
    out: dict[str, Any] = {"norm1": L.rms_norm_meta(d)}
    out["mixer"] = (
        attn_mod.attn_meta(cfg) if spec.mixer == "attn" else mamba_mod.mamba_meta(cfg)
    )
    if spec.ffn != "none":
        out["norm2"] = L.rms_norm_meta(d)
        out["ffn"] = (
            L.mlp_meta(d, cfg.d_ff, cfg.act)
            if spec.ffn == "dense"
            else moe_mod.moe_meta(cfg)
        )
    return out


def _stack_meta(tree, n: int):
    return jax.tree.map(
        lambda m: dataclasses.replace(
            m, shape=(n,) + m.shape, axes=("layers",) + m.axes
        ),
        tree,
        is_leaf=lambda x: isinstance(x, ParamMeta),
    )


def _scanned_periods(cfg: ModelConfig) -> int:
    return (cfg.num_layers - cfg.first_k_dense) // len(cfg.layer_pattern)


def model_meta(cfg: ModelConfig) -> dict:
    out: dict[str, Any] = {
        "embed": L.embed_meta(cfg),
        "head": L.head_meta(cfg),
        "final_norm": L.rms_norm_meta(cfg.d_model),
    }
    blocks = {}
    for i, spec in enumerate(cfg.layer_pattern):
        blocks[f"slot{i}"] = _stack_meta(_slot_meta(cfg, spec), _scanned_periods(cfg))
    out["blocks"] = blocks
    for j in range(cfg.first_k_dense):
        out[f"prelude{j}"] = _slot_meta(
            cfg, dataclasses.replace(cfg.layer_pattern[j % len(cfg.layer_pattern)],
                                     ffn="dense")
        )
    return out


def init_model(cfg: ModelConfig, rng: jax.Array):
    return init_params(model_meta(cfg), rng, dtype=jnp.dtype(cfg.dtype))


def abstract_model(cfg: ModelConfig):
    return abstract_params(model_meta(cfg), dtype=jnp.dtype(cfg.dtype))


# ---------------------------------------------------------------------------
# Caches (prefill/decode).
# ---------------------------------------------------------------------------


def _slot_cache(cfg, spec: LayerSpec, batch: int, capacity: int):
    if spec.mixer == "attn":
        return attn_mod.init_attn_cache(cfg, batch, capacity)
    return mamba_mod.init_mamba_cache(cfg, batch)


def init_cache(cfg: ModelConfig, batch: int, capacity: int):
    P = _scanned_periods(cfg)
    cache: dict[str, Any] = {"blocks": {}}
    for i, spec in enumerate(cfg.layer_pattern):
        one = _slot_cache(cfg, spec, batch, capacity)
        cache["blocks"][f"slot{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (P,) + x.shape).copy(), one
        )
    for j in range(cfg.first_k_dense):
        spec = cfg.layer_pattern[j % len(cfg.layer_pattern)]
        cache[f"prelude{j}"] = _slot_cache(cfg, spec, batch, capacity)
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, capacity: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, capacity))


# ---------------------------------------------------------------------------
# Forward machinery.
# ---------------------------------------------------------------------------


def counter_names(cfg: ModelConfig) -> tuple[str, ...]:
    """The counts among the loss's metrics, beside its means: summed over
    layers, and by the train steps over micro-batches and chips."""
    if any(s.ffn == "moe" for s in cfg.layer_pattern):
        return ("moe_dropped", "moe_routed")
    return ()


def _zero_stats(cfg: ModelConfig) -> dict:
    return {k: jnp.zeros((), jnp.float32)
            for k in ("aux",) + counter_names(cfg)}


def _add_stats(total: dict, stats: dict) -> dict:
    return {k: v + stats[k] if k in stats else v for k, v in total.items()}


def _apply_slot(
    cfg, spec: LayerSpec, p, x, positions, *, cache=None, cache_pos=None,
    fill_cache=False, act_shard=None,
):
    stats = {"aux": jnp.zeros((), jnp.float32)}
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == "attn":
        res = attn_mod.attention(
            cfg, p["mixer"], h, positions,
            cache=cache, cache_pos=cache_pos, fill_cache=fill_cache,
        )
        mix, new_cache = res.out, res.cache
    else:
        mix, new_cache = mamba_mod.mamba(
            cfg, p["mixer"], h, cache=cache, fill_cache=fill_cache
        )
    x = x + mix
    if spec.ffn != "none":
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.ffn == "dense":
            f = L.mlp(p["ffn"], h2, cfg.act)
        else:
            f, stats = moe_mod.moe(cfg, p["ffn"], h2, act_shard=act_shard)
        x = x + f
    return x, new_cache, stats


def _period_body(cfg, positions, *, mode: str, cache_pos=None, remat=False,
                 act_shard=None):
    """Returns a scan body over (carry=(x, stats), xs=(period_params[,cache]))."""

    def body(carry, xs):
        x, stats_sum = carry
        if act_shard is not None:
            # re-pin the batch-dim DP sharding every period: GSPMD otherwise
            # drifts to feature-dim sharding inside the scan (observed as
            # fully replicated microbatches in the compiled HLO)
            x = act_shard(x)
        if mode == "train":
            pp, caches = xs, {}
        else:
            pp, caches = xs
        new_caches = {}
        for i, spec in enumerate(cfg.layer_pattern):
            slot = f"slot{i}"
            x, nc, stats = _apply_slot(
                cfg, spec, pp[slot], x, positions,
                cache=caches.get(slot),
                cache_pos=cache_pos,
                fill_cache=(mode == "prefill"),
                act_shard=act_shard,
            )
            stats_sum = _add_stats(stats_sum, stats)
            if nc is not None:
                new_caches[slot] = nc
        if mode == "train":
            return (x, stats_sum), None
        return (x, stats_sum), new_caches

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    return body


def _backbone(cfg: ModelConfig, params, x, positions, *, mode, cache=None,
              cache_pos=None, act_shard=None):
    """Embed-to-final-norm trunk shared by all entry points; returns the
    trunk's output, the layers' summed stats (``aux`` and the counters of
    :func:`counter_names`) and the new cache."""
    stats = _zero_stats(cfg)
    if act_shard is not None:
        x = act_shard(x)
    # prelude (unrolled, e.g. DeepSeek first dense layer)
    for j in range(cfg.first_k_dense):
        spec = dataclasses.replace(
            cfg.layer_pattern[j % len(cfg.layer_pattern)], ffn="dense"
        )
        x, nc, a = _apply_slot(
            cfg, spec, params[f"prelude{j}"], x, positions,
            cache=None if cache is None else cache.get(f"prelude{j}"),
            cache_pos=cache_pos,
            fill_cache=(mode == "prefill"),
            act_shard=act_shard,
        )
        stats = _add_stats(stats, a)
        if cache is not None and nc is not None:
            cache = {**cache, f"prelude{j}": nc}

    body = _period_body(
        cfg, positions, mode=mode, cache_pos=cache_pos,
        remat=(mode == "train" and cfg.parallel.remat),
        act_shard=act_shard,
    )
    if mode == "train":
        (x, stats), _ = jax.lax.scan(body, (x, stats), params["blocks"])
        new_cache = None
    elif mode == "decode":
        # Decode unrolls the period loop: a lax.scan would carry the whole
        # KV cache as while-loop state, which XLA double/triple-buffers —
        # observed as ~3x cache bytes of temp in the dry-run (gemma
        # decode_32k: 25.4 GiB vs a 3.8 GiB cache).  Unrolled, each period
        # slices its layer cache out of the stacked (donated) buffers and
        # writes it back with dynamic_update_index — a linear
        # dynamic-update-slice chain XLA keeps in place.
        P_ = _scanned_periods(cfg)
        block_caches = cache["blocks"]
        for i in range(P_):
            pp = jax.tree.map(lambda a: a[i], params["blocks"])
            pc = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                block_caches,
            )
            (x, stats), nc = body((x, stats), (pp, pc))
            block_caches = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), i, 0
                ),
                block_caches, nc,
            )
        new_cache = {**{k: v for k, v in cache.items() if k != "blocks"},
                     "blocks": block_caches}
    else:
        (x, stats), block_caches = jax.lax.scan(
            body, (x, stats), (params["blocks"], cache["blocks"])
        )
        new_cache = {**{k: v for k, v in cache.items() if k != "blocks"},
                     "blocks": block_caches}
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, stats, new_cache


def _default_positions(cfg: ModelConfig, B: int, S: int, offset=0):
    pos = offset + jnp.arange(S, dtype=jnp.int32)
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.attn is not None and cfg.attn.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


# ---------------------------------------------------------------------------
# Entry points.
# ---------------------------------------------------------------------------


def _embed_or_passthrough(cfg, params, batch):
    if cfg.embed_inputs:
        tokens = batch["tokens"]
        B, S = tokens.shape[0], tokens.shape[1]
        x = L.embed(cfg, params["embed"], tokens)
    else:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        B, S = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    return x, positions


def loss_fn(cfg: ModelConfig, params, batch, *, act_shard=None) -> tuple[jax.Array, dict]:
    """Cross-entropy training objective.  batch: tokens/embeds + labels
    ([B, S] int32, or [B, S, K] for multi-codebook).  ``act_shard`` is an
    optional x -> x hook pinning activation shardings (see train_step)."""
    x, positions = _embed_or_passthrough(cfg, params, batch)
    x, stats, _ = _backbone(cfg, params, x, positions, mode="train",
                            act_shard=act_shard)
    lg = L.logits(cfg, params, x)
    labels = batch["labels"]
    # lse in fp32 (logsumexp upcasts internally); label logit via one-hot
    # contraction so the (possibly vocab-sharded) logits never re-gather.
    lse = jax.nn.logsumexp(lg.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, lg.shape[-1], dtype=lg.dtype)
    ll = jnp.einsum("...v,...v->...", lg, onehot,
                    preferred_element_type=jnp.float32)
    nll = (lse - ll).mean()
    aux = stats["aux"]
    loss = nll + aux
    counts = {k: stats[k] for k in counter_names(cfg)}
    return loss, {"loss": loss, "nll": nll, "aux": aux, **counts}


def prefill(cfg: ModelConfig, params, batch, *, capacity: int | None = None,
            act_shard=None):
    """Process a prompt; returns (last_logits [B, V...], filled cache)."""
    x, positions = _embed_or_passthrough(cfg, params, batch)
    B, S = x.shape[0], x.shape[1]
    cache = init_cache(cfg, B, capacity or S)
    x, _, new_cache = _backbone(
        cfg, params, x, positions, mode="prefill", cache=cache,
        act_shard=act_shard,
    )
    lg = L.logits(cfg, params, x[:, -1:])
    return lg[:, 0], new_cache


def decode_step(cfg: ModelConfig, params, tokens_or_embeds, cache, cache_pos,
                *, act_shard=None):
    """One decode step.  ``tokens_or_embeds``: [B, 1] int32 (or [B, 1, D]).
    ``cache_pos``: scalar int32 — number of tokens already in the cache.
    Returns (logits [B, V...], new cache)."""
    if cfg.embed_inputs:
        x = L.embed(cfg, params["embed"], tokens_or_embeds)
    else:
        x = tokens_or_embeds.astype(jnp.dtype(cfg.dtype))
    B = x.shape[0]
    positions = _default_positions(cfg, B, 1, offset=cache_pos)
    x, _, new_cache = _backbone(
        cfg, params, x, positions, mode="decode", cache=cache,
        cache_pos=cache_pos, act_shard=act_shard,
    )
    lg = L.logits(cfg, params, x)
    return lg[:, 0], new_cache
