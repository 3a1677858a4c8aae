"""Shared neural-net building blocks: norms, MLPs, rotary embeddings,
token/codebook embedding and LM heads.

Each block has a ``*_meta`` builder (parameter metadata, see
:mod:`repro.models.params`) and a pure forward function operating on the
materialized (or abstract) parameter dict.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamMeta

__all__ = [
    "rms_norm",
    "rms_norm_meta",
    "mlp_meta",
    "mlp",
    "embed_meta",
    "embed",
    "head_meta",
    "logits",
    "rope",
    "mrope_positions",
    "yarn_inv_freq",
    "yarn_mscale",
]


# ---------------------------------------------------------------------------
# RMSNorm.
# ---------------------------------------------------------------------------


def rms_norm_meta(d: int) -> ParamMeta:
    return ParamMeta((d,), ("d_model",), init="ones")


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU).
# ---------------------------------------------------------------------------


def mlp_meta(d: int, ff: int, act: str) -> dict:
    if act in ("silu", "geglu"):
        return {
            "w_gate": ParamMeta((d, ff), ("d_model", "ff")),
            "w_up": ParamMeta((d, ff), ("d_model", "ff")),
            "w_down": ParamMeta((ff, d), ("ff", "d_model")),
        }
    return {
        "w_up": ParamMeta((d, ff), ("d_model", "ff")),
        "w_down": ParamMeta((ff, d), ("ff", "d_model")),
    }


def mlp(p: dict, x: jax.Array, act: str) -> jax.Array:
    if act in ("silu", "geglu"):
        g = x @ p["w_gate"]
        g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
        return (g * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]


# ---------------------------------------------------------------------------
# Embedding + LM head (multi-codebook aware for MusicGen).
# ---------------------------------------------------------------------------


def embed_meta(cfg: ModelConfig) -> dict:
    v, d = cfg.padded_vocab, cfg.d_model
    out = {}
    if cfg.embed_inputs:
        out["embedding"] = ParamMeta(
            (cfg.num_codebooks, v, d) if cfg.num_codebooks > 1 else (v, d),
            ("layers", "vocab", "d_model") if cfg.num_codebooks > 1 else ("vocab", "d_model"),
            scale=0.02,
        )
    return out


def _lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """One-hot matmul embedding lookup.  A plain gather against a
    vocab-sharded table forces GSPMD to all-gather the whole table
    ("involuntary full rematerialization"); the one-hot contraction
    partitions cleanly over the sharded vocab dim (partial products +
    psum), at a FLOP cost that is <2% of a training step."""
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
    return onehot @ table


def embed(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    """tokens: [B, S] int32, or [B, S, K] for K codebooks."""
    emb = p["embedding"]
    if cfg.num_codebooks > 1:
        # sum the K codebook embeddings (MusicGen parallel pattern)
        x = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), emb.dtype)
        for k in range(cfg.num_codebooks):
            x = x + _lookup(emb[k], tokens[..., k])
    else:
        x = _lookup(emb, tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return x


def head_meta(cfg: ModelConfig) -> dict:
    v, d = cfg.padded_vocab, cfg.d_model
    if cfg.tie_embeddings and cfg.embed_inputs and cfg.num_codebooks == 1:
        return {}
    k = cfg.num_codebooks
    return {
        "lm_head": ParamMeta(
            (d, k * v) if k > 1 else (d, v),
            ("d_model", "vocab"),
        )
    }


def logits(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """x: [B, S, D] -> [B, S, V] or [B, S, K, V]."""
    v = cfg.padded_vocab
    if cfg.tie_embeddings and cfg.embed_inputs and cfg.num_codebooks == 1:
        out = x @ params["embed"]["embedding"].T
    else:
        out = x @ params["head"]["lm_head"]
    if cfg.num_codebooks > 1:
        out = out.reshape(out.shape[:-1] + (cfg.num_codebooks, v))
    return out


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + M-RoPE).
# ---------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> jax.Array:
    """YaRN rotary frequencies (DeepSeek-V2's ``rope_scaling``): each
    frequency is the plain one divided by ``factor`` below the dim that
    turns ``beta_slow`` times over the original context, kept above the
    dim that turns ``beta_fast`` times, and blended linearly between."""
    def turn_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turn_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(turn_dim(yarn.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    extra = _rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the plain frequency, 0: interpolated
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def mrope_positions(positions: jax.Array, sections: tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal RoPE: ``positions`` [B, S, 3] (t, h, w) ->
    per-frequency positions [B, S, head_dim/2] by section assignment."""
    parts = [
        jnp.broadcast_to(positions[..., i : i + 1], positions.shape[:-1] + (sec,))
        for i, sec in enumerate(sections)
    ]
    return jnp.concatenate(parts, axis=-1)


def rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    *,
    sections: tuple[int, ...] | None = None,
    yarn=None,
) -> jax.Array:
    """Apply rotary embedding.

    x: [B, S, H, head_dim]; positions: [B, S] (or [B, S, 3] with
    ``sections`` for M-RoPE).  Rotation uses the llama "rotate-half" layout.
    ``yarn`` (a :class:`YarnScaling`) takes YaRN's frequencies, and its
    cos/sin scale ``mscale(mscale) / mscale(mscale_all_dim)``.
    """
    head_dim = x.shape[-1]
    if yarn is None:
        freqs = _rope_freqs(head_dim, theta)  # [hd/2]
    else:
        freqs = yarn_inv_freq(head_dim, theta, yarn)
    if sections is not None:
        pos = mrope_positions(positions, sections).astype(jnp.float32)  # [B,S,hd/2]
        angles = pos * freqs  # [B, S, hd/2]
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs  # [B,S,hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    cos = cos[..., None, :].astype(x.dtype)  # [B,S,1,hd/2]
    sin = sin[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
