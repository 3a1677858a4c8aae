"""Attention mixers: GQA (llama-family, optional sliding window, M-RoPE)
and MLA (DeepSeek-V2 / MiniCPM3 multi-head latent attention).

Three compute paths:

* ``chunked_attention`` — flash-style online-softmax attention in pure JAX
  (lax scans + dynamic slices).  This is the training/prefill path, the
  dry-run path (lowers on any backend) and the oracle for the Pallas
  ``flash_attention`` kernel.  ``causal_skip`` bounds the inner loop at the
  causal frontier (a beyond-paper compute-roofline optimization — halves
  attention FLOPs vs. masked-full computation).
* decode — single-token attention over a KV cache (scores materialize;
  they are tiny for q_len = 1).
* MLA decode uses the *absorbed* latent form: scores and values are taken
  directly against the compressed ``c_kv`` cache (the MLA serving win).

MLA with a ``yarn`` rope scaling (DeepSeek-V2) takes YaRN's rotary
frequencies and multiplies the softmax scale by ``mscale(mscale_all_dim)²``,
as the published modeling code does.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig, ModelConfig
from repro.models.layers import rope, yarn_mscale
from repro.models.params import ParamMeta

__all__ = [
    "attn_meta",
    "attention",
    "init_attn_cache",
    "chunked_attention",
]

_NEG = -1e30


# ---------------------------------------------------------------------------
# Parameter metadata.
# ---------------------------------------------------------------------------


def attn_meta(cfg: ModelConfig) -> dict:
    a = cfg.attn
    d = cfg.d_model
    if a.kind == "mla":
        out = {}
        q_in = d
        if a.q_lora_rank:
            out["wq_a"] = ParamMeta((d, a.q_lora_rank), ("d_model", "lora"))
            out["q_norm"] = ParamMeta((a.q_lora_rank,), ("lora",), init="ones")
            q_in = a.q_lora_rank
        out["wq_b"] = ParamMeta(
            (q_in, a.num_heads * a.qk_head_dim), ("lora", "heads_flat")
        )
        out["wkv_a"] = ParamMeta(
            (d, a.kv_lora_rank + a.qk_rope_head_dim), ("d_model", "lora")
        )
        out["kv_norm"] = ParamMeta((a.kv_lora_rank,), ("lora",), init="ones")
        out["wkv_b"] = ParamMeta(
            (a.kv_lora_rank, a.num_heads * (a.qk_nope_head_dim + a.v_head_dim)),
            ("lora", "heads_flat"),
        )
        out["wo"] = ParamMeta(
            (a.num_heads * a.v_head_dim, d), ("heads_flat", "d_model")
        )
        return out
    return {
        "wq": ParamMeta((d, a.num_heads * a.head_dim), ("d_model", "heads_flat")),
        "wk": ParamMeta((d, a.num_kv_heads * a.head_dim), ("d_model", "heads_flat")),
        "wv": ParamMeta((d, a.num_kv_heads * a.head_dim), ("d_model", "heads_flat")),
        "wo": ParamMeta((a.num_heads * a.head_dim, d), ("heads_flat", "d_model")),
    }


# ---------------------------------------------------------------------------
# KV caches.
# ---------------------------------------------------------------------------


def init_attn_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16):
    """Abstract/zero cache for ONE attention layer.  ``capacity`` is the ring
    size for sliding-window attention, else the max sequence length."""
    a = cfg.attn
    if a.sliding_window is not None:
        capacity = min(capacity, a.sliding_window)
    if a.kind == "mla":
        return {
            "ckv": jnp.zeros((batch, capacity, a.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, capacity, a.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, capacity, a.num_kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, capacity, a.num_kv_heads, a.head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — train / prefill path and kernel oracle.
# ---------------------------------------------------------------------------


def _chunk_size(n: int, want: int) -> int:
    want = min(want, n)
    while n % want:
        want -= 1
    return want


def chunked_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Skv, Hkv, hd]
    v: jax.Array,  # [B, Skv, Hkv, hdv]
    q_pos: jax.Array,  # [Sq] int32 absolute positions (monotone)
    k_off: int,  # positions of k are k_off + arange(Skv)
    *,
    window: int | None = None,
    chunk_q: int = 512,
    chunk_kv: int = 512,
    causal_skip: bool = True,
    scale: float | None = None,
    unroll: bool = False,
) -> jax.Array:
    """``unroll=True`` (the training path) unrolls the q-chunk loop in
    Python so the causal-skip KV bounds are *static* per chunk — this keeps
    the ~2x FLOP saving while remaining reverse-differentiable (a dynamic
    fori_loop bound is not).  It assumes the standard aligned layout
    ``q_pos == arange(Sq)`` and ``k_off == 0``, which holds for every
    training/prefill call in this framework."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // Hkv
    cq = _chunk_size(Sq, chunk_q)
    ck = _chunk_size(Skv, chunk_kv)
    nq, nk = Sq // cq, Skv // ck
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qs = q.reshape(B, nq, cq, Hkv, G, hd)
    qps = q_pos.reshape(nq, cq)

    def attend_chunk(qc, qp, lb, ub):
        """qc [B, cq, Hkv, G, hd]; iterate KV chunks in [lb, ub)."""
        m0 = jnp.full((B, Hkv, G, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, cq, hdv), jnp.float32)

        def kv_body(i, state):
            m, l, acc = state
            kc = jax.lax.dynamic_slice(k, (0, i * ck, 0, 0), (B, ck, Hkv, hd))
            vc = jax.lax.dynamic_slice(v, (0, i * ck, 0, 0), (B, ck, Hkv, hdv))
            kp = k_off + i * ck + jnp.arange(ck)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qc, kc, preferred_element_type=jnp.float32
            ) * scale
            mask = kp[None, :] <= qp[:, None]
            if window is not None:
                mask &= kp[None, :] > qp[:, None] - window
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new[..., None])
            l = l * alpha + pr.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", pr, vc, preferred_element_type=jnp.float32
            )
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(lb, ub, kv_body, (m0, l0, a0))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None]).astype(q.dtype)  # [B, Hkv, G, cq, hdv]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, hdv)

    if unroll:
        # static causal/window bounds per q chunk (aligned layout assumed)
        chunks = []
        for i in range(nq):
            if causal_skip:
                ub = min(nk, ((i + 1) * cq - 1) // ck + 1)
                lb = 0 if window is None else max(0, (i * cq - window + 1) // ck)
            else:
                lb, ub = 0, nk
            chunks.append(attend_chunk(qs[:, i], qps[i], lb, ub))
        return jnp.concatenate(chunks, axis=1)

    def q_body(carry, xs):
        qc, qp = xs
        if causal_skip and window is None:
            lb = jnp.int32(0)
            ub = jnp.clip((qp[-1] - k_off) // ck + 1, 0, nk).astype(jnp.int32)
        elif causal_skip:
            lb = jnp.clip((qp[0] - window + 1 - k_off) // ck, 0, nk).astype(jnp.int32)
            ub = jnp.clip((qp[-1] - k_off) // ck + 1, 0, nk).astype(jnp.int32)
        else:
            lb, ub = jnp.int32(0), jnp.int32(nk)
        return carry, attend_chunk(qc, qp, lb, ub)

    _, outs = jax.lax.scan(q_body, None, (qs.swapaxes(0, 1), qps))
    # outs: [nq, B, cq, H, hdv]
    return outs.swapaxes(0, 1).reshape(B, Sq, H, hdv)


# ---------------------------------------------------------------------------
# Decode attention over a cache (q_len == 1; scores materialize — tiny).
# ---------------------------------------------------------------------------


def _decode_attend(q, k, v, valid, scale):
    """q [B,1,H,hd]; k/v [B,C,Hkv,hd*]; valid [C] bool."""
    B, _, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, 1, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(valid[None, None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, 1, H, v.shape[-1])


# ---------------------------------------------------------------------------
# Full attention layer (projections + rope + mixer + output).
# ---------------------------------------------------------------------------


class AttnResult(NamedTuple):
    out: jax.Array
    cache: dict | None


def attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # [B, S, D]
    positions: jax.Array,  # [B, S] or [B, S, 3] (mrope)
    *,
    cache: dict | None = None,
    cache_pos: jax.Array | None = None,  # scalar: #valid entries in cache
    fill_cache: bool = False,  # prefill: return a filled cache
) -> AttnResult:
    a = cfg.attn
    if a.kind == "mla":
        return _mla_attention(cfg, p, x, positions, cache, cache_pos, fill_cache)
    return _gqa_attention(cfg, p, x, positions, cache, cache_pos, fill_cache)


def _pos1d(a: AttnConfig, positions: jax.Array) -> jax.Array:
    """Scalar per-token position sequence [S] (batch-uniform)."""
    if positions.ndim == 3:
        return positions[0, :, 0]
    return positions[0] if positions.ndim == 2 else positions


def _gqa_attention(cfg, p, x, positions, cache, cache_pos, fill_cache):
    a = cfg.attn
    B, S, _ = x.shape
    pl = cfg.parallel
    q = (x @ p["wq"]).reshape(B, S, a.num_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(B, S, a.num_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(B, S, a.num_kv_heads, a.head_dim)
    q = rope(q, positions, a.rope_theta, sections=a.mrope_sections)
    k = rope(k, positions, a.rope_theta, sections=a.mrope_sections)
    scale = 1.0 / math.sqrt(a.head_dim)

    if cache is None and not fill_cache:
        # ---- training: custom-VJP flash attention (memory-lean backward) ----
        from repro.models.flash import flash_attention_train

        G = a.num_heads // a.num_kv_heads
        qg = q.reshape(B, S, a.num_kv_heads, G, a.head_dim)
        o = flash_attention_train(
            qg, k, v, scale, a.sliding_window,
            pl.attn_chunk_q, pl.attn_chunk_kv, pl.causal_skip,
        ).reshape(B, S, a.num_heads, a.head_dim)
        out = o.reshape(B, S, a.num_heads * a.head_dim) @ p["wo"]
        return AttnResult(out, None)

    if cache is not None and not fill_cache:
        # ---- decode: append one token, attend over cache ----
        C = cache["k"].shape[1]
        widx = cache_pos % C if a.sliding_window is not None else cache_pos
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, widx, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, widx, 0, 0))
        idx = jnp.arange(C)
        if a.sliding_window is not None:
            # ring buffer: slot s holds position cache_pos - ((cache_pos - s) % C)
            slot_pos = cache_pos - jnp.mod(cache_pos - idx, C)
            valid = (slot_pos >= 0) & (slot_pos >= cache_pos - a.sliding_window + 1)
        else:
            valid = idx <= cache_pos
        o = _decode_attend(q, kc, vc, valid, scale)
        new_cache = {"k": kc, "v": vc}
    else:
        o = chunked_attention(
            q, k, v, _pos1d(a, positions), 0,
            window=a.sliding_window,
            chunk_q=pl.attn_chunk_q, chunk_kv=pl.attn_chunk_kv,
            causal_skip=pl.causal_skip, scale=scale,
            unroll=not fill_cache,  # train: static bounds (differentiable)
        )
        new_cache = None
        if fill_cache:
            cap = cache["k"].shape[1] if cache is not None else S
            if a.sliding_window is not None:
                cap = min(cap, a.sliding_window)
            new_cache = {"k": k[:, -cap:], "v": v[:, -cap:]}
            if cap > k.shape[1]:
                pad = cap - k.shape[1]
                new_cache = {
                    n: jnp.pad(arr, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    for n, arr in new_cache.items()
                }
    out = o.reshape(B, S, a.num_heads * a.head_dim) @ p["wo"]
    return AttnResult(out, new_cache)


def _mla_attention(cfg, p, x, positions, cache, cache_pos, fill_cache):
    a = cfg.attn
    B, S, _ = x.shape
    pl = cfg.parallel
    H = a.num_heads
    nope, rdim, vdim = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    scale = 1.0 / math.sqrt(a.qk_head_dim)
    if a.yarn is not None and a.yarn.mscale_all_dim:
        # YaRN's temperature, on the scores of every dim (DeepSeek-V2)
        scale *= yarn_mscale(a.yarn.factor, a.yarn.mscale_all_dim) ** 2

    # --- queries ---
    if a.q_lora_rank:
        from repro.models.layers import rms_norm

        cq = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        qf = (cq @ p["wq_b"]).reshape(B, S, H, nope + rdim)
    else:
        qf = (x @ p["wq_b"]).reshape(B, S, H, nope + rdim)
    q_nope, q_rope = qf[..., :nope], qf[..., nope:]
    q_rope = rope(q_rope, positions, a.rope_theta, yarn=a.yarn)

    # --- compressed kv ---
    from repro.models.layers import rms_norm

    kv_a = x @ p["wkv_a"]  # [B, S, kv_lora + rdim]
    ckv = rms_norm(kv_a[..., : a.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = rope(
        kv_a[..., a.kv_lora_rank :][:, :, None, :], positions, a.rope_theta,
        yarn=a.yarn,
    )[:, :, 0, :]  # [B, S, rdim] shared across heads

    wkv_b = p["wkv_b"].reshape(a.kv_lora_rank, H, nope + vdim)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]

    if cache is None and not fill_cache:
        # ---- training: expanded form through custom-VJP flash ----
        from repro.models.flash import flash_attention_train

        kv = jnp.einsum("bsl,lhm->bshm", ckv, wkv_b)
        k_nope, vv = kv[..., :nope], kv[..., nope:]
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rdim))],
            axis=-1,
        )
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)[:, :, :, None, :]
        # MLA is MHA (G == 1): q [B,S,H,1,hd], k/v [B,S,H,*]
        o = flash_attention_train(
            qq, kk, vv, scale, None,
            pl.attn_chunk_q, pl.attn_chunk_kv, pl.causal_skip,
        ).reshape(B, S, H, vdim)
        out = o.reshape(B, S, H * vdim) @ p["wo"]
        return AttnResult(out, None)

    if cache is not None and not fill_cache:
        # ---- absorbed decode over the latent cache ----
        C = cache["ckv"].shape[1]
        ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, cache_pos, 0))
        kr_c = jax.lax.dynamic_update_slice(cache["krope"], k_rope, (0, cache_pos, 0))
        valid = jnp.arange(C) <= cache_pos
        q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk)
        s = (
            jnp.einsum("bqhl,bkl->bhqk", q_lat, ckv_c,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhr,bkr->bhqk", q_rope, kr_c,
                         preferred_element_type=jnp.float32)
        ) * scale
        s = jnp.where(valid[None, None, None, :], s, _NEG)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqk,bkl->bqhl", pr.astype(ckv_c.dtype), ckv_c)
        o = jnp.einsum("bqhl,lhv->bqhv", o_lat, w_uv)
        new_cache = {"ckv": ckv_c, "krope": kr_c}
    else:
        # ---- expanded training / prefill form ----
        kv = jnp.einsum("bsl,lhm->bshm", ckv, wkv_b)  # [B,S,H,nope+vdim]
        k_nope, vv = kv[..., :nope], kv[..., nope:]
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rdim))],
            axis=-1,
        )
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = chunked_attention(
            qq, kk, vv, _pos1d(a, positions), 0,
            chunk_q=pl.attn_chunk_q, chunk_kv=pl.attn_chunk_kv,
            causal_skip=pl.causal_skip, scale=scale,
            unroll=not fill_cache,  # train: static bounds (differentiable)
        )
        new_cache = None
        if fill_cache:
            cap = cache["ckv"].shape[1] if cache is not None else S
            ckv_c, kr_c = ckv[:, -cap:], k_rope[:, -cap:]
            if cap > S:
                pad = cap - S
                ckv_c = jnp.pad(ckv_c, ((0, 0), (0, pad), (0, 0)))
                kr_c = jnp.pad(kr_c, ((0, 0), (0, pad), (0, 0)))
            new_cache = {"ckv": ckv_c, "krope": kr_c}
    out = o.reshape(B, S, H * vdim) @ p["wo"]
    return AttnResult(out, new_cache)
