"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively; anywhere else (this CPU
container, unit tests) they execute with ``interpret=True`` so the kernel
*body* is validated against the ref.py oracles.  Model code can route
through these via ``use_pallas=True`` config; the default JAX paths in
models/ remain the portable implementation (and the dry-run lowers those,
since interpreted kernels carry no FLOP/byte cost model)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.a2a_pack import a2a_pack_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

__all__ = ["flash_attention", "mamba_scan", "rmsnorm", "a2a_pack", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "causal", "window", "scale", "block_q", "block_k"),
)
def flash_attention(
    q, k, v, *, group_size=1, causal=True, window=None, scale=None,
    block_q=512, block_k=512,
):
    """q [BH, Sq, hd]; k/v [BHkv, Skv, hd].  head_dim is padded to a lane
    multiple (128) when needed (h2o-danube's 120)."""
    hd = q.shape[-1]
    pad = (-hd) % 128
    if pad and on_tpu():
        zp = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad)))
        # keys pad with zeros (dot ignores), values too (sliced after)
        out = flash_attention_pallas(
            zp(q), zp(k), zp(v), group_size=group_size, causal=causal,
            window=window, scale=scale or 1.0 / (hd**0.5),
            block_q=block_q, block_k=block_k, interpret=False,
        )
        return out[..., :hd]
    return flash_attention_pallas(
        q, k, v, group_size=group_size, causal=causal, window=window,
        scale=scale, block_q=block_q, block_k=block_k,
        interpret=not on_tpu(),
    )


@functools.partial(jax.jit, static_argnames=("chunk", "block_d"))
def mamba_scan(a, b, c, *, chunk=8, block_d=512):
    return mamba_scan_pallas(
        a, b, c, chunk=chunk, block_d=block_d, interpret=not on_tpu()
    )


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, w, *, eps=1e-6, block_rows=256):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = rmsnorm_pallas(x2, w, eps=eps, block_rows=block_rows,
                         interpret=not on_tpu())
    return out.reshape(shape)


@jax.jit
def a2a_pack(x):
    return a2a_pack_pallas(x, interpret=not on_tpu())
