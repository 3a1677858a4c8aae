"""Pallas TPU fused RMSNorm.

One pass: fp32 mean-square reduction + rsqrt scaling + weight multiply,
tiled over rows (grid = (num_row_blocks,)), with the full feature dimension
resident in VMEM (d_model <= 8192 for all assigned archs -> <= 4 MB fp32
per 128-row block)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import sublanes, tile

__all__ = ["rmsnorm_kernel", "rmsnorm_pallas"]


def rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [br, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def rmsnorm_pallas(
    x: jax.Array,  # [T, d]
    w: jax.Array,  # [d]
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    T, d = x.shape
    block_rows = tile(T, sublanes(x.dtype), block_rows)
    kernel = functools.partial(rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(T // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        interpret=interpret,
    )(x, w)
