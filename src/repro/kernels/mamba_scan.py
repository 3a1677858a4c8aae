"""Pallas TPU selective-scan kernel (Mamba-1 recurrence).

Computes ``h_t = a_t * h_{t-1} + b_t`` over the sequence and the readout
``y_t = sum_n h_t[:, n] * C_t[n]`` in one pass, tiled as:

  grid = (batch, d_inner_blocks, seq_chunks)   — seq innermost (sequential)

The SSM state ``h`` ([block_d, N]) lives in VMEM scratch and carries across
sequence chunks (TPU grid order guarantees sequential execution of the last
dimension).  Within a chunk the recurrence is a ``fori_loop`` over time —
the arithmetic-intensity-poor inner loop the VPU handles while the MXU-bound
projections around it stay in XLA land.

VMEM per step: the a/b blocks, double-buffered, are 4 * chunk * block_d *
128 fp32 words, since VMEM pads the minor N dim (16 for Mamba-1) to 128
lanes.  The defaults (chunk=8, block_d=512) take 8 MiB of the 16 MiB scoped
limit on v5e; the old chunk=64 took 64 MiB and was refused at d_inner 8192.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_scan_kernel", "mamba_scan_pallas"]


def mamba_scan_kernel(
    a_ref, b_ref, c_ref,  # [1, ch, bd, N], [1, ch, bd, N], [1, ch, N]
    y_ref, hlast_ref,  # [1, ch, bd], [1, bd, N]
    h_scr,  # VMEM [bd, N] carried state
    *,
    chunk: int,
    num_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        a_t = a_ref[0, t]  # [bd, N]
        b_t = b_ref[0, t]
        c_t = c_ref[0, t]  # [N]
        h = a_t * h + b_t
        y_ref[0, t] = (h * c_t[None, :]).sum(axis=1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == num_chunks - 1)
    def _final():
        hlast_ref[0] = h_scr[...].astype(hlast_ref.dtype)


def mamba_scan_pallas(
    a: jax.Array,  # [B, S, di, N] fp32 decay
    b: jax.Array,  # [B, S, di, N] fp32 input
    c: jax.Array,  # [B, S, N]     fp32 readout
    *,
    chunk: int = 8,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B, S, di], h_last [B, di, N])."""
    B, S, di, N = a.shape
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    if S % chunk or di % block_d:
        raise ValueError(f"S={S} % chunk={chunk} or di={di} % block_d={block_d}")
    nc, nd = S // chunk, di // block_d

    kernel = functools.partial(mamba_scan_kernel, chunk=chunk, num_chunks=nc)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d, N), lambda bi, d, ci: (bi, ci, d, 0)),
            pl.BlockSpec((1, chunk, block_d, N), lambda bi, d, ci: (bi, ci, d, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, d, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, d, ci: (bi, ci, d)),
            pl.BlockSpec((1, block_d, N), lambda bi, d, ci: (bi, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        interpret=interpret,
    )(a, b, c)
    return y, h_last
