"""Pallas TPU lane-major block pack for the hierarchical all-to-all.

The paper's on-node phase of the full-lane alltoall regroups each
processor's blocks by destination *lane* before the cross-node exchange.
As a whole-buffer step that is the local ``[No, Ni, blk, d] -> [Ni, No,
blk, d]`` block transpose this kernel performs, VMEM-tiled (one ``(tb,
td)`` tile of a ``(blk, d)`` block per grid step, so arbitrary No*Ni
fan-outs and block sizes stream through VMEM instead of materializing a
transposed HBM temp).  ``repro.core.collectives.fulllane_all_to_all`` needs
no such transpose: it sends whole blocks of its input and lands each block
in place in its output, so no program path calls this kernel.

Tiles are capped at ``_TILE_BYTES``: the pipeline double-buffers the input
and the output tile, so a step holds four tiles, which must fit the scoped
VMEM limit (16 MiB by default on v5e).  A whole ``(blk, d)`` block does not:
at 64 MiB per chip one block of ``[2, 2, 8192, 512]`` f32 is 16 MiB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import sublanes, tile

__all__ = ["a2a_pack_kernel", "a2a_pack_pallas"]

_TILE_BYTES = 2 << 20


def a2a_pack_kernel(x_ref, o_ref):
    # x tile: [1, 1, tb, td] of block (o, i); written to block (i, o).
    o_ref[...] = x_ref[...]


def a2a_pack_pallas(
    x: jax.Array,  # [No, Ni, blk, d]
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns x with the leading two (destination-group) dims swapped."""
    No, Ni, blk, d = x.shape
    itemsize = x.dtype.itemsize
    rows = sublanes(x.dtype)
    td = tile(d, 128, max(128, _TILE_BYTES // (rows * itemsize)))
    tb = tile(blk, rows, max(rows, _TILE_BYTES // (td * itemsize)))
    spec = (1, 1, tb, td)
    return pl.pallas_call(
        a2a_pack_kernel,
        grid=(No, Ni, blk // tb, d // td),
        in_specs=[pl.BlockSpec(spec, lambda o, i, r, c: (o, i, r, c))],
        out_specs=pl.BlockSpec(spec, lambda o, i, r, c: (i, o, r, c)),
        out_shape=jax.ShapeDtypeStruct((Ni, No, blk, d), x.dtype),
        interpret=interpret,
    )(x)
