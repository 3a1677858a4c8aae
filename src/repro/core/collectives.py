"""TPU-native (shard_map) implementations of the paper's collective families.

The paper's k-lane insight maps onto a multi-pod TPU mesh as follows: the
"compute node" is the pod (fast intra-pod ICI = the paper's shared memory),
the k "lanes" are the concurrent inter-pod streams, and the *full-lane
problem-splitting* family becomes the hierarchical decomposition of cross-pod
collectives:

    cross-pod allreduce  = reduce_scatter(intra) -> allreduce(pod) -> all_gather(intra)
    cross-pod reduce-scatter = reduce_scatter(intra, along a dim) -> allreduce(pod)
    cross-pod broadcast  = [payload lane-sharded on root pod] -> psum(pod) -> all_gather(intra)
    cross-pod alltoall   = own-pod blocks sent on-node (intra) beside the
                           rest sent across pods (cross_pod), each
                           forwarded on-node as it lands

Each function opens a ``jax.named_scope`` of its own name, and each phase
a scope inside it (``reduce_scatter`` / ``cross_pod`` / ``all_gather``,
``intra`` / ``cross_pod``, ``round<r>``), so every device op of a phase
carries ``<function>/<phase>`` in its HLO ``op_name``.  Scopes are trace-time
metadata only: the compiled program is the same without them.

Every function here must be called INSIDE ``jax.shard_map``
(they use named-axis collectives), mirroring how ``jax.lax.psum`` et al. are
used.  The k-ported tree algorithms are also provided, compiled from the
schedule generators into ``ppermute`` round programs — they exist so the
dry-run can compare collective bytes/rounds of the paper's baseline against
the full-lane family on identical payloads.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedule as sched
from repro.core.topology import Topology
from repro.obs.trace import TRACER

__all__ = [
    "axis_size",
    "hierarchical_psum",
    "hierarchical_reduce_scatter",
    "fulllane_psum",
    "fulllane_broadcast",
    "fulllane_all_to_all",
    "kported_broadcast_ppermute",
    "kported_scatter_ppermute",
    "flat_psum",
    "flat_all_to_all",
]


def axis_size(axis_name) -> int:
    if isinstance(axis_name, (tuple, list)):
        return int(np.prod([jax.lax.axis_size(a) for a in axis_name]))
    return int(jax.lax.axis_size(axis_name))


def _pad_to_multiple(x: jax.Array, m: int, axis: int = 0):
    size = x.shape[axis]
    pad = (-size) % m
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# ---------------------------------------------------------------------------
# Full-lane (hierarchical) family — the paper's §2.2 on TPU.
# ---------------------------------------------------------------------------


def hierarchical_psum(x: jax.Array, outer_axis, inner_axis) -> jax.Array:
    """All-reduce over (outer x inner) via the full-lane decomposition:
    reduce-scatter over ``inner`` (on-node phase), all-reduce over ``outer``
    (every inner chip drives an independent cross-pod subproblem — all lanes
    busy), all-gather over ``inner``.

    Mathematically identical to ``psum(x, (outer, inner))``; the win is that
    the cross-pod traffic per chip drops from ``2*C`` to ``2*C/n``.

    With ``inner_axis`` None there are no on-node phases: the cross-pod
    all-reduce alone, under the caller's scope, as
    ``hierarchical_reduce_scatter`` runs it on its tile.
    """
    if inner_axis is None:
        with jax.named_scope("cross_pod"):
            return jax.lax.psum(x, outer_axis)
    with jax.named_scope("hierarchical_psum"):
        n = axis_size(inner_axis)
        shape = x.shape
        flat = x.reshape(-1)
        flat, pad = _pad_to_multiple(flat, n)
        with jax.named_scope("reduce_scatter"):
            part = jax.lax.psum_scatter(flat, inner_axis, scatter_dimension=0,
                                        tiled=True)
        with jax.named_scope("cross_pod"):
            part = jax.lax.psum(part, outer_axis)
        with jax.named_scope("all_gather"):
            full = jax.lax.all_gather(part, inner_axis, axis=0, tiled=True)
        if pad:
            full = full[: flat.shape[0] - pad]
        return full.reshape(shape)


def hierarchical_reduce_scatter(x: jax.Array, outer_axis, inner_axis,
                                dim: int = 0) -> jax.Array:
    """Sum over (outer x inner), leaving each chip its ``inner`` tile of
    ``x`` along ``dim``: the first two phases of ``hierarchical_psum``,
    reduce-scatter over ``inner`` then all-reduce of the tile over
    ``outer``, without the flatten.

    Equal to this chip's ``inner`` tile along ``dim`` of
    ``psum(x, (outer, inner))``, up to the order of the sums;
    ``x.shape[dim]`` must divide by the ``inner`` size.  Scattering along
    a dim of the leaf compiles to a true reduce-scatter on the v5e, where
    ``hierarchical_psum``'s flat scatter compiles to a full-size
    all-reduce and a slice.
    """
    with jax.named_scope("hierarchical_reduce_scatter"):
        with jax.named_scope("reduce_scatter"):
            part = jax.lax.psum_scatter(x, inner_axis, scatter_dimension=dim,
                                        tiled=True)
        return hierarchical_psum(part, outer_axis, None)


# The paper's name for the family:
fulllane_psum = hierarchical_psum


def fulllane_broadcast(x: jax.Array, outer_axis, inner_axis, *, root: int = 0) -> jax.Array:
    """Broadcast a payload that is *valid on the root pod only* to all pods.

    ``x`` is the per-device shard of a payload laid out sharded over
    ``inner_axis`` (the paper's phase A — the on-node scatter — is the
    sharding itself).  Phase B: each inner chip broadcasts its chunk across
    pods (n concurrent inter-pod subproblems == full-lane).  Phase C: on-node
    all-gather reassembles the full payload everywhere.

    Returns the *full* payload (all inner shards concatenated on axis 0) on
    every device.
    """
    with jax.named_scope("fulllane_broadcast"):
        pod = jax.lax.axis_index(outer_axis)
        masked = jnp.where(pod == root, x, jnp.zeros_like(x))
        with jax.named_scope("cross_pod"):
            seeded = jax.lax.psum(masked, outer_axis)  # chunk across pods
        with jax.named_scope("all_gather"):
            return jax.lax.all_gather(seeded, inner_axis, axis=0, tiled=True)


def _shift(x: jax.Array, axis, n: int, s: int) -> jax.Array:
    """``x`` of every chip to the chip ``s`` places on along ``axis``."""
    return jax.lax.ppermute(x, axis, perm=[(a, (a + s) % n) for a in range(n)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fulllane_all_to_all(x: jax.Array, outer_axis, inner_axis) -> jax.Array:
    """Hierarchical all-to-all over the merged (outer, inner) axis.

    Semantics match ``jax.lax.all_to_all(x, (outer, inner), 0, 0, tiled=True)``
    for a per-device input of shape ``[P, ...]`` with ``P = No * Ni`` blocks
    ordered destination-major ``dest = o * Ni + i``:  block ``x[d]`` on device
    ``s`` ends up as output block ``s`` on device ``d``.

    Paper §2.2 as block moves on chip ``(w, j)`` (pod ``w``, lane ``j``).
    Block ``(w + t, j + u)`` (mod ``No``, ``Ni``) of its input goes:

    * ``t = 0``: on-node, straight to lane ``j + u`` (``intra`` shift
      round ``u``).  These blocks wait for nothing, so they start with the
      first cross-pod send and the lane link runs beside the pod link.
    * ``t > 0``: across pods to ``(w + t, j)`` (``cross_pod`` shift round
      ``t``), which keeps it (``u = 0``) or forwards it on its own lane
      link (``intra`` shift ``u``) as soon as it lands.  Every chip of a
      pod drives its own cross-pod stream (all ``Ni`` lanes busy).  A
      round's blocks go one after the other, those the receiver forwards
      first, so each forward runs under the round's later sends.

    Each block sent is one whole block of ``x``, and the blocks of each
    link's first send are cut before the others; each block received,
    and the chip's own block, lands in place at its source's index in an
    uninitialised output.  No whole buffer is transposed, filled or
    copied: a chip writes ``P - 1`` send slices and ``P`` landed blocks.
    Building it records ``fulllane_all_to_all.schedule``
    (``cross_pod_rounds``, ``intra_rounds``, ``overlapped_blocks``: lane
    sends under a cross-pod send in flight, ``forwarded_blocks``,
    ``local_move_bytes``) as an event of the tracer.

    Its own transpose: the VJP is the same all-to-all of the cotangent.
    """
    return _fulllane_all_to_all(x, outer_axis, inner_axis)


def _fulllane_all_to_all(x: jax.Array, outer_axis, inner_axis) -> jax.Array:
    No = axis_size(outer_axis)
    Ni = axis_size(inner_axis)
    P = No * Ni
    if x.shape[0] != P:
        raise ValueError(f"leading dim {x.shape[0]} != mesh size {P}")
    block_bytes = x.nbytes // P
    forwarded = (No - 1) * (Ni - 1)
    TRACER.event("fulllane_all_to_all.schedule", cross_pod_rounds=No - 1,
                 intra_rounds=Ni - 1,
                 overlapped_blocks=No * (Ni - 1) if No > 1 else 0,
                 forwarded_blocks=forwarded,
                 local_move_bytes=(2 * P - 1) * block_bytes)

    with jax.named_scope("fulllane_all_to_all"):
        w = _axis_linear_index(outer_axis)
        j = _axis_linear_index(inner_axis)

        # each link's first send is cut first; the other blocks are cut
        # while those sends run
        first = {(0, u) for u in range(1, Ni)} | {
            (t, 1 % Ni) for t in range(1, No)}
        rest = jax.lax.optimization_barrier(x)

        def block(t, u):
            """Block of ``x`` bound ``t`` pods and ``u`` lanes on."""
            k = (w + t) % No * Ni + (j + u) % Ni
            return jax.lax.dynamic_slice_in_dim(
                x if (t, u) in first else rest, k, 1)

        def source(t, u):
            """Output index of a block from ``t`` pods and ``u`` lanes back."""
            return (w - t) % No * Ni + (j - u) % Ni

        # (source index, block), in the order the blocks should land
        landed = [(source(0, 0), block(0, 0))]
        with jax.named_scope("intra"):
            landed += [(source(0, u), _shift(block(0, u), inner_axis, Ni, u))
                       for u in range(1, Ni)]
        for t in range(1, No):
            prev, prev_u = None, 0
            for u in list(range(1, Ni)) + [0]:
                b = block(t, u)
                if prev is not None:
                    # the send waits for the round's previous block to land
                    prev, b = jax.lax.optimization_barrier((prev, b))
                    if prev_u:
                        with jax.named_scope("intra"):
                            landed.append((source(t, prev_u),
                                           _shift(prev, inner_axis, Ni,
                                                  prev_u)))
                with jax.named_scope("cross_pod"):
                    prev, prev_u = _shift(b, outer_axis, No, t), u
            landed.append((source(t, 0), prev))

        out = jax.lax.empty(x.shape, x.dtype)
        for k, b in landed:
            out = jax.lax.dynamic_update_slice_in_dim(out, b, k, 0)
        return out


def _fulllane_all_to_all_fwd(x, outer_axis, inner_axis):
    return _fulllane_all_to_all(x, outer_axis, inner_axis), None


def _fulllane_all_to_all_bwd(outer_axis, inner_axis, _, g):
    # block (s, d) went to (d, s): the transpose is the same exchange
    return (_fulllane_all_to_all(g, outer_axis, inner_axis),)


fulllane_all_to_all.defvjp(_fulllane_all_to_all_fwd, _fulllane_all_to_all_bwd)


# ---------------------------------------------------------------------------
# k-ported tree algorithms compiled to ppermute round programs (§2.1).
# ---------------------------------------------------------------------------


def _axis_linear_index(axis_names: Sequence[str]):
    """Linear device index over possibly-multiple named axes (row-major)."""
    if isinstance(axis_names, str):
        return jax.lax.axis_index(axis_names)
    idx = jax.lax.axis_index(axis_names[0])
    for a in axis_names[1:]:
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx


def _run_rounds(x: jax.Array, schedule, axis_names, me) -> jax.Array:
    """Run ``schedule``'s rounds as ``ppermute`` waves, round ``r`` inside
    a ``round<r>`` scope: device ``me`` takes a wave's payload where it is
    a destination and keeps its buffer elsewhere."""
    cur = x
    for r, rnd in enumerate(schedule.rounds):
        # Each round has at most k messages per source; ppermute supports one
        # message per source, so split the round into <= k waves.
        waves: list[list[tuple[int, int]]] = []
        per_src: dict[int, int] = {}
        for m in rnd.msgs:
            w = per_src.get(m.src, 0)
            per_src[m.src] = w + 1
            while len(waves) <= w:
                waves.append([])
            waves[w].append((m.src, m.dst))
        with jax.named_scope(f"round{r}"):
            for wave in waves:
                recv = jax.lax.ppermute(cur, axis_names, perm=wave)
                dsts = jnp.asarray([d for _, d in wave])
                is_dst = jnp.any(me == dsts)
                cur = jnp.where(is_dst, recv, cur)
    return cur


def kported_broadcast_ppermute(
    x: jax.Array, axis_names, *, k: int, root: int = 0
) -> jax.Array:
    """The paper's §2.1 radix-(k+1) divide & conquer broadcast, executed as
    ``ceil(log_{k+1} P)`` rounds of (up to k sequential) ``ppermute``s.

    On a machine without true k-ported chips the k sends of a round
    serialize — exactly the effect the paper measures; the dry-run uses this
    to compare collective schedules, and it is the faithful baseline.
    """
    with jax.named_scope("kported_broadcast_ppermute"):
        P = axis_size(axis_names)
        schedule = sched.kported_broadcast(P, k, c=1, root=root)
        return _run_rounds(x, schedule, axis_names,
                           _axis_linear_index(axis_names))


def kported_scatter_ppermute(
    x: jax.Array, axis_names, *, k: int, root: int = 0
) -> jax.Array:
    """§2.1 divide & conquer scatter as ppermute rounds.

    ``x``: per-device buffer of shape [P, ...]; the root's buffer holds block
    ``j`` for device ``j`` at ``x[j]``.  Returns each device's own block
    (shape ``x.shape[1:]``).  Intermediate devices carry their subrange's
    blocks in a full-size buffer (XLA needs static shapes); the *collective*
    traffic volume still shrinks per round, which is what the dry-run
    measures via per-round message sizes in the schedule metadata.
    """
    P = axis_size(axis_names)
    if x.shape[0] != P:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {P}")
    with jax.named_scope("kported_scatter_ppermute"):
        schedule = sched.kported_scatter(P, k, c=1, root=root)
        me = _axis_linear_index(axis_names)
        cur = _run_rounds(x, schedule, axis_names, me)
        return jnp.take(cur, me, axis=0)


# ---------------------------------------------------------------------------
# Flat (XLA-native) baselines for comparison.
# ---------------------------------------------------------------------------


def flat_psum(x: jax.Array, outer_axis, inner_axis) -> jax.Array:
    axes = []
    for a in (outer_axis, inner_axis):
        if isinstance(a, (tuple, list)):
            axes.extend(a)
        else:
            axes.append(a)
    return jax.lax.psum(x, tuple(axes))


def flat_all_to_all(x: jax.Array, outer_axis, inner_axis) -> jax.Array:
    axes = []
    for a in (outer_axis, inner_axis):
        if isinstance(a, (tuple, list)):
            axes.extend(a)
        else:
            axes.append(a)
    return jax.lax.all_to_all(x, tuple(axes), split_axis=0, concat_axis=0, tiled=True)
