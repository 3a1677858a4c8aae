"""Schedule optimizer: the one rewrite pipeline the planner runs over a
:class:`CompiledSchedule`.

The paper's k-lane adaptations are explicitly non-optimal: the k-lane
alltoall pays ``(N-1)*n`` rounds of per-round latency even though a node's
``k`` lanes could carry ``k`` of those steps concurrently, and every
multi-phase lane algorithm serializes phases that touch disjoint
processors.  Träff's companion decomposition paper (arXiv:1910.13373)
shows lane-parallel restructuring recovers most of that gap.  A rewrite
over the compiled IR is array surgery on ``round_ptr``/message arrays, so
this module holds the optimization layer between schedule generation and
simulation.  A plan request reaches it along one of two paths::

    opt: candidate (selector) ──▶ compiled_schedule(optimize="color")
        generate (schedule_ir) ──▶ ColorRounds(limit=None)
            conflict-graph coloring at the structural budget rung
            (choose_color_budget); payload-independent, so it runs once
            per structure on a tagged copy and is recorded as a
            (morder, round_ptr) recipe that replays at every payload
        ──▶ validate_schedule (full oracle, first application)

    faulted request ──▶ repair_schedule(healthy or packed entry, FaultSpec)
        RepairSchedule: relay inter hops off dead network ports
            (schedule_ir.relay_messages), then ColorRounds(limit=k_eff)
            under the surviving lane budget — a rewrite, never a
            regeneration
        ──▶ validate_schedule (full oracle, every repair)

Both paths end in the process-wide schedule cache of
:mod:`repro.core.schedule_ir`, keyed on ``(op, algorithm, topo, k, c,
root, optimize, pipeline_fingerprint, fault_fingerprint)``.

Bitset-coloring memory bound (ISSUE 5): a naive DSATUR adjacency for an
O(p^2)-message alltoall would need ``p^2 msgs x p^2/64`` uint64 words
(~2e10 at p=1152); even per-message forbidden-color sets over all R
colors are ``M x R/64`` words.  ``ColorRounds`` therefore colors through
a sliding 64-color window whose packed per-(processor, side) bitsets are
O(p) total, with one transient uint64 per candidate.

Passes
------
A pass is an object with ``.name`` (parameter-bearing: it feeds
:func:`pipeline_fingerprint`), ``.recipe_safe`` and a pure
``.apply(cs) -> CompiledSchedule`` that never mutates its input.

* :class:`ColorRounds` — **conflict-graph coloring packer**:
  messages are the vertices of a conflict graph whose edges are the port
  budget, the intra/inter class-purity rule and the causality partial
  order exported by :func:`repro.core.validate.block_dependencies`;
  rounds are the colors.  Not provably never slower, so the selector
  *races* every ``opt:`` candidate against its unoptimized base.
* :class:`RepairSchedule` — **fault repair** (ISSUE 6): rewrite a healthy
  schedule so it stays correct and routable on a degraded machine
  (:mod:`repro.core.faults`).  Dead *nodes* are unrepairable by rewrite
  (their data is gone): the pass raises :class:`repro.core.faults.
  UnrepairableFaultError` and :func:`repair_schedule` reverts to the
  input, deferring to the elastic layer's remesh.

:func:`run_passes` applies a pass list in order inside an ``optimize``
span with one ``pass:{name}`` span and :class:`PassRecord` per pass.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Sequence

import numpy as np

from repro.core.faults import (
    FaultSpec,
    UnrepairableFaultError,
    degradation_of,
)
from repro.core.schedule_ir import (
    CompiledSchedule,
    gather_block_csr,
    relay_messages,
    segmented_arange,
)
from repro.core.topology import Machine, Topology
from repro.core.validate import block_dependencies, validate_schedule
from repro.obs import metrics as obs_metrics
from repro.obs.trace import TRACER

__all__ = [
    "ColorRounds",
    "RepairSchedule",
    "repair_schedule",
    "PassRecord",
    "run_passes",
    "optimize_pipeline",
    "choose_color_budget",
    "pipeline_fingerprint",
    "mode_fingerprint",
    "PASS_PIPELINE_VERSION",
]

#: Version salt for :func:`pipeline_fingerprint`.  Bump whenever a pass's
#: *semantics* change without its ``name`` changing — the optimized-schedule
#: cache in :mod:`repro.core.schedule_ir` keys on the fingerprint, so a bump
#: invalidates every cached rewrite produced by the old semantics.
PASS_PIPELINE_VERSION = "pr5.1"


def pipeline_fingerprint(passes: Sequence) -> str:
    """Stable fingerprint of a pass pipeline: the version salt plus every
    pass's parameter-bearing ``name``, hashed.  Two pipelines with the same
    fingerprint produce the same rewrite on the same input, so the
    process-wide schedule cache may key optimized entries on it."""
    names = ",".join(ps.name for ps in passes)
    raw = f"{PASS_PIPELINE_VERSION}|{names}"
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def optimize_pipeline(mode: str, topo: Topology) -> list:
    """The pass list of the ``optimize=`` value ``mode`` on ``topo``.  The
    one value is ``"color"``: the structural :class:`ColorRounds` packer
    the selector's ``opt:`` candidates run.  Anything else raises
    ``ValueError``."""
    if mode != "color":
        raise ValueError(
            f"unknown optimize mode {mode!r}; expected 'color' (or None)"
        )
    return [ColorRounds(limit=None, procs_per_node=topo.procs_per_node)]


def mode_fingerprint(mode: str, topo: Topology) -> str:
    """The current fingerprint of the ``mode`` pipeline as it would be
    instantiated for ``topo`` — the validity check the on-disk artifact
    store (:mod:`repro.store`) runs at warm-start: a persisted optimized
    entry whose recorded fingerprint no longer equals
    ``mode_fingerprint(entry.optimize, entry.topo)`` was produced by a
    pipeline that has since changed (version salt bump or pass/parameter
    change) and must be evicted, not served."""
    return pipeline_fingerprint(optimize_pipeline(mode, topo))


# ---------------------------------------------------------------------------
# Passes.  A pass is any object with .name and .apply(cs) -> CompiledSchedule
# (pure: the input schedule is never mutated).
# ---------------------------------------------------------------------------


# --- bitset coloring helpers (ISSUE 5) -------------------------------------

#: bit weight of each color slot in a 64-color window.
_BITW = np.uint64(1) << np.arange(64, dtype=np.uint64)
_U0 = np.uint64(0)
_U1 = np.uint64(1)
_UALL = np.uint64(0xFFFFFFFFFFFFFFFF)
#: low-mask table: _BIT_LOW[i] has bits 0..i-1 set (colors below slot i).
_BIT_LOW = _BITW - _U1


def _ctz64(x: np.ndarray) -> np.ndarray:
    """Index of the lowest set bit of each (nonzero) ``uint64``.  The
    isolated low bit is a power of two, which float64 represents exactly up
    to 2**63, so ``log2`` of the isolated bit is exact."""
    low = x & (~x + _U1)
    return np.log2(low.astype(np.float64)).astype(np.int64)


def _side_groups(keys: np.ndarray, prank: np.ndarray):
    """Sort one endpoint side's candidates by ``(keys, prank)`` — a single
    argsort on the fused key, since prank values are globally unique — and
    return ``(order, firsts, start_idx, gid_ord)``: the sort order, the
    group-first flags, the index (into the sorted array) of each element's
    group leader, and each sorted element's group id."""
    n = keys.size
    mul = np.int64(prank.max()) + 1 if n else np.int64(1)
    order = np.argsort(keys * mul + prank)
    sk = keys[order]
    firsts = np.ones(n, dtype=bool)
    if n:
        firsts[1:] = sk[1:] != sk[:-1]
    start_idx = np.maximum.accumulate(np.where(firsts, np.arange(n), 0))
    gid_ord = np.cumsum(firsts) - 1
    return order, firsts, start_idx, gid_ord


def _dag_depth(dep_ptr: np.ndarray, dep_ids: np.ndarray) -> int:
    """Critical-path length (in messages) of the block-dependency DAG: a
    lower bound on any coloring's round count.  Wave relaxation over the
    CSR — one ``reduceat`` per level, and the level count is the answer."""
    M = dep_ptr.size - 1
    rows = np.flatnonzero(np.diff(dep_ptr))
    if rows.size == 0:
        return 1 if M else 0
    starts = dep_ptr[rows]
    depth = np.ones(M, dtype=np.int64)
    for _ in range(M):
        upd = np.maximum.reduceat(depth[dep_ids], starts) + 1
        if bool((depth[rows] >= upd).all()):
            break
        depth[rows] = np.maximum(depth[rows], upd)
    return int(depth.max())


#: the ``ColorRounds`` budget ladder: port budget ``mult * cs.k``
_COLOR_RUNGS = (1, 2, 4, 8)


def choose_color_budget(
    cs: CompiledSchedule,
    *,
    dep_csr: tuple[np.ndarray, np.ndarray] | None = None,
) -> tuple[int, int]:
    """Structural budget chooser: pick the ``ColorRounds``
    ladder rung ``mult`` of :data:`_COLOR_RUNGS` (port budget
    ``mult * cs.k``) without coloring or simulating anything.

    The packed color count is lower-bounded by ``max(ceil(msgs/L))`` over
    senders and receivers and by the block-dependency critical path; the
    chooser takes the deepest rung that still shrinks that bound.  In the
    alpha-dominated regime deeper packing amortizes more per-round
    latencies, and the selector races ``opt:`` candidates against their
    bases anyway.  The choice reads no message sizes, so it is
    payload-independent (the recipe cache relies on it).

    Returns ``(mult, limit)``.
    """
    p, M = cs.p, cs.num_msgs
    k = max(cs.k, 1)
    mults = _COLOR_RUNGS
    if M == 0:
        return mults[0], max(mults[0] * k, 1)
    if dep_csr is None:
        dep_csr = block_dependencies(cs)
    depth = _dag_depth(*dep_csr)
    ms = np.bincount(cs.src, minlength=p)
    mr = np.bincount(cs.dst, minlength=p)

    def colors_lb(limit: int) -> int:
        return int(
            max(
                -(-ms.max() // limit),
                -(-mr.max() // limit),
                depth,
                1,
            )
        )

    best = mults[0]
    best_lb = colors_lb(max(mults[0] * k, 1))
    for m in mults[1:]:
        lb = colors_lb(max(m * k, 1))
        if lb < best_lb:
            best, best_lb = m, lb
    return best, max(best * k, 1)


class ColorRounds:
    """Conflict-graph coloring round packer: DSATUR-style greedy coloring at
    **message** granularity (ISSUE 4 tentpole).

    The conflict graph has one vertex per message; rounds are the colors.
    Two messages conflict — cannot share a color — through

    * **port budget**: more than ``limit`` messages sharing a sender (or a
      receiver) cannot be concurrent.  ``limit=None`` resolves to the rung
      :func:`choose_color_budget` picks (the planner's ``"color"``
      pipeline); an explicit ``limit`` is the repair's surviving lane
      budget;
    * **class purity**: a per-processor intra/inter mixing ban at message
      granularity — mixing re-prices a processor's on-node bytes at
      network alpha/beta, so an intra message that was intra-priced in the
      *input* round may never share a color with off-node traffic at
      either endpoint; an intra message whose input round already carried
      off-node traffic at that endpoint was already network-priced, so
      packing it with inter traffic re-prices nothing (this is what lets
      the packer reproduce — and then beat — input rounds that themselves
      mix classes, e.g. the k-ported trees' node-boundary waves);
    * **causality**: the partial order exported by
      :func:`repro.core.validate.block_dependencies` — a message is colored
      strictly after every provider of a block it forwards.

    Coloring order is the DSATUR recipe adapted to capacities, batched
    (ISSUE 5 tentpole rewrite): colors are filled in **64-color windows**
    whose per-(processor, side) state is packed ``uint64`` bitsets — one
    bit per window color for "at port capacity", "has off-node (A)
    traffic", and "has intra-priced on-node (C) traffic".  Every batch
    iteration assigns *many colors at once*: each dependency-ready
    candidate's forbidden-color set is a handful of bitwise ORs over the
    bitsets of its two endpoints, its target color is the lowest clear bit
    at or above its per-sender chunk slot (position in the sender's
    priority queue divided by the budget — exactly where sequential
    per-color filling would land it), and per-(endpoint, color) conflicts
    are resolved by priority rank in one sort.  The loop runs over 64-color
    windows with a few batch iterations each; there is no per-message
    Python anywhere.

    **Memory bound**: the window holds one uint64 per (processor, side,
    state-kind) plus a ``[p, 64]`` count grid, i.e. O(p) — candidates
    carry one transient uint64 each.  If the packing degenerates
    (pathological inputs), the pass still terminates: each window
    advances monotonically.

    The pass reads no message sizes, so it is payload-independent
    (``recipe_safe``).  The result is not a pure round union of its input,
    so it is *not* provably never slower; the selector races it against
    the unoptimized base.  Requires block metadata.
    """

    #: payload-independent message permutation + re-rounding: the schedule
    #: cache runs it once per structure and replays it as a recipe.
    recipe_safe = True

    def __init__(self, limit: int | None = None, *, procs_per_node: int):
        self.limit = limit
        self.procs_per_node = procs_per_node
        lim = "auto" if limit is None else str(limit)
        self.name = f"color_rounds[limit={lim},n={procs_per_node}]"

    def apply(self, cs: CompiledSchedule) -> CompiledSchedule:
        if not cs.has_blocks:
            raise ValueError(
                "ColorRounds needs block metadata to honour the "
                "dependency DAG; generate the schedule with blocks"
            )
        n = self.procs_per_node
        p, R, M = cs.p, cs.num_rounds, cs.num_msgs
        if p % n:
            raise ValueError(f"p={p} not divisible by procs_per_node={n}")
        if R <= 1 or M == 0:
            return cs

        # --- causality DAG + transpose (provider -> dependents) -----------
        dep_ptr, dep_ids = block_dependencies(cs)
        remaining = np.diff(dep_ptr).astype(np.int64)  # uncolored providers
        dep_req = np.repeat(np.arange(M, dtype=np.int64), np.diff(dep_ptr))
        t_ids = dep_req[np.argsort(dep_ids, kind="stable")]
        t_ptr = np.zeros(M + 1, dtype=np.int64)
        np.cumsum(np.bincount(dep_ids, minlength=M), out=t_ptr[1:])

        if self.limit is not None:
            limit = max(self.limit, 1)
        else:
            _, limit = choose_color_budget(cs, dep_csr=(dep_ptr, dep_ids))

        # --- per-side traffic categories for the class-purity test --------
        # A (=2): off-node; C (=0): on-node, intra-priced in the input
        # round; B (=1): on-node but the endpoint already had off-node
        # traffic in its input round, i.e. already network-priced.  Packing
        # may mix A with B freely; A with C would re-price C's bytes
        # upward, so it is banned per (processor, side, color).
        inter = (cs.src // n) != (cs.dst // n)
        st_in = cs.stats(n)
        rid_in = cs.round_ids()
        cat_s = np.where(
            inter, 2, st_in.send_inter[rid_in, cs.src].astype(np.int8)
        ).astype(np.int8)
        cat_r = np.where(
            inter, 2, st_in.recv_inter[rid_in, cs.dst].astype(np.int8)
        ).astype(np.int8)

        # --- saturation-degree priority (static proxy) --------------------
        # conflict degree = messages competing for either endpoint's port;
        # ties break in generation order, which keeps the phase structure
        # of regular schedules intact.
        deg = (
            np.bincount(cs.src, minlength=p)[cs.src]
            + np.bincount(cs.dst, minlength=p)[cs.dst]
        )
        prank = np.empty(M, dtype=np.int64)
        prank[np.argsort(-deg, kind="stable")] = np.arange(M, dtype=np.int64)

        # per-sender queues in priority order (CSR over src) — one fused-key
        # argsort (prank is a permutation, so the key is collision-free)
        pool = np.argsort(cs.src * np.int64(M) + prank)
        qptr = np.zeros(p + 1, dtype=np.int64)
        np.cumsum(np.bincount(cs.src, minlength=p), out=qptr[1:])
        head = qptr[:-1].copy()
        qend = qptr[1:]

        # per-(processor, side) per-color message caps: the port budget
        lim_s = lim_r = np.full(p, limit, dtype=np.int64)
        span_cap = lim_s * 64  # max placeable per sender per window

        color_of = np.full(M, -1, dtype=np.int64)
        floor = np.zeros(M, dtype=np.int64)  # min color from providers
        done = np.zeros(M, dtype=bool)
        uncolored = M
        base = 0  # first color of the current 64-color window
        while uncolored:
            # --- fresh window state: packed uint64 bitsets per (proc, side)
            s_cnt = np.zeros((p, 64), dtype=np.int32)
            r_cnt = np.zeros((p, 64), dtype=np.int32)
            full_s = np.zeros(p, dtype=np.uint64)  # at-capacity colors
            full_r = np.zeros(p, dtype=np.uint64)
            hasA_s = np.zeros(p, dtype=np.uint64)  # off-node traffic colors
            hasA_r = np.zeros(p, dtype=np.uint64)
            hasC_s = np.zeros(p, dtype=np.uint64)  # intra-priced colors
            hasC_r = np.zeros(p, dtype=np.uint64)
            # advance queue heads to each sender's first uncolored entry —
            # one cumulative sum + searchsorted per *window*, then build the
            # window's candidate pool once: per sender, the queue prefix the
            # window's colors can hold.  Dependency-blocked entries stay in
            # the pool (they may become ready mid-window); batch iterations
            # below only ever shrink it.
            pre = np.zeros(M + 1, dtype=np.int64)
            np.cumsum(~done[pool], out=pre[1:])
            head = np.minimum(np.searchsorted(pre, pre[head] + 1) - 1, qend)
            sizes = np.minimum(qend - head, span_cap)
            W = pool[np.repeat(head, sizes) + segmented_arange(sizes)]
            W = W[~done[W]]
            wlive = np.ones(W.size, dtype=bool)  # uncolored, not deferred
            wtry = np.full(W.size, -1, dtype=np.int64)  # last tried color
            while uncolored:
                # ~done guards entries colored through the escape path
                ok = wlive & (~done[W]) & (remaining[W] == 0)
                ok_idx = np.flatnonzero(ok)
                cand = W[ok_idx]
                escape = False
                if not cand.size:
                    if not bool((wlive & ~done[W]).any()):
                        break  # pool fully consumed: advance the window
                    ready = np.flatnonzero((~done) & (remaining == 0))
                    if not ready.size:
                        raise AssertionError(
                            "ColorRounds: unfinished coloring with no ready "
                            "message — cyclic block dependencies (invalid "
                            "input)"
                        )
                    # the pool holds only dependency-blocked entries but
                    # ready work hides beyond a blocked sender prefix
                    # (rare): feed the top-priority ready message through
                    # the same batched machinery to unjam the pool
                    cand = ready[[int(np.argmin(prank[ready]))]]
                    escape = True
                csrc, cdst = cs.src[cand], cs.dst[cand]
                cas, car = cat_s[cand], cat_r[cand]
                # tentative chunk slot on first consideration: position
                # among the sender's pending candidates (cand is
                # sender-major in priority order) plus its already-placed
                # window load, divided by its cap — where sequential
                # per-color filling would land it.  Retries resume
                # *next-fit* from the color they last lost (class/capacity
                # losers bump minimally instead of herding or skipping
                # refillable slots).
                runs = np.ones(cand.size, dtype=bool)
                runs[1:] = csrc[1:] != csrc[:-1]
                gstart = np.maximum.accumulate(
                    np.where(runs, np.arange(cand.size), 0)
                )
                pos = np.arange(cand.size) - gstart
                used = s_cnt.sum(axis=1, dtype=np.int64)
                lo = (used[csrc] + pos) // lim_s[csrc]
                if not escape:
                    last = wtry[ok_idx]
                    lo = np.where(last < 0, lo, last + 1)
                lo = np.maximum(lo, floor[cand] - base)
                # forbidden colors: packed bitset adjacency — port-full
                # colors at either endpoint, class-purity conflicts, and
                # everything below the chunk/causality floor
                defer = lo >= 64
                lo_c = np.clip(lo, 0, 63).astype(np.uint64)
                forbid = (_BIT_LOW[lo_c] | full_s[csrc] | full_r[cdst])
                forbid |= np.where(cas == 0, hasA_s[csrc], _U0)
                forbid |= np.where(cas == 2, hasC_s[csrc], _U0)
                forbid |= np.where(car == 0, hasA_r[cdst], _U0)
                forbid |= np.where(car == 2, hasC_r[cdst], _U0)
                forbid = np.where(defer, _UALL, forbid)
                free = ~forbid
                alive = free != _U0
                if not escape:
                    # window exhausted for these candidates: out of the
                    # pool until the next window
                    wlive[ok_idx[~alive]] = False
                if not alive.any():
                    if escape:
                        break  # not even the escape fits: advance window
                    continue  # deferred some; recheck what remains
                widx = ok_idx[alive] if not escape else None
                cand, csrc, cdst = cand[alive], csrc[alive], cdst[alive]
                cas, car = cas[alive], car[alive]
                crel = _ctz64(free[alive])
                if widx is not None:
                    wtry[widx] = crel  # losers resume next-fit from here
                pr = prank[cand]
                # one fused-key sort per endpoint side serves both the
                # class-purity and the capacity selection
                sides = []
                for procs, cats in ((csrc, cas), (cdst, car)):
                    sides.append(
                        (procs, cats, *_side_groups(procs * 64 + crel, pr))
                    )
                # class purity inside this batch: per (endpoint, color)
                # group the highest-priority candidate decides which of
                # A/C survives (B mixes with both)
                sel = np.ones(cand.size, dtype=bool)
                for procs, cats, order, firsts, start_idx, gid_ord in sides:
                    cats_ord = cats[order]
                    first_cat = cats_ord[start_idx]
                    hasA = (np.bincount(gid_ord, cats_ord == 2) > 0)[gid_ord]
                    drop = (
                        (cats_ord == 0) & hasA & (first_cat != 0)
                    ) | ((cats_ord == 2) & (first_cat == 0))
                    sel[order[drop]] = False
                # capacity: top surviving takers per (endpoint, color) in
                # priority order, sender side first (mirrors the
                # sequential packer); survivor rank via a prefix sum over
                # the already-sorted groups
                for (procs, cats, order, firsts, start_idx, _), cnt, lim in (
                    (sides[0], s_cnt, lim_s), (sides[1], r_cnt, lim_r),
                ):
                    k_ord = sel[order].astype(np.int64)
                    ex = np.cumsum(k_ord) - k_ord  # survivors before elem
                    surv = ex - ex[start_idx]
                    po, co = procs[order], crel[order]
                    bad = (k_ord != 0) & (surv >= (lim[po] - cnt[po, co]))
                    sel[order[bad]] = False
                tsel = np.flatnonzero(sel)
                if not tsel.size:
                    # guaranteed progress: the top-priority live candidate
                    # alone is always legal at its free color
                    tsel = np.array([int(np.argmin(pr))], dtype=np.int64)
                take, tcrel = cand[tsel], crel[tsel]
                tsrc, tdst = csrc[tsel], cdst[tsel]
                done[take] = True
                if widx is not None:
                    wlive[widx[tsel]] = False
                col = base + tcrel
                color_of[take] = col
                uncolored -= int(take.size)
                # --- update window state: counts, then OR the new bits
                # straight into the packed bitsets (counts only grow and
                # caps are static, so a full/class bit never clears)
                s_cnt += np.bincount(
                    tsrc * 64 + tcrel, minlength=p * 64
                ).reshape(p, 64).astype(np.int32)
                r_cnt += np.bincount(
                    tdst * 64 + tcrel, minlength=p * 64
                ).reshape(p, 64).astype(np.int32)
                fs = s_cnt[tsrc, tcrel] >= lim_s[tsrc]
                np.bitwise_or.at(full_s, tsrc[fs], _BITW[tcrel[fs]])
                fr = r_cnt[tdst, tcrel] >= lim_r[tdst]
                np.bitwise_or.at(full_r, tdst[fr], _BITW[tcrel[fr]])
                tcs, tcr = cat_s[take], cat_r[take]
                np.bitwise_or.at(
                    hasA_s, tsrc[tcs == 2], _BITW[tcrel[tcs == 2]]
                )
                np.bitwise_or.at(
                    hasC_s, tsrc[tcs == 0], _BITW[tcrel[tcs == 0]]
                )
                np.bitwise_or.at(
                    hasA_r, tdst[tcr == 2], _BITW[tcrel[tcr == 2]]
                )
                np.bitwise_or.at(
                    hasC_r, tdst[tcr == 0], _BITW[tcrel[tcr == 0]]
                )
                rep = t_ptr[take + 1] - t_ptr[take]
                if int(rep.sum()):  # release dependents of new providers
                    hit = np.repeat(t_ptr[take], rep) + segmented_arange(rep)
                    dmsg = t_ids[hit]
                    remaining -= np.bincount(dmsg, minlength=M)
                    np.maximum.at(floor, dmsg, np.repeat(col, rep) + 1)
            base += 64

        g = int(color_of.max()) + 1
        if g == R and bool((color_of == cs.round_ids()).all()):
            return cs  # coloring reproduced the input rounds
        morder = np.argsort(color_of, kind="stable")
        new_ptr = np.zeros(g + 1, dtype=np.int64)
        np.cumsum(np.bincount(color_of, minlength=g), out=new_ptr[1:])
        blk_ptr, blk_ids = gather_block_csr(cs.blk_ptr, cs.blk_ids, morder)
        return dataclasses.replace(
            cs,
            src=cs.src[morder],
            dst=cs.dst[morder],
            elems=cs.elems[morder],
            round_ptr=new_ptr,
            blk_ptr=blk_ptr,
            blk_ids=blk_ids,
            _stats={},
        )


class RepairSchedule:
    """Fault-repair rewrite (ISSUE 6 tentpole): make a schedule correct and
    routable on the degraded machine described by a
    :class:`~repro.core.faults.FaultSpec`.

    Two rewrite steps, both pure array surgery:

    1. **Relay off dead network ports.**  Every inter-node message whose
       sender or receiver lost its network port is rerouted through the
       lowest-numbered surviving live-port rank on the same node
       (:func:`repro.core.schedule_ir.relay_messages`): an intra-node
       stage-out hop before the original round and/or a stage-in hop after
       it.  Every hop carries the full payload and block slice, so block
       semantics are bit-identical — the relay acquires strictly before it
       forwards, and the final owner still receives every block before any
       round that consumes it.  Intra-node messages are untouched (shared
       memory does not ride the NIC).
    2. **Re-pack under the reduced lane budget.**  When the fault set
       shrinks a node's surviving rails below the schedule's packing width
       (or step 1 staged new hops), the schedule is re-colored with the
       existing bitset :class:`ColorRounds` at ``limit = min surviving
       lanes`` — the same packer the optimizer uses, so a repaired
       ``opt:`` schedule keeps its packed structure wherever the budget
       still allows it.

    Unrepairable faults — a dead *node* whose traffic the schedule still
    carries (its data is gone), or a dead-port endpoint with no surviving
    live-port rank on its node — raise
    :class:`~repro.core.faults.UnrepairableFaultError`; the
    :func:`repair_schedule` driver catches it and reverts (repair is a
    rewrite, never a regeneration — regeneration on a shrunk topology is
    the elastic layer's ``plan_remesh`` job, not the repairer's).

    The rewrite relays payloads (duplicating ``elems`` across hops), so it
    is *not* recipe-cacheable; degraded entries are cached per fault
    fingerprint by ``schedule_ir.compiled_schedule(faults=...)`` instead.
    """

    recipe_safe = False

    def __init__(self, spec: FaultSpec, *, topo: Topology):
        spec.validate(topo)
        self.spec = spec
        self.topo = topo
        self.name = (
            f"repair_schedule[{spec.fingerprint()},n={topo.procs_per_node}]"
        )

    def apply(self, cs: CompiledSchedule) -> CompiledSchedule:
        spec, topo = self.spec, self.topo
        if spec.is_healthy or cs.num_msgs == 0:
            return cs
        if cs.p != topo.p:
            raise ValueError(
                f"schedule has p={cs.p} but repair topology has p={topo.p}"
            )
        N, n = topo.num_nodes, topo.procs_per_node
        deg = degradation_of(spec, topo)

        # dead nodes: their ranks' data is unreachable — no rewrite can
        # deliver it, so any schedule still touching them is unrepairable
        if deg.dead_node.any():
            touched = deg.dead_rank[cs.src] | deg.dead_rank[cs.dst]
            if bool(touched.any()):
                raise UnrepairableFaultError(
                    f"dead node(s) {list(spec.dead_nodes)} own data the "
                    "schedule must route; rewrite cannot preserve block "
                    "semantics — shrink the job (plan_remesh) instead"
                )

        relayed = False
        if deg.dead_port.any():
            inter = (cs.src // n) != (cs.dst // n)
            need_src = inter & deg.dead_port[cs.src]
            need_dst = inter & deg.dead_port[cs.dst]
            if bool(need_src.any()) or bool(need_dst.any()):
                live = (~deg.dead_port).reshape(N, n)
                has_live = live.any(axis=1)
                proxy = np.where(
                    has_live,
                    np.arange(N, dtype=np.int64) * n + np.argmax(live, axis=1),
                    -1,
                )
                if bool(
                    (need_src & (proxy[cs.src // n] < 0)).any()
                    or (need_dst & (proxy[cs.dst // n] < 0)).any()
                ):
                    raise UnrepairableFaultError(
                        "a node lost every live network port; no surviving "
                        "local rank to relay through — shrink the job instead"
                    )
                via_src = np.where(need_src, proxy[cs.src // n], -1)
                via_dst = np.where(need_dst, proxy[cs.dst // n], -1)
                rsp = TRACER.start(
                    "repair.relay",
                    relayed_src=int(need_src.sum()),
                    relayed_dst=int(need_dst.sum()),
                ) if TRACER else None
                try:
                    cs = relay_messages(cs, via_src, via_dst)
                except BaseException:
                    if rsp:
                        TRACER.finish(rsp, outcome="error")
                    raise
                if rsp:
                    TRACER.finish(rsp, msgs_after=cs.num_msgs)
                obs_metrics.counter("repair.relayed_msgs").inc(
                    int(need_src.sum()) + int(need_dst.sum())
                )
                relayed = True

        # reduced per-node port budget: the narrowest surviving lane count
        alive_lanes = deg.lanes[~deg.dead_node]
        k_eff = max(1, int(alive_lanes.min())) if alive_lanes.size else 1
        if relayed or cs.max_port_width() > k_eff:
            trigger = "relayed" if relayed else "overwidth"
            psp = TRACER.start("repair.repack", k_eff=k_eff,
                               trigger=trigger) if TRACER else None
            try:
                cs = ColorRounds(limit=k_eff, procs_per_node=n).apply(cs)
            except BaseException:
                if psp:
                    TRACER.finish(psp, outcome="error")
                raise
            if psp:
                TRACER.finish(psp, rounds_after=cs.num_rounds)
            obs_metrics.counter("repair.repacks").inc()
        return cs


def repair_schedule(
    cs: CompiledSchedule,
    spec: FaultSpec,
    *,
    topo: Topology | None = None,
    machine: Machine | None = None,
    validate: bool = True,
) -> tuple[CompiledSchedule, list[PassRecord]]:
    """One-call fault repair: rewrite ``cs`` for the degraded machine and
    oracle-check the result; returns ``(repaired, records)``.

    The revert contract (graceful degradation): when the fault set is
    unrepairable by rewrite — dead nodes, or a node with no surviving
    live-port rank — the input schedule is returned *unchanged* with an
    ``applied=False`` record, never an exception.  Callers that must make
    progress anyway (the selector's fallback ladder, the chaos harness)
    pair the revert with an elastic remesh; the degraded simulator prices
    the un-repaired schedule at ``inf``, so a reverted repair can never
    win a selection race.  A genuinely broken rewrite (oracle violation)
    still raises — corruption is a bug, not a degraded mode.
    """
    if topo is None and machine is not None:
        topo = machine.topo
    if topo is None:
        raise ValueError("repair_schedule needs topo= or machine=")
    ps = RepairSchedule(spec, topo=topo)
    t0 = time.perf_counter()
    sp = TRACER.start("repair", fingerprint=spec.fingerprint()) if TRACER \
        else None
    try:
        new = ps.apply(cs)
    except UnrepairableFaultError:
        obs_metrics.counter("repair.reverted").inc()
        if sp:
            TRACER.finish(sp, applied=False, outcome="unrepairable")
        return cs, [
            PassRecord(
                name=ps.name,
                applied=False,
                rounds_before=cs.num_rounds,
                rounds_after=cs.num_rounds,
                msgs_before=cs.num_msgs,
                msgs_after=cs.num_msgs,
                wall_s=time.perf_counter() - t0,
                oracle_ok=None,
            )
        ]
    except BaseException:
        if sp:
            TRACER.finish(sp, applied=False, outcome="error")
        raise
    ok = None
    if validate and new is not cs:
        osp = TRACER.start("repair.oracle") if TRACER else None
        tv = time.perf_counter()
        try:
            report = validate_schedule(new)
        except BaseException:
            if osp:
                TRACER.finish(osp, outcome="error")
            raise
        obs_metrics.counter("repair.oracle_checks").inc()
        obs_metrics.gauge("repair.last_oracle_verify_s").set(
            time.perf_counter() - tv
        )
        ok = report.ok
        if osp:
            TRACER.finish(osp, ok=ok)
        if not ok and sp:
            TRACER.finish(sp, applied=False, outcome="oracle_violation")
        report.raise_if_invalid()
    obs_metrics.counter("repair.applied" if new is not cs
                        else "repair.noop").inc()
    if sp:
        TRACER.finish(sp, applied=new is not cs, outcome="ok",
                      rounds_after=new.num_rounds, msgs_after=new.num_msgs)
    return new, [
        PassRecord(
            name=ps.name,
            applied=new is not cs,
            rounds_before=cs.num_rounds,
            rounds_after=new.num_rounds,
            msgs_before=cs.num_msgs,
            msgs_after=new.num_msgs,
            wall_s=time.perf_counter() - t0,
            oracle_ok=ok,
        )
    ]


# ---------------------------------------------------------------------------
# Running a pass list.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PassRecord:
    """What one pass did to the schedule it was given.  ``oracle_ok`` is
    None when the result was not oracle-checked (no validation asked, or
    the pass returned its input unchanged)."""

    name: str
    applied: bool
    rounds_before: int
    rounds_after: int
    msgs_before: int
    msgs_after: int
    wall_s: float
    oracle_ok: bool | None = None


def run_passes(
    cs: CompiledSchedule, passes: Sequence
) -> tuple[CompiledSchedule, list[PassRecord]]:
    """Apply ``passes`` in order, each to the previous one's output, and
    return the result with one :class:`PassRecord` per pass.  Traced, the
    run is an ``optimize`` span holding one ``pass:{name}`` span per pass.

    Validation is the caller's: the schedule cache's recipe path
    (:mod:`repro.core.schedule_ir`) oracle-checks the first application of
    the recorded permutation, and :func:`repair_schedule` checks every
    repair."""
    records: list[PassRecord] = []
    run_sp = TRACER.start(
        "optimize", passes=[ps.name for ps in passes]
    ) if TRACER else None
    try:
        for ps in passes:
            sp = TRACER.start(f"pass:{ps.name}") if TRACER else None
            t0 = time.perf_counter()
            try:
                new = ps.apply(cs)
            except BaseException:
                if sp:
                    TRACER.finish(sp, outcome="error")
                raise
            rec = PassRecord(
                name=ps.name,
                applied=new is not cs,
                rounds_before=cs.num_rounds,
                rounds_after=new.num_rounds,
                msgs_before=cs.num_msgs,
                msgs_after=new.num_msgs,
                wall_s=time.perf_counter() - t0,
            )
            if sp:
                TRACER.finish(
                    sp, applied=rec.applied,
                    rounds_before=rec.rounds_before,
                    rounds_after=rec.rounds_after,
                    msgs_before=rec.msgs_before, msgs_after=rec.msgs_after,
                )
            records.append(rec)
            cs = new
    except BaseException:
        if run_sp:
            TRACER.finish(run_sp, outcome="error")
        raise
    if run_sp:
        TRACER.finish(run_sp, outcome="ok",
                      applied=sum(1 for r in records if r.applied))
    return cs, records
