"""Drive the planner: ``repro.api.plan_batch([request])`` and then
``Plan.schedule()``, one request at a time, closed loop.

Every request is an expert-parallel dispatch alltoall on one of the
configuration's meshes: ``T`` tokens per chip, each sent to ``top_k``
experts, split evenly over the ``P`` chips of the group, so the per-pair
block is ``T * top_k * hidden // P`` elements.  The requests come from a
fixed population drawn once from the traffic file's own seed (``T``
log-uniform in ``[tokens_min, tokens_max]``, meshes alternating), so every
run plans the same kind of work; the run's seed sets the order, stratified
so that every stretch of the stream holds the same mix of meshes and sizes
(see :func:`stratified_order`).  Set-up plans one request per mesh, as a
job pins its plans when it starts, and builds each family the race can
pick once.  Set-up also lets the C allocator keep what it frees (see
:func:`keep_freed_memory`).

A sample of the completed requests, drawn from the seed, is checked after
the window: each schedule's data flow is replayed by a plain check
(``chipbench/refs/schedule_replay.py``) and every breach of delivery is
counted.
"""

from __future__ import annotations

import ctypes
import dataclasses
import time

import numpy as np

from chipbench.harness import Compared
from chipbench.refs import schedule_replay


# glibc mallopt parameters
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3


def keep_freed_memory() -> None:
    """Have glibc serve blocks up to 32 MiB from its heap, grow the heap 64
    MiB at a time and keep up to 1 GiB freed at its top rather than give it
    back.  The planner's caches grow
    through the window's first seconds; with glibc's defaults each growth
    maps and faults in fresh pages, which on a kernel where that is costly
    (gVisor on a TPU v5e host: 240-260 requests in the first second against
    some 430 later) made the start of every window slower than the rest, by
    an amount that varied from run to run."""
    libc = ctypes.CDLL("libc.so.6")
    libc.mallopt(_M_MMAP_THRESHOLD, 32 << 20)
    libc.mallopt(_M_TRIM_THRESHOLD, 1 << 30)
    libc.mallopt(_M_TOP_PAD, 64 << 20)


def population(traffic: dict, hidden: int, top_k: int) -> list[tuple]:
    """The fixed list of (num_nodes, procs_per_node, k_lanes, payload)."""
    rng = np.random.default_rng(traffic["population_seed"])
    lo, hi = np.log(traffic["tokens_min"]), np.log(traffic["tokens_max"] + 1)
    tokens = np.floor(np.exp(rng.uniform(lo, hi, traffic["population"])))
    meshes = traffic["meshes"]
    out = []
    for i, t in enumerate(tokens.astype(np.int64)):
        nn, ppn, kl = meshes[i % len(meshes)]
        out.append((nn, ppn, kl, max(1, int(t) * top_k * hidden // (nn * ppn))))
    return out


def stratified_order(requests, seed: int, stratum: int) -> np.ndarray:
    """The seed's order of the population: requests sorted by mesh and size
    are cut into strata of ``stratum`` neighbours, and the stream takes one
    request from every stratum (strata and members in the seed's order)
    before it takes a second from any.  A window completes a prefix of the
    stream, so every seed's prefix holds the same mix, and the seed changes
    the order of the work and not the work."""
    keys = np.array([(nn * ppn, c) for nn, ppn, _, c in requests])
    by_size = np.lexsort((keys[:, 1], keys[:, 0]))
    strata = by_size.reshape(-1, stratum)
    rng = np.random.default_rng(seed)
    strata = rng.permuted(strata, axis=1)[rng.permutation(len(strata))]
    return strata.T.reshape(-1)


class PlannerCell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        m = config["model"]
        self.traffic, self.seed = traffic, seed
        self.hidden, self.top_k = m["hidden_size"], m["num_experts_per_tok"]
        self.requests = population(traffic, self.hidden, self.top_k)
        self.order = stratified_order(self.requests, seed, traffic["stratum"])

    def _request(self, i: int):
        from repro import api

        nn, ppn, kl, c = self.requests[self.order[i % len(self.order)]]
        return api.PlanRequest("alltoall", c, num_nodes=nn,
                               procs_per_node=ppn, k_lanes=kl)

    def setup(self) -> None:
        from repro import api

        keep_freed_memory()
        t = self.traffic["tokens_max"]  # the pinned plan: a prefill chunk
        for nn, ppn, kl in self.traffic["meshes"]:
            c = t * self.top_k * self.hidden // (nn * ppn)
            plan = api.plan_batch([api.PlanRequest(
                "alltoall", c, num_nodes=nn, procs_per_node=ppn,
                k_lanes=kl)])[0]
            # every family the race can pick builds its structure once
            # (an optimized family records the recipe it replays at other
            # payloads), so the window meets no first build
            for alg, _ in plan.candidates:
                dataclasses.replace(plan, algorithm=alg).schedule()

    def window(self, seconds: float, traced: bool) -> dict:
        from repro import api

        rng = np.random.default_rng(self.seed)
        keep_at = set(rng.choice(self.traffic["sample_from_first"],
                                 self.traffic["sampled_requests"],
                                 replace=False).tolist())
        kept, stamps, done, failed = [], [], 0, 0
        clock = time.perf_counter_ns
        t0 = time.perf_counter()
        deadline = t0 + seconds
        cs = None
        while time.perf_counter() < deadline:
            req = self._request(done)
            a = clock()
            plan = api.plan_batch([req])[0]
            b = clock()
            cs = plan.schedule()
            if traced:
                stamps.append((a, b, clock()))
            if done in keep_at:
                kept.append(cs)
            done += 1
        dt = time.perf_counter() - t0
        kept.append(cs)
        self.kept = kept
        return {"attempted": done, "failed": failed,
                "metrics": {"plan_ms": dt / done * 1e3},
                "info": {"requests": done, "seconds": dt, "stamps": stamps}}

    def free(self) -> None:
        pass

    def check(self) -> list[Compared]:
        bad = sum(schedule_replay.schedule_defects(cs) for cs in self.kept)
        return [Compared("schedule_defects", float(bad),
                         self.traffic["limits"]["schedule_defects"])]


def build(config, traffic, seed, devices) -> PlannerCell:
    return PlannerCell(config, traffic, seed, devices)


def calibrate(cell: PlannerCell, seeds, control_seeds) -> dict:
    """The program's breach count on ``seeds``; on ``control_seeds`` that of
    the same schedules with one guarantee broken: the last message of each
    schedule carries none of its blocks."""
    cell.setup()
    out = {"program": {}, "control": {}}
    for s in seeds:
        cell.seed = s
        cell.order = stratified_order(cell.requests, s,
                                      cell.traffic["stratum"])
        cell.window(2.0, traced=False)
        out["program"][s] = float(sum(schedule_replay.schedule_defects(cs)
                                      for cs in cell.kept))
        if s in control_seeds:
            bad = 0
            for cs in cell.kept:
                ptr = np.array(cs.blk_ptr)
                ptr[-1] = ptr[-2]
                bad += schedule_replay.defects(cs.p, cs.src, cs.dst,
                                               cs.round_ptr, ptr,
                                               cs.blk_ids[:ptr[-1]])
            out["control"][s] = float(bad)
    return out
