"""Batched serving engine: slot-based continuous batching over a fixed-size
decode batch.

``ServeEngine`` keeps ``num_slots`` independent sequences in one KV cache;
requests are admitted into free slots (prefill), all active slots decode in
lock-step (one ``decode_step`` per iteration — the shape the decode_32k /
long_500k dry-run cells lower), and finished sequences free their slot.

For simplicity each slot tracks its own length; attention masking uses the
global ``cache_pos`` per slot via per-slot position offsets — on this
framework's synchronized-decode cache (scalar cache_pos), admission pads
the new prompt to the current step so all slots share the write index, the
standard static-batching compromise (documented; per-slot paged caches are
the next step and orthogonal to the paper's collectives).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs.trace import TRACER

__all__ = ["Request", "ServeEngine", "greedy_sample", "temperature_sample"]

#: decode-step latency buckets (seconds): 100us .. 10s geometric — jit
#: warm-up lands in the top buckets, steady-state decode in the middle.
_STEP_EDGES = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0, 10.0)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32 (or [S, K] codebooks; [S, D] embeds)
    max_new_tokens: int = 32
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


def greedy_sample(logits: jax.Array, rng=None) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature_sample(temp: float) -> Callable:
    def fn(logits, rng):
        return jax.random.categorical(rng, logits / temp, axis=-1).astype(jnp.int32)

    return fn


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        num_slots: int = 4,
        capacity: int = 512,
        sampler: Callable = greedy_sample,
        seed: int = 0,
        monitor=None,
        plan_mesh: tuple[int, int, int] | None = None,
        replan_deadline_s: float = 0.25,
    ):
        if not cfg.embed_inputs:
            raise ValueError("serving engine drives token models")
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.capacity = capacity
        self.sampler = sampler
        self.rng = jax.random.PRNGKey(seed)
        self.slots: list[Request | None] = [None] * num_slots
        self.cache = None
        self.pos = 0  # synchronized cache position
        # optional fault/straggler hook: any object with observe(seconds)
        # and observe_fault(event) -> "ok"|"warn"|"evict" (duck-typed so the
        # jax-free decision layer repro.training.elastic.StragglerMonitor
        # plugs straight in).  run() times every decode step through it and
        # stops decoding on "evict" — the chaos harness drives this.
        self.monitor = monitor
        self.fault_events: list = []
        self.monitor_actions: list[str] = []
        # store-aware admission (ISSUE 10): with ``plan_mesh`` set the
        # decode-collective plans are pinned here, once, via plan_batch;
        # thereafter they replan only on an injected FaultEvent (under
        # the planner's backoff/deadline budget and circuit breaker)
        self.planner = None
        if plan_mesh is not None:
            from repro.serving.planner import DecodePlanner

            nn, ppn, kl = plan_mesh
            self.planner = DecodePlanner(
                num_slots=num_slots, d_model=cfg.d_model,
                num_codebooks=cfg.num_codebooks,
                num_nodes=nn, procs_per_node=ppn, k_lanes=kl,
                replan_deadline_s=replan_deadline_s,
            )

        self._decode = jax.jit(
            lambda p, t, c, i: lm.decode_step(cfg, p, t, c, i)
        )

    # ------------------------------------------------------------------
    def _tok_shape(self, n: int):
        k = self.cfg.num_codebooks
        return (self.num_slots, n, k) if k > 1 else (self.num_slots, n)

    def admit(self, requests: list[Request]) -> list[Request]:
        """Fill free slots; prefill runs over the padded batch of prompts.
        Returns the admitted subset."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        admitted = requests[: len(free)]
        if not admitted:
            return []
        max_len = max(len(r.prompt) for r in admitted)
        start = self.pos
        toks = np.zeros(self._tok_shape(start + max_len), np.int32)
        for slot, req in zip(free, admitted):
            p = np.asarray(req.prompt)
            toks[slot, start + max_len - len(p):start + max_len] = p
            self.slots[slot] = req
        lgts, cache = jax.jit(
            lambda p, b: lm.prefill(self.cfg, p, b, capacity=self.capacity)
        )(self.params, {"tokens": jnp.asarray(toks)})
        self.cache = cache
        self.pos = start + max_len
        # first sampled token from prefill logits
        self.rng, k = jax.random.split(self.rng)
        nxt = np.asarray(self.sampler(lgts, k))
        for slot, req in zip(free, admitted):
            req.out_tokens.append(nxt[slot].tolist())
        self._pending = jnp.asarray(
            nxt.reshape(self._tok_shape(1))
        )
        return admitted

    def step(self) -> None:
        """One lock-step decode for all active slots."""
        if self.cache is None or self.pos >= self.capacity:
            return
        lgts, self.cache = self._decode(
            self.params, self._pending, self.cache, jnp.int32(self.pos)
        )
        self.pos += 1
        self.rng, k = jax.random.split(self.rng)
        nxt = np.asarray(self.sampler(lgts, k))
        self._pending = jnp.asarray(nxt.reshape(self._tok_shape(1)))
        for slot, req in enumerate(self.slots):
            if req is None or req.done:
                continue
            req.out_tokens.append(nxt[slot].tolist())
            if len(req.out_tokens) >= req.max_new_tokens:
                req.done = True

    def plan_decode_collectives(
        self,
        *,
        num_nodes: int = 2,
        procs_per_node: int = 8,
        k_lanes: int = 2,
        faults=None,
    ):
        """Plan the per-decode-step collectives for this engine's shapes on
        the given collective mesh, in one :func:`repro.api.plan_batch` call:

        * ``broadcast`` of the pending sampled-token batch (one int32 per
          slot per codebook) from the sampling host to every proc;
        * ``scatter`` of the activation block (``num_slots * d_model``
          split over procs) for tensor-parallel resharding;
        * ``alltoall`` with the per-pair block of that same activation
          resharding (the transpose the paper's Section 5 lowers).

        Returns ``{op: Plan}``.  Deliberately jax-free — the planning layer
        prices schedules, it does not run them — so a monitor process can
        call this off the hot path.  Faulted meshes flow through the
        ISSUE 6 degradation ladder via ``faults``.

        With a pinned planner (``plan_mesh`` at construction) a query for
        the pinned mesh is a dict lookup — no re-pricing; the pinned set
        only moves on :meth:`inject_fault`.  Explicit ``faults`` or a
        different mesh still price ad hoc."""
        from repro import api

        if self.planner is not None and faults is None \
                and (num_nodes, procs_per_node, k_lanes) == self.planner.mesh:
            return self.planner.plans()

        p = num_nodes * procs_per_node
        bcast_elems = self.num_slots * max(1, self.cfg.num_codebooks)
        act = self.num_slots * self.cfg.d_model
        reqs = [
            api.PlanRequest("broadcast", bcast_elems, num_nodes=num_nodes,
                            procs_per_node=procs_per_node, k_lanes=k_lanes,
                            faults=faults),
            api.PlanRequest("scatter", max(1, act // p), num_nodes=num_nodes,
                            procs_per_node=procs_per_node, k_lanes=k_lanes,
                            faults=faults),
            api.PlanRequest("alltoall", max(1, act // (p * p)),
                            num_nodes=num_nodes,
                            procs_per_node=procs_per_node, k_lanes=k_lanes,
                            faults=faults),
        ]
        plans = api.plan_batch(reqs)
        if TRACER:
            TRACER.event("engine.plan_collectives",
                         mesh=(num_nodes, procs_per_node, k_lanes),
                         algs={pl.op: pl.algorithm for pl in plans})
        return {pl.op: pl for pl in plans}

    def inject_fault(self, event) -> str:
        """Report a mid-run fault (a ``repro.training.elastic.FaultEvent``)
        into the engine: the event is recorded and folded into the monitor's
        warn/evict policy.  Returns the resulting action; without a monitor
        the default policy is kind-based (node faults evict, lane faults
        warn — lanes are survivable via schedule repair).

        With a pinned planner the event also triggers exactly one
        bounded-latency replan of the pinned decode collectives
        (``DecodePlanner.observe_fault``)."""
        self.fault_events.append(event)
        if self.monitor is not None:
            action = self.monitor.observe_fault(event)
        else:
            action = "evict" if getattr(event, "kind", "node") == "node" else "warn"
        self.monitor_actions.append(action)
        if self.planner is not None:
            self.planner.observe_fault(event)
        obs_metrics.counter("engine.fault_events").inc()
        obs_metrics.counter(f"engine.fault_action.{action}").inc()
        if TRACER:
            TRACER.event("engine.fault", kind=getattr(event, "kind", None),
                         action=action)
        return action

    def drain(self) -> list[Request]:
        """Release finished requests from their slots."""
        out = []
        for i, req in enumerate(self.slots):
            if req is not None and req.done:
                out.append(req)
                self.slots[i] = None
        return out

    def run(self, requests: list[Request], *, max_steps: int = 256) -> list[Request]:
        """Convenience driver: admit everything (in waves), decode to done.

        With a monitor attached every decode step is timed through
        ``monitor.observe``; an "evict" verdict (a straggling host over the
        hard deadline ``patience`` times, or an injected node fault) stops
        the decode loop — the finished requests so far are returned and the
        caller remeshes (``elastic.plan_remesh_for_faults``) before
        resuming the rest."""
        pending = list(requests)
        finished: list[Request] = []
        steps = 0
        while (pending or any(s is not None for s in self.slots)) and steps < max_steps:
            if pending and any(s is None for s in self.slots) and self.cache is None:
                n = self.admit(pending)
                pending = pending[len(n):]
            sp = TRACER.start("decode_step", step=steps) if TRACER else None
            t0 = time.perf_counter()
            try:
                self.step()
            except BaseException:
                if sp:
                    TRACER.finish(sp, outcome="error")
                raise
            dt = time.perf_counter() - t0
            if sp:
                TRACER.finish(sp, pos=self.pos)
            obs_metrics.histogram(
                "engine.step_latency_s", edges=_STEP_EDGES
            ).observe(dt)
            if self.monitor is not None:
                action = self.monitor.observe(dt)
                self.monitor_actions.append(action)
                if action == "evict":
                    break
            finished.extend(self.drain())
            steps += 1
            if not any(s is not None and not s.done for s in self.slots) and not pending:
                break
        return finished
