"""Data pipeline determinism, fault-tolerance policies, serving engine."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving.engine import Request, ServeEngine
from repro.training.data import Prefetcher, SyntheticLM, make_batch
from repro.training.elastic import StragglerMonitor, plan_remesh


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_batch_determinism():
    cfg = get_smoke_config("yi_6b")
    b1 = make_batch(cfg, 4, 16, seed=7, step=5)
    b2 = make_batch(cfg, 4, 16, seed=7, step=5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = make_batch(cfg, 4, 16, seed=7, step=6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_stream_resumable():
    cfg = get_smoke_config("yi_6b")
    full = [b for _, b in zip(range(5), SyntheticLM(cfg, 2, 8, seed=3))]
    resumed = [b for _, b in zip(range(2), SyntheticLM(cfg, 2, 8, seed=3, start_step=3))]
    np.testing.assert_array_equal(full[3][1]["tokens"], resumed[0][1]["tokens"])


def test_prefetcher_order_and_termination():
    it = iter([(i, i * i) for i in range(5)])
    out = list(Prefetcher(it, depth=2))
    assert out == [(i, i * i) for i in range(5)]


def test_vlm_batch_shape():
    cfg = get_smoke_config("qwen2_vl_7b")
    b = make_batch(cfg, 2, 8)
    assert b["embeds"].shape == (2, 8, cfg.d_model)
    assert b["labels"].shape == (2, 8)


# ---------------------------------------------------------------------------
# elastic / straggler
# ---------------------------------------------------------------------------


def test_straggler_actions():
    m = StragglerMonitor(patience=2)
    acts = [m.observe(t) for t in (1.0, 1.0, 1.1, 5.0, 5.0, 1.0)]
    assert acts[3] == "warn" and acts[4] == "evict"
    assert acts[5] == "ok"  # recovery resets strikes


def test_straggler_ema_resists_poisoning():
    m = StragglerMonitor()
    for _ in range(10):
        m.observe(1.0)
    m.observe(50.0)  # one massive outlier
    assert m.ema < 2.0  # clamped update


@settings(max_examples=50, deadline=None)
@given(pods=st.integers(1, 16), lost=st.integers(0, 16),
       batch=st.integers(1, 4096))
def test_remesh_plans(pods, lost, batch):
    plan = plan_remesh(num_pods=pods, pods_lost=min(lost, pods),
                       data_axis=16, model_axis=16, global_batch=batch,
                       last_committed_step=10)
    if lost >= pods:
        assert not plan.feasible
    else:
        assert plan.feasible
        assert plan.global_batch >= 1
        assert plan.restart_step == 10
        assert "model" in plan.mesh_axes  # TP axis never re-sharded


def test_remesh_single_pod_drops_pod_axis():
    plan = plan_remesh(num_pods=2, pods_lost=1, data_axis=16, model_axis=16,
                       global_batch=256, last_committed_step=5)
    assert plan.mesh_axes == ("data", "model")
    assert plan.global_batch == 128


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_engine_end_to_end():
    cfg = get_smoke_config("yi_6b")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, num_slots=2, capacity=64)
    reqs = [Request(rid=i,
                    prompt=np.random.RandomState(i).randint(
                        0, cfg.vocab_size, (8,)).astype(np.int32),
                    max_new_tokens=5)
            for i in range(2)]
    fin = eng.run(reqs, max_steps=32)
    assert len(fin) == 2
    assert all(len(r.out_tokens) == 5 for r in fin)


def test_engine_plans_decode_collectives():
    from repro.api import Plan, PlanRequest, plan
    from repro.core.faults import FaultSpec

    cfg = get_smoke_config("yi_6b")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, num_slots=4, capacity=64)
    plans = eng.plan_decode_collectives(num_nodes=2, procs_per_node=8,
                                        k_lanes=2)
    assert set(plans) == {"broadcast", "scatter", "alltoall"}
    for op, pl in plans.items():
        assert isinstance(pl, Plan) and pl.op == op
        assert pl.schedule().p == 16
        # the engine's batched call equals the per-query planner
        assert pl == plan(pl.request)
    # faulted meshes flow through the degradation ladder and still answer
    deg = eng.plan_decode_collectives(
        num_nodes=2, procs_per_node=8, k_lanes=2,
        faults=FaultSpec(dead_lanes=((1, 1),)))
    assert all(p.algorithm for p in deg.values())


def test_engine_greedy_deterministic():
    cfg = get_smoke_config("yi_6b")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    outs = []
    for _ in range(2):
        eng = ServeEngine(cfg, params, num_slots=1, capacity=64)
        fin = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=6)])
        outs.append(fin[0].out_tokens)
    assert outs[0] == outs[1]
