"""The plain references that decide ``correct``, at tiny sizes on the CPU:
each agrees with the program where the program is sound."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.entries import alltoall
from chipbench.refs import dense_lm, schedule_replay

try:
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:  # backend already up, with the suite's 8 devices
    pass

TINY = {"num_layers": 2, "d_model": 64, "d_ff": 160, "vocab_size": 200,
        "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
        "sliding_window": 32, "rope_theta": 10000.0, "norm_eps": 1e-6,
        "dtype": "float32"}


def tiny_program_config():
    from chipbench.entries.train_step import program_config

    return program_config({"program": {"arch": "h2o_danube_3_4b",
                                       "smoke": True}, "model": TINY})


def test_reference_model_matches_the_program_in_float32():
    """Same weights, float32 throughout: the reference's loss and
    gradients are the program's, sliding window and padded vocabulary
    included."""
    from repro.models import lm

    cfg = tiny_program_config()
    assert cfg.padded_vocab == 256 > TINY["vocab_size"]
    shapes = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    w = dense_lm.init_weights(shapes, dense_lm.key_data(2**33 + 5),
                              jnp.float32)
    tokens, labels = dense_lm.batch(7, 0, 2, 96, TINY["vocab_size"])
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(
            lambda p: lm.loss_fn(cfg, p, batch), has_aux=True)(w)
    lr, gr = jax.value_and_grad(dense_lm.loss)(w, batch["tokens"],
                                               batch["labels"], TINY)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    np.testing.assert_allclose(dense_lm.leaf_norms(gr),
                               dense_lm.leaf_norms(gp), rtol=1e-4)


def test_token_stream_is_the_programs():
    from repro.training.data import make_batch

    cfg = tiny_program_config()
    seed = 2**31 + 1234
    for step in (0, 5):
        got = make_batch(cfg, 4, 16, seed=seed, step=step)
        tokens, labels = dense_lm.batch(seed, step, 4, 16, TINY["vocab_size"])
        np.testing.assert_array_equal(got["tokens"], tokens)
        np.testing.assert_array_equal(got["labels"], labels)


def test_init_weights_same_for_every_compile():
    shapes = {"a": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16),
              "norm": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
    kd = dense_lm.key_data(2**40 + 3)
    eager = dense_lm.init_weights(shapes, kd, jnp.bfloat16)
    jitted = jax.jit(lambda k: dense_lm.init_weights(
        shapes, k, jnp.bfloat16))(kd)
    np.testing.assert_array_equal(eager["a"], jitted["a"])
    assert bool(jnp.all(eager["norm"] == 1))
    other = dense_lm.init_weights(shapes, dense_lm.key_data(3), jnp.bfloat16)
    assert not bool(jnp.all(other["a"] == eager["a"]))


def test_gaps_worst_leaf_against_leaf_or_median():
    ref = {"losses": [2.0, 2.0], "grad_norms": np.array([1.0, 2.0, 1e-6]),
           "change_norms": np.array([1.0, 1.0, 5.0])}
    prog = {"losses": [2.0, 2.2], "grad_norms": np.array([1.0, 2.5, 0.0]),
            "change_norms": np.array([1.5, 1.0, 0.0])}
    g = dense_lm.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(0.1)
    # leaf 1: 0.5 against max(2.0, median 1.0)
    assert g["grad_gap"] == pytest.approx(0.25)
    # leaf 2's gradient is under a thousandth of the median: left out
    assert g["change_gap"] == pytest.approx(0.5)


def _schedules():
    from repro import api

    reqs = [api.PlanRequest("alltoall", c, num_nodes=2, procs_per_node=4,
                            k_lanes=2) for c in (1, 7, 4096)]
    reqs += [api.PlanRequest("alltoall", 960, num_nodes=4, procs_per_node=8,
                             k_lanes=8)]
    return [p.schedule() for p in api.plan_batch(reqs)]


def test_schedule_replay_passes_the_planners_schedules():
    from repro.core.validate import validate_schedule

    for cs in _schedules():
        assert validate_schedule(cs).ok
        assert schedule_replay.schedule_defects(cs) == 0


def test_schedule_replay_counts_broken_delivery():
    cs = _schedules()[-1]
    p = cs.p
    args = (cs.src, cs.dst, cs.round_ptr, cs.blk_ptr, cs.blk_ids)
    assert schedule_replay.defects(p, *args) == 0
    # a message sent to the wrong process
    dst = cs.dst.copy()
    dst[-1] = (dst[-1] + 1) % p
    assert schedule_replay.defects(p, cs.src, dst, *args[2:]) > 0
    # the last round dropped
    ptr = cs.round_ptr[:-1]
    assert schedule_replay.defects(p, cs.src, cs.dst, ptr, cs.blk_ptr,
                                   cs.blk_ids) > 0
    # a block sent by a process that does not hold it
    src = cs.src.copy()
    src[0] = (src[0] + 1) % p
    assert schedule_replay.defects(p, src, cs.dst, *args[2:]) > 0
    assert schedule_replay.defects(p, cs.src, cs.dst, cs.round_ptr,
                                   None, None) == p * p


def test_alltoall_reference_is_the_programs_semantics():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import collectives as C

    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, alltoall.AXES)
    spec = P(alltoall.AXES)
    x = jax.device_put(jnp.arange(16 * 3 * 5, dtype=jnp.float32).reshape(
        16, 3, 5), NamedSharding(mesh, spec))
    for f in (lambda v: jax.lax.all_to_all(v, alltoall.AXES, 0, 0,
                                           tiled=True),
              lambda v: C.fulllane_all_to_all(v, *alltoall.AXES)):
        got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                    out_specs=spec))(x)
        np.testing.assert_array_equal(got, alltoall.reference(x, 4))


def test_dataclass_config_keeps_the_program_parallel_settings():
    cfg = tiny_program_config()
    assert cfg.parallel.fsdp is False
    base = dataclasses.replace(cfg, num_layers=2)
    assert base.attn.sliding_window == TINY["sliding_window"]
