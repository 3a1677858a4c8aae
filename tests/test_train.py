"""Training integration: pjit vs shard_map paths, backend equivalence,
loss descent, microbatch-accumulation consistency (8-device mesh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.launch.mesh import make_test_mesh
from repro.models import lm
from repro.models.params import ParamMeta
from repro.obs.trace import TRACER
from repro.training.optimizer import OptConfig, adamw_update, init_opt_state
from repro.training.train_step import (
    _grad_and_metrics,
    dp_axes,
    make_train_step_pjit,
    make_train_step_shardmap,
)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")

OPT = OptConfig(learning_rate=1e-3, warmup_steps=2)


def _batch(cfg, B=8, S=32, seed=0):
    r = np.random.RandomState(seed)
    if cfg.embed_inputs:
        shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks > 1 else (B, S)
        return {"tokens": r.randint(0, cfg.vocab_size, shape).astype(np.int32),
                "labels": r.randint(0, cfg.vocab_size, shape).astype(np.int32)}
    return {"embeds": r.randn(B, S, cfg.d_model).astype(np.float32),
            "labels": r.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)}


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh((2, 2, 2), ("pod", "data", "model"))


def test_backends_agree(mesh):
    """xla (flat psum) and fulllane (hierarchical) grad sync must produce
    identical training trajectories."""
    cfg = get_smoke_config("yi_6b")
    cfg = dataclasses.replace(cfg, parallel=dataclasses.replace(cfg.parallel, fsdp=False))
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, OPT)
    batch = _batch(cfg)
    results = {}
    for backend in ("xla", "fulllane"):
        mk, _ = make_train_step_shardmap(cfg, mesh, OPT, backend=backend)
        fn = mk(batch)
        p, o, m = fn(jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, opt), batch)
        results[backend] = (p, m)
    np.testing.assert_allclose(results["xla"][1]["loss"],
                               results["fulllane"][1]["loss"], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(results["xla"][0]),
                    jax.tree.leaves(results["fulllane"][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def _replicated_step(cfg, mesh, opt):
    """The shard_map step without ZeRO-1 inside it: moments replicated
    through the step, the whole gradient summed over the DP axes, every
    chip updating every element."""
    dp = dp_axes(mesh)
    ndp = int(np.prod([mesh.shape[a] for a in dp]))

    def step(params, state, batch):
        grads, _ = _grad_and_metrics(cfg, params, batch)
        grads = jax.tree.map(lambda g: jax.lax.psum(g, dp) / ndp, grads)
        return adamw_update(grads, state, params, opt)

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=(P(), P(), P(dp)),
                                 out_specs=(P(), P(), P()),
                                 axis_names=set(dp), check_vma=False))


def _zero1_event(build):
    """Run ``build`` with the tracer on; the attributes of the
    ``train_step.zero1`` event it records."""
    was = bool(TRACER)
    TRACER.enable()
    mark = TRACER.mark()
    try:
        out = build()
    finally:
        if not was:
            TRACER.disable()
    events = [r for r in TRACER.records_since(mark)
              if r["name"] == "train_step.zero1"]
    assert len(events) == 1
    return out, events[0]["args"]


@pytest.mark.parametrize("arch,shape,backend,whole_leaves", [
    ("h2o_danube_3_4b", (2, 2, 1), "fulllane", 0),
    ("h2o_danube_3_4b", (2, 2, 1), "xla", 0),
    ("h2o_danube_3_4b", (1, 1, 1), "fulllane", 0),
    # mamba's A_log, skip, conv, dt and x projections have no d_model dim
    ("falcon_mamba_7b", (2, 2, 1), "fulllane", 7),
])
def test_zero1_step_matches_replicated(arch, shape, backend, whole_leaves):
    """The ZeRO-1 step (moments sharded over ``data``, each gradient leaf
    reduced to its moment tile, the tile updated, parameters gathered)
    gives the replicated update's params, moments and grad norm over three
    steps; leaves with no dim that ``data`` shards take the whole path."""
    base = get_smoke_config(arch)
    cfg = dataclasses.replace(
        base, dtype="float32",
        parallel=dataclasses.replace(base.parallel, fsdp=False))
    n = int(np.prod(shape))
    mesh = jax.make_mesh(shape, ("pod", "data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * 3)
    (mk, (_, ospec)), counts = _zero1_event(
        lambda: make_train_step_shardmap(cfg, mesh, OPT, backend=backend))
    leaves = len(jax.tree.leaves(lm.model_meta(cfg),
                                 is_leaf=lambda x: isinstance(x, ParamMeta)))
    assert counts["leaves"] == leaves
    assert counts["sharded_leaves"] == leaves - whole_leaves
    assert (counts["sharded_bytes_share"] == 1.0) == (whole_leaves == 0)

    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    state = init_opt_state(params, OPT)
    batch = _batch(cfg)
    fn, ref = mk(batch), _replicated_step(cfg, mesh, OPT)
    got = (jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, state))
    want = (params, state)
    for t in range(3):
        *got, gm = fn(*got, _batch(cfg, seed=t))
        *want, wm = ref(*want, _batch(cfg, seed=t))
        np.testing.assert_allclose(gm["grad_norm"], wm["grad_norm"],
                                   rtol=1e-5)
    assert got[1]["m"]["head"]["lm_head"].sharding.spec == \
        ospec["m"]["head"]["lm_head"]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


@pytest.fixture
def compile_cache_restored():
    """train.main turns the persistent compile cache on for its process;
    hand the next test in this worker the configuration it had before."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_train_main_backends_agree(compile_cache_restored):
    """The entry point end to end (mesh, depth cut, placement, AOT compile,
    loop): fulllane and xla give the same losses and gradient norms."""
    from repro.launch import train

    argv = ["--arch", "h2o_danube_3_4b", "--smoke", "--mesh", "2,2,2",
            "--num-layers", "1", "--steps", "2", "--seq", "32"]
    runs = {b: train.main(argv + ["--backend", b]) for b in ("fulllane", "xla")}
    for out in runs.values():
        assert out["steps"] == 2
        assert np.all(np.isfinite(out["losses"] + out["grad_norms"]))
    # bf16 compute, FSDP (xla) against replicated (fulllane) placement: the
    # readings differ by < 1e-4 (loss) and < 5e-4 (grad norm); gradient sync
    # skipping the pod axis moves the grad norm by 32%.
    np.testing.assert_allclose(runs["fulllane"]["losses"],
                               runs["xla"]["losses"], rtol=1e-3)
    np.testing.assert_allclose(runs["fulllane"]["grad_norms"],
                               runs["xla"]["grad_norms"], rtol=1e-2)


def test_loss_decreases(mesh):
    cfg = get_smoke_config("yi_6b")
    params = lm.init_model(cfg, jax.random.PRNGKey(1))
    opt = init_opt_state(params, OPT)
    batch = _batch(cfg, seed=3)  # overfit one batch
    mk, _ = make_train_step_pjit(cfg, mesh, OPT)
    fn = mk(batch)
    losses = []
    for _ in range(12):
        params, opt, m = fn(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


@pytest.mark.parametrize("arch", ["gemma_7b", "musicgen_large"])
def test_microbatch_equivalence(mesh, arch):
    """micro=1 and micro=2 produce (nearly) the same first step.

    musicgen (multi-codebook) runs the accumulated forward under the
    activation-sharding constraint, the combination GSPMD once
    miscompiled (wrong loss, grad_norm off by ~sqrt(n))."""
    base = get_smoke_config(arch)
    batch = _batch(base)
    outs = {}
    for n in (1, 2):
        cfg = dataclasses.replace(base, parallel=dataclasses.replace(base.parallel, microbatches=n))
        params = lm.init_model(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params, OPT)
        mk, _ = make_train_step_pjit(cfg, mesh, OPT)
        p, o, m = mk(batch)(params, opt, batch)
        outs[n] = (float(m["loss"]), float(m["grad_norm"]))
    assert abs(outs[1][0] - outs[2][0]) < 1e-2
    assert abs(outs[1][1] - outs[2][1]) / max(outs[1][1], 1e-6) < 0.05


def test_fsdp_requires_pjit(mesh):
    cfg = get_smoke_config("yi_6b")  # fsdp defaults True
    assert cfg.parallel.fsdp
    with pytest.raises(ValueError):
        make_train_step_shardmap(cfg, mesh, OPT)


@pytest.mark.parametrize("arch", ["jamba_1_5_large_398b", "deepseek_v2_236b",
                                  "falcon_mamba_7b"])
def test_pjit_step_other_families(mesh, arch):
    cfg = get_smoke_config(arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, OPT)
    batch = _batch(cfg)
    mk, _ = make_train_step_pjit(cfg, mesh, OPT)
    p, o, m = mk(batch)(params, opt, batch)
    assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    assert int(o["step"]) == 1
