"""Training integration: pjit vs shard_map paths, backend equivalence,
loss descent, microbatch-accumulation consistency (8-device mesh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_test_mesh
from repro.models import lm
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_step import (
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
