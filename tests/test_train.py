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
from repro.models import moe as moe_mod
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


@pytest.mark.parametrize("shape,ep", [
    ((2, 2, 1), ("pod", "data")),
    ((2, 4, 1), ("data",)),  # 4 experts fit over data alone; pod sums
    ((1, 8, 1), ()),  # 4 experts do not divide over 8 chips: replicated
    ((1, 1, 1), ()),
])
def test_moe_step_matches_replicated(shape, ep):
    """A MoE model (dbrx smoke, 4 experts) through the fulllane shard_map
    step: expert-parallel over the DP axes the experts divide over, over
    some of them, or replicated where none fit; three steps give the
    replicated step's params, moments and grad norm."""
    from repro.training.train_step import ep_axes

    base = get_smoke_config("dbrx_132b")
    cfg = dataclasses.replace(
        base, dtype="float32",
        parallel=dataclasses.replace(base.parallel, fsdp=False))
    n = int(np.prod(shape))
    mesh = jax.make_mesh(shape, ("pod", "data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * 3)
    assert ep_axes(cfg, mesh) == ep
    mk, (pspec, _) = make_train_step_shardmap(cfg, mesh, OPT,
                                              backend="fulllane")
    if ep:  # [layers, experts, ...]
        assert pspec["blocks"]["slot0"]["ffn"]["w_gate"][1] == (
            ep if len(ep) > 1 else ep[0])
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    state = init_opt_state(params, OPT)
    fn, ref = mk(_batch(cfg)), _replicated_step(cfg, mesh, OPT)
    got = (jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, state))
    want = (params, state)
    for t in range(3):
        *got, gm = fn(*got, _batch(cfg, seed=t))
        *want, wm = ref(*want, _batch(cfg, seed=t))
        np.testing.assert_allclose(gm["grad_norm"], wm["grad_norm"],
                                   rtol=1e-5)
        assert gm["moe_routed"] == cfg.num_layers * 8 * 32 * cfg.moe.top_k
    # the exchange sums an expert's gradient in another order than the
    # psum: AdamW's normalisation lifts that to 2e-6 on a few of 49152
    # elements of the expert leaves (leaving out the sum over ``pod`` on
    # (2, 4, 1) reads 3e-3)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


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


def test_pjit_refuses_ep_axes(mesh):
    """EP axes name a shard_map's axes: the pjit step has none."""
    base = get_smoke_config("dbrx_132b")
    cfg = dataclasses.replace(base, parallel=dataclasses.replace(
        base.parallel, ep_axes=("data",)))
    with pytest.raises(ValueError, match="ep_axes"):
        make_train_step_pjit(cfg, mesh, OPT)


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


def test_v2_lite_ep_step_matches_reference():
    """DeepSeek-V2-Lite (smoke widths, float32) through the expert-parallel
    shard_map step on (pod 2, data 2): three steps read as the benchmark
    reads them (losses, first gradient per leaf, change per leaf) match the
    plain reference (chipbench/refs/moe_lm.py); the build records the EP
    leaves, and the metrics carry the layers' counters."""
    from jax.sharding import NamedSharding

    from chipbench.entries.train_moe_step import published_widths
    from chipbench.refs import dense_lm, moe_lm

    base = get_smoke_config("deepseek_v2_lite")
    cfg = dataclasses.replace(base, dtype="float32")
    mesh = jax.make_mesh((2, 2, 1), ("pod", "data", "model"),
                         devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,) * 3)
    opt = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, eps=1e-8,
               weight_decay=0.1, grad_clip=1.0, warmup_steps=2)
    was = bool(TRACER)
    TRACER.enable()
    mark = TRACER.mark()
    try:
        mk, (pspec, ospec) = make_train_step_shardmap(
            cfg, mesh, OptConfig(**opt), backend="fulllane")
        seed, rows, seq = 2**31 + 5, 8, 32
        batches = [dense_lm.batch(seed, t, rows, seq, cfg.vocab_size)
                   for t in range(3)]
        fn = mk({"tokens": batches[0][0], "labels": batches[0][1]})
    finally:
        if not was:
            TRACER.disable()
    events = {r["name"]: r["args"] for r in TRACER.records_since(mark)}
    held = cfg.moe.num_experts
    assert events["train_step.ep"] == {
        "expert_leaves": 3, "experts_per_chip": held // 4,
        "capacity": moe_mod.capacity(2 * seq, cfg.moe),
        "dispatch_bytes": held * moe_mod.capacity(2 * seq, cfg.moe)
        * cfg.d_model * 4}
    leaves = len(jax.tree.leaves(lm.model_meta(cfg),
                                 is_leaf=lambda x: isinstance(x, ParamMeta)))
    assert events["train_step.zero1"]["leaves"] == leaves
    assert events["train_step.zero1"]["sharded_leaves"] == leaves - 3
    assert pspec["blocks"]["slot0"]["ffn"]["w_gate"][1] == ("pod", "data")

    shapes = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    ns = lambda t: jax.tree.map(  # noqa: E731
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
    kd = dense_lm.key_data(seed)
    params = jax.jit(lambda: moe_lm.init_weights(shapes, kd, jnp.float32),
                     out_shardings=ns(pspec))()
    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), shapes)
    state = jax.jit(lambda: {"m": zero, "v": zero,
                             "step": jnp.zeros((), jnp.int32)},
                    out_shardings=ns(ospec))()
    losses = []
    for t, (tokens, labels) in enumerate(batches):
        params, state, met = fn(params, state,
                                {"tokens": tokens, "labels": labels})
        losses.append(float(met["loss"]))
        # summed over the 2 MoE layers and the 4 chips
        assert 0 < met["moe_dropped"] < met["moe_routed"] == 2 * rows * seq * 6
        if t == 0:
            clip = min(1.0, 1.0 / float(met["grad_norm"]))
            grads = np.asarray(dense_lm.leaf_norms(state["m"])) / (0.1 * clip)
    change = np.asarray(dense_lm.leaf_norms(jax.tree.map(
        jnp.subtract, params, moe_lm.init_weights(shapes, kd, jnp.float32))))
    got = {"losses": losses, "grad_norms": grads, "change_norms": change}
    want = moe_lm.Reference(published_widths(cfg), opt, shapes,
                            jax.devices()[:4]).run(seed, batches)
    gaps = dense_lm.gaps(got, want)
    # f32 on both sides: the gaps read 1e-7 to 5e-6; an exchange that moves
    # nothing reads 4e-3, 0.11 and 0.01
    assert gaps["loss_gap"] < 1e-5 and gaps["grad_gap"] < 1e-4 and \
        gaps["change_gap"] < 1e-4, gaps


@pytest.mark.parametrize("backend", ["fulllane", "xla"])
def test_train_main_v2_lite(compile_cache_restored, backend):
    """The entry point trains DeepSeek-V2-Lite (smoke) on (2, 2, 1); on
    fulllane every MoE layer's tokens cross the mesh in block sends under
    the layer's dispatch and combine scopes."""
    from repro.launch import hloanalysis, train

    out = train.main(["--arch", "deepseek_v2_lite", "--smoke", "--mesh",
                      "2,2,1", "--steps", "2", "--seq", "32",
                      "--backend", backend])
    assert out["steps"] == 2
    assert np.all(np.isfinite(out["losses"] + out["grad_norms"]))
    if backend == "fulllane":
        scopes = [n for k, n in hloanalysis.collective_scopes(
            out["compiled"].as_text())
            if k in ("all-to-all", "collective-permute")]
        assert scopes and all("moe/dispatch/fulllane_all_to_all/" in n
                              or "moe/combine/fulllane_all_to_all/" in n
                              for n in scopes), scopes
