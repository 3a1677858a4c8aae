"""Dry-run cell specs: shapes, shardings, eligibility matrix (no
compilation — the heavy sweep lives in launch/dryrun.py)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES
from repro.launch import specs as SP
from repro.launch.mesh import make_test_mesh
from repro.models import lm


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh((2, 2, 2), ("pod", "data", "model"))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_batch_structs_all_shapes(arch):
    cfg = get_config(arch)
    for shape in SHAPES.values():
        b = SP.batch_structs(cfg, shape.global_batch, shape.seq_len)
        for leaf in jax.tree.leaves(b):
            assert leaf.shape[0] == shape.global_batch
        if cfg.embed_inputs:
            assert b["tokens"].shape[1] == shape.seq_len
        else:
            assert b["embeds"].shape[-1] == cfg.d_model


def test_eligibility_matrix():
    eligible_500k = {a for a in ARCH_IDS
                     if SP.cell_eligible(get_config(a), SHAPES["long_500k"])[0]}
    assert eligible_500k == {"falcon_mamba_7b", "jamba_1_5_large_398b",
                             "h2o_danube_3_4b"}
    for a in ARCH_IDS:  # every other shape runs everywhere
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert SP.cell_eligible(get_config(a), SHAPES[s])[0]
    # 44 cells = 36 runnable + 8 documented skips
    runnable = sum(
        1 for a in ARCH_IDS for s in SHAPES.values()
        if SP.cell_eligible(get_config(a), s)[0]
    )
    assert runnable == 36


@pytest.mark.parametrize("arch", ["yi_6b", "deepseek_v2_236b",
                                  "jamba_1_5_large_398b", "falcon_mamba_7b"])
def test_cache_pspecs_valid(mesh, arch):
    cfg = get_config(arch)
    cache = lm.abstract_cache(cfg, 128, 1024)
    specs = SP.cache_pspecs(cfg, mesh, cache)
    flat_c = jax.tree.leaves(cache)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for leaf, spec in zip(flat_c, flat_s):
        assert len(spec) <= len(leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            n = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                n *= sizes[a]
            assert dim % n == 0, (arch, leaf.shape, spec)


def test_batch_pspec_replicates_tiny_batch(mesh):
    tok = SP.decode_token_struct(get_config("yi_6b"), 1)  # long_500k batch=1
    spec = SP.batch_pspecs(mesh, tok)
    assert spec == P()


def test_decode_token_struct_families():
    assert SP.decode_token_struct(get_config("musicgen_large"), 4).shape == (4, 1, 4)
    assert SP.decode_token_struct(get_config("yi_6b"), 4).shape == (4, 1)
    q = SP.decode_token_struct(get_config("qwen2_vl_7b"), 4)
    assert q.shape == (4, 1, 3584) and q.dtype == jnp.bfloat16
