"""The expert-parallel MoE layer (``models/moe.py``) against the plain
reference layer of ``chipbench/refs/moe_lm.py`` at the DeepSeek-V2-Lite
smoke size, in float32 on the CPU: on the (pod 2, data 2) mesh, with the
experts sharded over both axes and the tokens exchanged by
``fulllane_all_to_all`` (or ``lax.all_to_all``), the layer equals the uncut
layer of the four source chips, drop counts included; and the four
one-chip shares of the experts add up to the uncut layer, the shared
experts counted once."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from chipbench.entries.train_moe_step import published_widths
from chipbench.refs import moe_lm
from repro.configs import get_smoke_config
from repro.models import moe as moe_mod

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")

DP = ("pod", "data")
EXPERT = ("w_gate", "w_up", "w_down")
HI = jax.lax.Precision.HIGHEST


def _cfg(norm_topk_prob=False, held=None):
    base = get_smoke_config("deepseek_v2_lite")
    return dataclasses.replace(base, dtype="float32", moe=dataclasses.replace(
        base.moe, norm_topk_prob=norm_topk_prob, num_experts_held=held))


def _layer(cfg, seed=0):
    """Seeded f32 weights of one MoE layer and its input, 8 rows of 32."""
    meta = moe_mod.moe_meta(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(meta) + 1)
    p = {k: jax.random.normal(kk, m.shape, jnp.float32) / np.sqrt(m.shape[-2])
         for (k, m), kk in zip(sorted(meta.items()), keys)}
    x = jax.random.normal(keys[-1], (8, 32, cfg.d_model), jnp.float32)
    return p, x


def _uncut(cfg, p, x, n_src):
    """The plain reference layer over ``n_src`` source chips."""
    def mm(a, b):
        return jnp.matmul(a, b, precision=HI)

    out, aux, dropped, routed = moe_lm.moe_ffn(
        p, x.reshape((n_src, -1) + x.shape[1:]), published_widths(cfg),
        mm)
    return out.reshape(x.shape), aux, dropped, routed


def _mesh():
    return jax.make_mesh((2, 2, 1), ("pod", "data", "model"),
                         devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,) * 3)


@pytest.mark.parametrize("backend", ["fulllane", "xla"])
@pytest.mark.parametrize("norm", [False, True], ids=["unnormalised",
                                                     "normalised"])
def test_ep_layer_equals_uncut_layer(backend, norm):
    cfg = _cfg(norm)
    ecfg = dataclasses.replace(cfg, parallel=dataclasses.replace(
        cfg.parallel, ep_axes=DP, collective_backend=backend))
    p, x = _layer(cfg)

    def f(p, x):
        out, st = moe_mod.moe(ecfg, p, x)
        st = {k: jax.lax.psum(v, DP) for k, v in st.items()}
        return out, {**st, "aux": st["aux"] / 4}

    specs = {k: P(DP) if k in EXPERT else P() for k in p}
    got, st = jax.jit(jax.shard_map(
        f, mesh=_mesh(), in_specs=(specs, P(DP)),
        out_specs=(P(DP), P()), axis_names=set(DP), check_vma=False))(p, x)
    want, aux, dropped, routed = _uncut(cfg, p, x, 4)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(st["aux"], aux, rtol=1e-5)
    assert float(st["moe_dropped"]) == float(dropped) > 0
    assert float(st["moe_routed"]) == float(routed) == 8 * 32 * 6


def test_one_chip_shares_add_up_to_uncut_layer():
    """Share i holds experts 4i..4i+3 (the router's columns rolled so that
    they come first): its partial output, less the shared experts all but
    once, and its counts add up over the four shares to the uncut layer."""
    cfg = _cfg()
    share = _cfg(held=4)
    p, x = _layer(cfg, seed=1)
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) \
        @ p["shared_down"]
    total, dropped, routed = -3 * shared, 0.0, 0.0
    for i in range(4):
        pi = {**{k: p[k][4 * i:4 * i + 4] for k in EXPERT},
              **{k: v for k, v in p.items() if k not in EXPERT},
              "router": jnp.roll(p["router"], -4 * i, axis=1)}
        out, st = moe_mod.moe(share, pi, x)
        total = total + out
        dropped += float(st["moe_dropped"])
        routed += float(st["moe_routed"])
    want, _, w_dropped, w_routed = _uncut(cfg, p, x, 1)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert (dropped, routed) == (float(w_dropped), float(w_routed))
    assert routed == 8 * 32 * 6
