"""Pallas kernel sweeps (interpret=True on CPU) against the ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.RandomState(0)


@pytest.mark.parametrize(
    "BH,S,hd,g,win,dt",
    [
        (4, 128, 32, 1, None, jnp.float32),
        (6, 256, 64, 3, None, jnp.bfloat16),
        (2, 128, 32, 1, 48, jnp.float32),
        (4, 64, 16, 2, None, jnp.float32),
        (2, 96, 16, 2, 32, jnp.bfloat16),
    ],
)
def test_flash_attention(BH, S, hd, g, win, dt):
    q = jnp.asarray(RNG.randn(BH, S, hd), dt)
    k = jnp.asarray(RNG.randn(BH // g, S, hd), dt)
    v = jnp.asarray(RNG.randn(BH // g, S, hd), dt)
    out = ops.flash_attention(q, k, v, group_size=g, window=win,
                              block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, group_size=g, window=win)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    err = float(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)).max())
    assert err < tol


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.randn(2, 64, 16), jnp.float32)
    k = jnp.asarray(RNG.randn(2, 64, 16), jnp.float32)
    v = jnp.asarray(RNG.randn(2, 64, 16), jnp.float32)
    out = ops.flash_attention(q, k, v, group_size=1, causal=False,
                              block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, group_size=1, causal=False)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,di,N,chunk,bd", [
    (2, 64, 32, 8, 16, 16),
    (1, 128, 64, 16, 32, 32),
    (3, 96, 16, 4, 16, 16),
    (1, 32, 512, 16, 8, 512),  # the default tiles
])
def test_mamba_scan(B, S, di, N, chunk, bd):
    a = jnp.asarray(RNG.rand(B, S, di, N) * 0.9, jnp.float32)
    b = jnp.asarray(RNG.randn(B, S, di, N) * 0.1, jnp.float32)
    c = jnp.asarray(RNG.randn(B, S, N), jnp.float32)
    y, h = ops.mamba_scan(a, b, c, chunk=chunk, block_d=bd)
    yr, hr = ref.mamba_scan_ref(a, b, c)
    np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h, hr, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("T,d,dt", [
    (64, 128, jnp.float32), (100, 96, jnp.bfloat16), (256, 512, jnp.float32),
    (360, 3840, jnp.float32), (360, 3840, jnp.bfloat16),
])
def test_rmsnorm(T, d, dt):
    x = jnp.asarray(RNG.randn(T, d), dt)
    w = jnp.asarray(RNG.rand(d), dt)
    out = ops.rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    err = float(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)).max())
    assert err < (2e-2 if dt == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("No,Ni,blk,d", [
    (3, 4, 8, 16), (2, 2, 4, 4), (8, 1, 2, 32),
    (1, 2, 2048, 512),  # 4 MiB blocks: tiled in two 1024-row steps
])
def test_a2a_pack(No, Ni, blk, d):
    x = jnp.asarray(RNG.randn(No, Ni, blk, d), jnp.float32)
    np.testing.assert_allclose(ops.a2a_pack(x), ref.a2a_pack_ref(x))
