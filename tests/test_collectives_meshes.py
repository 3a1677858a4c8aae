"""Hierarchical collectives across mesh factorizations of 8 devices —
the full-lane decomposition must be exact for any (outer, inner) split."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import collectives as C
from repro.launch.mesh import make_test_mesh

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")

SHAPES = [(2, 4), (4, 2), (8, 1), (1, 8)]


def _mesh(shape):
    return make_test_mesh(shape, ("pod", "lane"))


@pytest.mark.parametrize("shape", SHAPES)
def test_hierarchical_psum_all_factorizations(shape):
    mesh = _mesh(shape)
    x = np.random.RandomState(0).randn(8, 13).astype(np.float32)
    sm = lambda f: jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(("pod", "lane")), out_specs=P(("pod", "lane"))))
    got = sm(lambda v: C.hierarchical_psum(v, "pod", "lane"))(x)
    want = sm(lambda v: C.flat_psum(v, "pod", "lane"))(x)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("shape", SHAPES)
def test_hierarchical_reduce_scatter_all_factorizations(shape, dim):
    """Each chip keeps its ``lane`` tile along ``dim`` of the whole sum."""
    mesh = _mesh(shape)
    lanes = shape[1]
    x = np.random.RandomState(3).randn(8, 8, 16).astype(np.float32)
    got = jax.jit(shard_map(
        lambda v: C.hierarchical_reduce_scatter(v[0], "pod", "lane",
                                                dim)[None],
        mesh=mesh, in_specs=P(("pod", "lane")),
        out_specs=P(("pod", "lane"))))(x)
    tiles = np.split(x.sum(0), lanes, axis=dim)
    for chip in range(8):
        np.testing.assert_allclose(got[chip], tiles[chip % lanes], rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 4), (4, 2)])
def test_fulllane_a2a_all_factorizations(shape):
    mesh = _mesh(shape)
    x = np.random.RandomState(1).randn(8, 8, 5).astype(np.float32)
    sm = lambda f: jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(("pod", "lane")), out_specs=P(("pod", "lane"))))
    got = sm(lambda v: C.fulllane_all_to_all(v[0], "pod", "lane")[None])(x)
    want = sm(lambda v: C.flat_all_to_all(v[0], "pod", "lane")[None])(x)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _a2a(fn, shape):
    """``fn`` over the ``(pod, lane)`` mesh of ``shape``, on the global
    ``[P * P, ...]`` array whose rows ``[d * P, (d + 1) * P)`` are device
    ``d``'s blocks."""
    return jax.jit(shard_map(lambda v: fn(v, "pod", "lane"), mesh=_mesh(shape),
                             in_specs=P(("pod", "lane")),
                             out_specs=P(("pod", "lane"))))


A2A_SHAPES = [(1, 4), (4, 1), (2, 2), (2, 4), (4, 2)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", A2A_SHAPES)
def test_fulllane_a2a_bit_exact(shape, dtype):
    """Every block lands bit for bit where the flat all-to-all puts it,
    also where one phase is empty and for a block of [3, 5], which no
    (8, 128) tile divides."""
    p = shape[0] * shape[1]
    x = jnp.asarray(np.random.RandomState(4).randn(p * p, 3, 5), dtype)
    got = _a2a(C.fulllane_all_to_all, shape)(x)
    want = _a2a(C.flat_all_to_all, shape)(x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(2, 2), (2, 4)])
def test_fulllane_a2a_vjp(shape):
    """The cotangent equals ``lax.all_to_all``'s: the exchange is its own
    transpose."""
    p = shape[0] * shape[1]
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(p * p, 3, 5), jnp.float32)
    ct = jnp.asarray(rs.randn(p * p, 3, 5), jnp.float32)
    flat = lambda v, o, i: jax.lax.all_to_all(v, (o, i), 0, 0, tiled=True)
    got = jax.vjp(_a2a(C.fulllane_all_to_all, shape), x)[1](ct)[0]
    want = jax.vjp(_a2a(flat, shape), x)[1](ct)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hierarchical_psum_dtypes(dtype):
    mesh = _mesh((2, 4))
    x = jnp.asarray(np.random.RandomState(2).randn(8, 16), dtype)
    sm = lambda f: jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(("pod", "lane")), out_specs=P(("pod", "lane"))))
    got = sm(lambda v: C.hierarchical_psum(v, "pod", "lane"))(x)
    want = sm(lambda v: C.flat_psum(v, "pod", "lane"))(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_kported_broadcast_nonzero_root():
    mesh = _mesh((2, 4))
    x = np.full((8, 4), -1.0, np.float32)
    x[5] = np.arange(4) + 1.0  # root device 5
    sm = jax.jit(shard_map(
        lambda v: C.kported_broadcast_ppermute(v[0], ("pod", "lane"), k=2, root=5)[None],
        mesh=mesh, in_specs=P(("pod", "lane")), out_specs=P(("pod", "lane"))))
    out = sm(x)
    for d in range(8):
        np.testing.assert_allclose(np.asarray(out[d]), np.arange(4) + 1.0)
