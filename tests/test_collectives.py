"""shard_map collective implementations vs flat XLA references (8 devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import collectives as C
from repro.launch.mesh import make_test_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 devices"
)


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh((2, 4), ("pod", "lane"))


def _sm(mesh, f):
    return jax.jit(
        shard_map(f, mesh=mesh, in_specs=P(("pod", "lane")),
                  out_specs=P(("pod", "lane")))
    )


def test_hierarchical_psum(mesh):
    x = np.random.RandomState(0).randn(8, 33, 5).astype(np.float32)
    got = _sm(mesh, lambda v: C.hierarchical_psum(v, "pod", "lane"))(x)
    want = _sm(mesh, lambda v: C.flat_psum(v, "pod", "lane"))(x)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_hierarchical_psum_grad(mesh):
    """The hierarchical collective must be differentiable (it sits on the
    gradient path of the fulllane train step)."""
    x = np.random.RandomState(1).randn(8, 16).astype(np.float32)

    def loss(v):
        return (C.hierarchical_psum(v * v, "pod", "lane") ** 2).sum()

    g = jax.jit(
        shard_map(jax.grad(loss), mesh=mesh, in_specs=P(("pod", "lane")),
                  out_specs=P(("pod", "lane")))
    )(x)
    assert np.all(np.isfinite(np.asarray(g)))


def test_fulllane_all_to_all(mesh):
    x = np.random.RandomState(2).randn(8, 8, 3).astype(np.float32)
    f = lambda v: C.fulllane_all_to_all(v[0], "pod", "lane")[None]
    g = lambda v: C.flat_all_to_all(v[0], "pod", "lane")[None]
    np.testing.assert_allclose(_sm(mesh, f)(x), _sm(mesh, g)(x), rtol=1e-6)


def test_fulllane_broadcast(mesh):
    payload = np.arange(24, dtype=np.float32)
    x = np.full((8, 6), -99.0, np.float32)
    for lane in range(4):
        x[lane] = payload[lane * 6:(lane + 1) * 6]
    out = _sm(mesh, lambda v: C.fulllane_broadcast(v[0], "pod", "lane")[None])(x)
    for d in range(8):
        np.testing.assert_allclose(np.asarray(out[d]), payload)


@pytest.mark.parametrize("k", [1, 2, 3, 5])
def test_kported_broadcast_ppermute(mesh, k):
    x = np.full((8, 5), -1.0, np.float32)
    x[0] = np.arange(5) + 1
    out = _sm(
        mesh,
        lambda v: C.kported_broadcast_ppermute(v[0], ("pod", "lane"), k=k)[None],
    )(x)
    for d in range(8):
        np.testing.assert_allclose(np.asarray(out[d]), np.arange(5) + 1)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_kported_scatter_ppermute(mesh, k):
    blocks = np.random.RandomState(3).randn(8, 2).astype(np.float32)
    x = np.zeros((8, 8, 2), np.float32)
    x[0] = blocks
    out = _sm(
        mesh,
        lambda v: C.kported_scatter_ppermute(v[0], ("pod", "lane"), k=k)[None],
    )(x)
    for d in range(8):
        np.testing.assert_allclose(np.asarray(out[d]), blocks[d])


def test_hierarchical_psum_nondivisible_pad(mesh):
    """Payloads not divisible by the inner axis size go through the pad path."""
    x = np.random.RandomState(4).randn(8, 7).astype(np.float32)  # 7 % 4 != 0
    got = _sm(mesh, lambda v: C.hierarchical_psum(v, "pod", "lane"))(x)
    want = _sm(mesh, lambda v: C.flat_psum(v, "pod", "lane"))(x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
