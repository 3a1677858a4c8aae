"""Production mesh construction.

A pod is 16x16 = 256 chips (TPU v5e); the multi-pod mesh adds a leading
``pod`` axis (2 pods = 512 chips for the dry-run; the axes generalize to
any pod count — see ``repro.training.elastic.plan_remesh``).

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_host_mesh", "make_production_mesh", "make_test_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """``(pod, data, model) = (1, n, 1)`` over the ``n`` devices present: one
    host is one pod, data-parallel across its chips."""
    return _make_mesh((1, jax.device_count(), 1), ("pod", "data", "model"))


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for the 8-device CPU test environment."""
    return _make_mesh(shape, axes)
