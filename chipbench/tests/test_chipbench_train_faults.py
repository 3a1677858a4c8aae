"""A run of the 2x2 training cell, tiny, with the timed path broken
underneath: the check must read ``correct`` false for every fault the cell
can have, and true for the sound program."""

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests import _tiny

CELL = "train.h2o-danube-3-4b.2x2"


def _unchanged(monkeypatch):
    from repro.training import train_step

    def frozen(grads, state, params, cfg):
        info = {"grad_norm": jnp.float32(1.0), "lr": jnp.float32(0.0)}
        return params, {**state, "step": state["step"] + 1}, info

    monkeypatch.setattr(train_step, "adamw_update", frozen)


def _half_batch(monkeypatch):
    from repro.training import train_step

    orig = train_step._grad_and_metrics

    def half(cfg, params, batch, act_shard=None):
        batch = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return orig(cfg, params, batch, act_shard)

    monkeypatch.setattr(train_step, "_grad_and_metrics", half)


def _no_exchange(monkeypatch):
    from repro.core import collectives

    monkeypatch.setattr(collectives, "hierarchical_psum",
                        lambda x, outer, inner: x)


def _altered(monkeypatch):
    from repro.training import train_step

    orig = train_step.adamw_update

    def altered(grads, state, params, cfg):
        new, st, info = orig(grads, state, params, cfg)
        head = new["head"]["lm_head"]
        return {**new, "head": {"lm_head": head.at[0, 0].add(1.0)}}, st, info

    monkeypatch.setattr(train_step, "adamw_update", altered)


def test_sound_step_is_correct():
    out = _tiny.run(CELL)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange,
                                   _altered], ids=lambda f: f.__name__[1:])
def test_broken_step_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _tiny.run(CELL)
    assert not out["correct"], out["compared"]
