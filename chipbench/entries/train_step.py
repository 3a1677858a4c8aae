"""Drive the program's data-parallel train step, with gradient sync through
``hierarchical_psum`` (``make_train_step_shardmap(..., backend="fulllane")``),
placed as ``repro.launch.train`` places it, fed by the program's own
``SyntheticLM`` stream through its ``Prefetcher``.

Set-up builds one compiled step with its state, makes the weights on the
devices from the seed in one jitted call, and drives the step through its
first three steps on the stream's first batches.  From those it keeps the
readings the check compares: each step's loss, the first gradient per leaf
as the optimizer got it (from its first moment after one step and the
reported gradient norm, which sets the clipping), and the change of every
parameter leaf after three steps, read before the fourth step consumes the
parameters.  The window then goes on with the same object and stream.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import counts
from chipbench.harness import Compared, compare, make_mesh
from chipbench.refs import dense_lm

CHECK_STEPS = 3
_AXES = ("pod", "data", "model")


def program_config(config: dict):
    """The program's ModelConfig for this configuration file: the named
    architecture with the file's sizes, parameters replicated over the
    data-parallel axes as the shard_map step requires."""
    from repro.configs import get_config, get_smoke_config

    prog, m = config["program"], config["model"]
    base = (get_smoke_config if prog.get("smoke") else get_config)(
        prog["arch"])
    attn = dataclasses.replace(
        base.attn, num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"])
    return dataclasses.replace(
        base, num_layers=m["num_layers"], d_model=m["d_model"],
        d_ff=m["d_ff"], vocab_size=m["vocab_size"], norm_eps=m["norm_eps"],
        dtype=m["dtype"], attn=attn,
        parallel=dataclasses.replace(base.parallel, fsdp=False))


class TrainCell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.m, self.opt = config["model"], config["optimizer"]
        self.traffic, self.seed, self.devices = traffic, seed, devices
        self.cfg = program_config(config)
        self.mesh = make_mesh(traffic["mesh"], _AXES, devices)
        self.seq = traffic["seq"]
        self.rows = traffic["seqs_per_chip"] * len(devices)

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from repro.models import lm
        from repro.training.optimizer import OptConfig
        from repro.training.train_step import (batch_pspec,
                                               make_train_step_shardmap)

        cfg, mesh = self.cfg, self.mesh
        if cfg.parallel.optimizer_dtype != "float32":
            raise ValueError("the check reads the first gradient from float32 "
                             "moments")
        opt_cfg = OptConfig(**self.opt,
                            moment_dtype=cfg.parallel.optimizer_dtype)
        mk, (pspec, ospec) = make_train_step_shardmap(cfg, mesh, opt_cfg,
                                                      backend="fulllane")
        sample = {k: np.zeros((self.rows, self.seq), np.int32)
                  for k in ("tokens", "labels")}

        def ns(tree):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))

        self.bsh = ns(batch_pspec(mesh, sample))
        shapes = jax.eval_shape(
            lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
        self.shapes = shapes
        dtype = jnp.dtype(cfg.dtype)
        self._weights = jax.jit(
            lambda kd: dense_lm.init_weights(shapes, kd, dtype),
            out_shardings=ns(pspec))
        mdt = jnp.dtype(cfg.parallel.optimizer_dtype)

        def zeros_state():
            z = jax.tree.map(lambda s: jnp.zeros(s.shape, mdt), shapes)
            return {"m": z, "v": z, "step": jnp.zeros((), jnp.int32)}

        self._state = jax.jit(zeros_state, out_shardings=ns(ospec))
        self._norms = jax.jit(dense_lm.leaf_norms)
        self._change = jax.jit(lambda p, kd: dense_lm.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, dense_lm.init_weights(shapes, kd, dtype))))
        kd = dense_lm.key_data(self.seed)
        params, state = self._weights(kd), self._state()
        self.step = mk(sample).lower(params, state, sample).compile()
        self.params, self.state = params, state
        self.readings = self.first_steps(self.seed, fresh=False)

    def _feed(self, seed: int):
        from repro.training.data import Prefetcher, SyntheticLM

        return Prefetcher(SyntheticLM(self.cfg, self.rows, self.seq,
                                      seed=seed), depth=2)

    def _place(self, batch):
        return jax.device_put(batch, self.bsh)

    def first_steps(self, seed: int, fresh: bool = True) -> dict:
        """Weights from ``seed``, then the first steps through the compiled
        step and the stream; returns the readings and keeps the state."""
        kd = dense_lm.key_data(seed)
        if fresh:
            self.params, self.state = self._weights(kd), self._state()
        self.feed = self._feed(seed)
        params, state = self.params, self.state
        losses, grads = [], None
        for t in range(CHECK_STEPS):
            _, batch = next(self.feed)
            params, state, met = self.step(params, state, self._place(batch))
            losses.append(float(met["loss"]))
            if t == 0:
                gnorm = float(met["grad_norm"])
                clip = min(1.0, self.opt["grad_clip"] / max(gnorm, 1e-9))
                grads = np.asarray(self._norms(state["m"])) / (
                    (1 - self.opt["beta1"]) * clip)
        change = np.asarray(self._change(params, kd))
        self.params, self.state = params, state
        return {"losses": losses, "grad_norms": grads, "change_norms": change}

    # -- window ---------------------------------------------------------

    def window(self, seconds: float, traced: bool) -> dict:
        params, state = self.params, self.state
        steps, logged = 0, []
        every = self.traffic["log_every"]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            _, batch = next(self.feed)
            params, state, met = self.step(params, state, self._place(batch))
            steps += 1
            if steps % every == 0:
                logged.append(float(met["loss"]))  # as a training loop logs
        jax.block_until_ready((params, state))
        dt = time.perf_counter() - t0
        self.params, self.state = params, state
        tokens = steps * self.rows * self.seq
        return {"attempted": steps,
                "failed": sum(not math.isfinite(v) for v in logged),
                "metrics": {"train_tokens_per_s": tokens / dt},
                "info": {"steps": steps, "tokens": tokens, "seconds": dt,
                         "flops_per_token": counts.train_flops_per_token(
                             self.m, self.seq)}}

    def free(self) -> None:
        self.params = self.state = None

    # -- check ----------------------------------------------------------

    def reference(self, cast=lambda x: x):
        return dense_lm.Reference(self.m, self.opt, self.shapes, self.devices,
                                  block_rows=2 * len(self.devices), cast=cast)

    def ref_batches(self, seed: int):
        return [dense_lm.batch(seed, t, self.rows, self.seq,
                               self.m["vocab_size"])
                for t in range(CHECK_STEPS)]

    def check(self) -> list[Compared]:
        ref = self.reference().run(self.seed, self.ref_batches(self.seed))
        return compare(dense_lm.gaps(self.readings, ref),
                       self.traffic["limits"])


def build(config, traffic, seed, devices) -> TrainCell:
    return TrainCell(config, traffic, seed, devices)


def calibrate(cell: TrainCell, seeds, control_seeds) -> dict:
    """Readings that the limits are set from: the program's gaps on
    ``seeds``; on ``control_seeds`` the gaps of the reference put in the
    program's place in float8, and with each planted fault."""
    cell.setup()
    out = {"program": {}, "control": {}, "half_batch": {}, "no_exchange": {},
           "altered": {}}
    ref = cell.reference()
    for s in seeds:
        prog = cell.readings if s == cell.seed else cell.first_steps(s)
        cell.free()
        want = ref.run(s, cell.ref_batches(s))
        out["program"][s] = dense_lm.gaps(prog, want)
        if s not in control_seeds:
            continue
        batches = cell.ref_batches(s)
        rows, ndp = cell.rows, len(cell.devices)
        out["control"][s] = dense_lm.gaps(
            cell.reference(dense_lm.to_fp8).run(s, batches), want)
        out["half_batch"][s] = dense_lm.gaps(ref.run(
            s, batches, grad_rows=lambda t, l: (t[:rows // 2], l[:rows // 2],
                                                1.0)), want)
        if ndp > 1:
            out["no_exchange"][s] = dense_lm.gaps(ref.run(
                s, batches, grad_rows=lambda t, l: (
                    t[:rows // ndp], l[:rows // ndp], 1.0 / ndp)), want)

        def alter(w):
            head = w["head"]["lm_head"]
            return {**w, "head": {"lm_head": head.at[0, 0].add(1.0)}}

        out["altered"][s] = dense_lm.gaps(ref.run(s, batches, alter=alter),
                                          want)
    return out
