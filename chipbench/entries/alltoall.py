"""Drive the program's k-lane alltoall, ``fulllane_all_to_all``, inside
``jax.shard_map`` on a ("pod", "lane") mesh: the dispatch of an
expert-parallel group, one block of routed token copies per destination.

Closed loop, one call in flight: each call is dispatched, waited for with
``block_until_ready`` and followed by the next, as an expert layer waits
for its dispatch.  The buffer is made on the devices from the seed.  A
sample of the calls, drawn from the seed, keeps its output; after the
window every element of those outputs is compared with the alltoall's
semantics computed by a plain transpose of the global array.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import counts
from chipbench.harness import Compared, make_mesh
from chipbench.refs import dense_lm

AXES = ("pod", "lane")
# the name of the timed program, which the trace reduction looks for
PROGRAM = "chipbench_alltoall"


def reference(x, p: int):
    """Block ``d`` of device ``s`` lands as block ``s`` of device ``d``: in
    the global ``[p * p, ...]`` view, a transpose of the leading (s, d)."""
    return x.reshape((p, p) + x.shape[1:]).swapaxes(0, 1).reshape(x.shape)


class AlltoallCell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.traffic, self.seed, self.devices = traffic, seed, devices
        self.p = len(devices)
        if self.p != config["prefill_ep_group"]:
            raise ValueError(f"the configuration's EP group is "
                             f"{config['prefill_ep_group']} chips, the cell "
                             f"has {self.p}")
        self.block = (traffic["tokens_per_block"], config["model"]["hidden_size"])
        self.dtype = jnp.dtype(traffic["dtype"])
        self.samples = traffic["sampled_calls"]

    def setup(self) -> None:
        from repro.core import collectives as C

        mesh = make_mesh(self.traffic["mesh"], AXES, self.devices)
        self.sharding = NamedSharding(mesh, P(AXES))
        shape = (self.p * self.p,) + self.block
        dtype = self.dtype

        def make(kd):
            key = jax.random.wrap_key_data(kd, impl="threefry2x32")
            return jax.random.normal(key, shape, jnp.float32).astype(dtype)

        self.x = jax.jit(make, out_shardings=self.sharding)(
            dense_lm.key_data(self.seed))

        def chipbench_alltoall(v):
            return C.fulllane_all_to_all(v, *AXES)

        fn = jax.jit(jax.shard_map(chipbench_alltoall, mesh=mesh,
                                   in_specs=P(AXES), out_specs=P(AXES)))
        self.call = fn.lower(self.x).compile()
        jax.block_until_ready(self.call(self.x))
        self._mismatch = jax.jit(
            lambda out, x: jnp.sum(out != reference(x, self.p)),
            in_shardings=(self.sharding, self.sharding))
        self._mismatch(self.x, self.x).block_until_ready()

    def window(self, seconds: float, traced: bool) -> dict:
        rng = np.random.default_rng(self.seed)
        # calls whose outputs are kept: drawn from the seed among the first
        # ones, so that every run reaches them, and the last one
        keep_at = set(rng.choice(self.traffic["sample_from_first"],
                                 self.samples, replace=False).tolist())
        kept, calls, failed = [], 0, 0
        out = None
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            out = self.call(self.x)
            out.block_until_ready()
            if calls in keep_at:
                kept.append(out)
            calls += 1
        dt = time.perf_counter() - t0
        kept.append(out)
        self.kept = kept
        nbytes = self.x.nbytes // self.p
        return {"attempted": calls, "failed": failed,
                "metrics": {"collective_us": dt / calls * 1e6},
                "info": {"calls": calls, "seconds": dt, "program": PROGRAM,
                         "egress_bytes": counts.alltoall_egress_bytes(
                             nbytes, self.p)}}

    def free(self) -> None:
        self.call = None

    def check(self) -> list[Compared]:
        wrong = sum(int(self._mismatch(o, self.x)) for o in self.kept)
        return [Compared("wrong_elements", float(wrong),
                         self.traffic["limits"]["wrong_elements"])]


def build(config, traffic, seed, devices) -> AlltoallCell:
    return AlltoallCell(config, traffic, seed, devices)


def calibrate(cell: AlltoallCell, seeds, control_seeds) -> dict:
    """The program's count of wrong elements on ``seeds``, and on
    ``control_seeds`` that of the reference put in the program's place in
    float8, the precision below the buffer's bfloat16."""
    out = {"program": {}, "control": {}}
    for s in seeds:
        cell.seed = s
        cell.setup()
        cell.window(2.0, traced=False)
        out["program"][s] = float(sum(int(cell._mismatch(o, cell.x))
                                      for o in cell.kept))
        if s in control_seeds:
            low = jax.jit(lambda x: jax.lax.reduce_precision(
                reference(x, cell.p), exponent_bits=4, mantissa_bits=3),
                out_shardings=cell.sharding)(cell.x)
            out["control"][s] = float(cell._mismatch(low, cell.x))
    return out
