"""MoE expert-parallel dispatch through the paper's hierarchical all-to-all.

Runs on 8 CPU devices (mesh 2 pods x 4 lanes):

1. routes a batch of tokens to experts with the *flat* XLA all-to-all and
   with ``fulllane_all_to_all`` (paper §2.2: own-pod blocks on-node
   while the rest cross pods, each forwarded on-node as it lands) inside
   shard_map — results must be identical;
2. compares the collective bytes in the two compiled HLO modules.

  PYTHONPATH=src python examples/moe_ep_demo.py
"""

import jax

jax.config.update("jax_num_cpu_devices", 8)

import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import collectives as C
from repro.launch.hloanalysis import analyze_module


def main():
    mesh = jax.make_mesh((2, 4), ("pod", "lane"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    P_TOTAL = 8  # devices == "expert groups"
    TOK, D = 16, 32  # tokens per device destined per expert-group, width

    rng = np.random.RandomState(0)
    # x[d] on device s: tokens from s for expert-group d
    x = rng.randn(8, P_TOTAL, TOK, D).astype(np.float32)

    def dispatch(a2a):
        def f(xs):
            local = xs[0]  # [P_TOTAL, TOK, D]
            routed = a2a(local.reshape(P_TOTAL, TOK * D))
            return routed.reshape(P_TOTAL, TOK, D)[None]
        return jax.jit(shard_map(f, mesh=mesh, in_specs=P(("pod", "lane")),
                                 out_specs=P(("pod", "lane"))))

    flat = dispatch(lambda v: C.flat_all_to_all(v, "pod", "lane"))
    hier = dispatch(lambda v: C.fulllane_all_to_all(v, "pod", "lane"))

    out_f = np.asarray(flat(x))
    out_h = np.asarray(hier(x))
    np.testing.assert_allclose(out_f, out_h, rtol=1e-6)
    print("dispatch equivalence: OK (flat == hierarchical)")

    for name, fn in [("flat", flat), ("fulllane", hier)]:
        comp = fn.lower(jax.ShapeDtypeStruct(x.shape, jnp.float32)).compile()
        cost = analyze_module(comp.as_text())
        print(f"{name:9s} collective bytes/device: "
              f"{ {k: v for k, v in sorted(cost.collective_bytes.items())} }")
    print("""
On this toy mesh both phases are ICI; on a multi-pod mesh every chip of a
pod drives its own cross-pod stream while its on-node link carries the
own-pod and forwarded blocks — the paper's full-lane argument.
""")


if __name__ == "__main__":
    main()
