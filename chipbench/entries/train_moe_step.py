"""Drive the program's expert-parallel train step: a DeepSeek-V2 MoE model
through ``make_train_step_shardmap(..., backend="fulllane")``, each MoE
layer's tokens exchanged over the data-parallel axes with
``fulllane_all_to_all``, fed by the program's ``SyntheticLM`` stream
through its ``Prefetcher``.

Set-up, window and check follow the dense entry (:mod:`.train_step`): one
compiled step, weights made on the devices from the seed, the first three
steps read for the check, the window going on with the same state.  Here
the configuration file gives the experts a chip holds; the chips of the
mesh's ``pod`` and ``data`` axes form one expert-parallel group, so the
program holds ``experts_per_chip`` times that many experts, all 64 on a
2x2 and the first 16 on one chip.  The window also reads the layers'
``moe_dropped`` and ``moe_routed`` counters where it reads the loss.  The
check is :mod:`chipbench.refs.moe_lm`, given the same share of the experts.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts
from chipbench.entries import train_step as dense_entry
from chipbench.harness import Compared, compare, make_mesh
from chipbench.refs import dense_lm, moe_lm

def ep_chips(traffic: dict) -> int:
    """Chips of the expert-parallel group: the mesh's ``pod`` x ``data``."""
    pod, data, _ = traffic["mesh"]
    return pod * data


def program_config(config: dict, chips: int):
    """The program's ModelConfig for this configuration file: the named
    architecture at the file's depth, vocabulary and share of the experts,
    parameters replicated over the data-parallel axes.  The file's widths
    must be the program's."""
    from repro.configs import get_config, get_smoke_config

    prog, m = config["program"], config["model"]
    base = (get_smoke_config if prog.get("smoke") else get_config)(
        prog["arch"])
    cfg = dataclasses.replace(
        base, num_layers=m["num_layers"], vocab_size=m["vocab_size"],
        dtype=m["dtype"],
        moe=dataclasses.replace(
            base.moe, num_experts_held=m["experts_per_chip"] * chips),
        parallel=dataclasses.replace(base.parallel, fsdp=False))
    sizes = ref_sizes(config)
    wrong = {k: (sizes[k], v) for k, v in published_widths(cfg).items()
             if (sizes[k] if k != "rope_scaling" else
                 {n: sizes[k][n] for n in v}) != v}
    if wrong:
        raise ValueError(f"file and program differ (file, program): {wrong}")
    return cfg


def published_widths(cfg) -> dict:
    """A program config's sizes under the published config.json's keys,
    with the capacity factor and the balance coefficient it assumes."""
    a, e, y = cfg.attn, cfg.moe, cfg.attn.yarn
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": e.d_ff_expert,
        "n_routed_experts": e.num_experts,
        "n_shared_experts": e.num_shared_experts,
        "num_experts_per_tok": e.top_k, "num_attention_heads": a.num_heads,
        "kv_lora_rank": a.kv_lora_rank, "q_lora_rank": a.q_lora_rank,
        "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim, "v_head_dim": a.v_head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": a.rope_theta,
        "norm_topk_prob": e.norm_topk_prob,
        "routed_scaling_factor": e.routed_scaling_factor,
        "seq_aux": e.seq_aux, "first_k_dense_replace": cfg.first_k_dense,
        "capacity_factor": e.capacity_factor,
        "aux_loss_alpha": e.router_aux_weight,
        "rope_scaling": {
            "factor": y.factor,
            "original_max_position_embeddings": y.original_max_position,
            "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
            "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
    }


def ref_sizes(config: dict) -> dict:
    """What the reference reads: the published keys, with the model
    section's program sizes and assumed values over them."""
    return {**config, **config["model"]}


def matmul_weights(config: dict, chips: int) -> dict:
    """Weights each token multiplies, by part: the attention projections
    of every layer, the dense layer's MLP, per MoE layer the router, the
    shared experts and the routed experts of its top-k that the mesh holds
    (k times the held share on average, routing being even), and the head
    over the vocabulary held.  The input embedding is a lookup."""
    m = ref_sizes(config)
    d, H = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    r, f = m["kv_lora_rank"], m["moe_intermediate_size"]
    attn = (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
            + H * vd * d)
    held = min(m["experts_per_chip"] * chips, m["n_routed_experts"])
    routed = m["num_experts_per_tok"] * held / m["n_routed_experts"]
    moe = (d * m["n_routed_experts"] + 3 * d * f * m["n_shared_experts"]
           + 3 * d * f * routed)
    dense = m["first_k_dense_replace"]
    return {"attention": m["num_layers"] * attn,
            "dense_mlp": dense * 3 * d * m["intermediate_size"],
            "moe": (m["num_layers"] - dense) * moe,
            "head": d * m["vocab_size"]}


def train_flops_per_token(config: dict, chips: int, seq: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    weight, and 6 * heads * (qk + v head dims) per attended key of every
    layer.  Recomputation is not counted."""
    m = ref_sizes(config)
    per_key = 6 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return (6.0 * sum(matmul_weights(config, chips).values())
            + m["num_layers"] * per_key * counts.mean_context(seq, None))


class MoETrainCell(dense_entry.TrainCell):
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.config = config
        self.chips = ep_chips(traffic)
        cfg = program_config(config, self.chips)
        self.m, self.opt = ref_sizes(config), config["optimizer"]
        self.traffic, self.seed, self.devices = traffic, seed, devices
        self.cfg = cfg
        self.mesh = make_mesh(traffic["mesh"], dense_entry._AXES, devices)
        self.seq = traffic["seq"]
        self.rows = traffic["seqs_per_chip"] * len(devices)

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

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
            lambda kd: moe_lm.init_weights(shapes, kd, dtype),
            out_shardings=ns(pspec))
        mdt = jnp.dtype(cfg.parallel.optimizer_dtype)

        def zeros_state():
            z = jax.tree.map(lambda s: jnp.zeros(s.shape, mdt), shapes)
            return {"m": z, "v": z, "step": jnp.zeros((), jnp.int32)}

        self._state = jax.jit(zeros_state, out_shardings=ns(ospec))
        self._norms = jax.jit(dense_lm.leaf_norms)
        self._change = jax.jit(lambda p, kd: dense_lm.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, moe_lm.init_weights(shapes, kd, dtype))))
        kd = dense_lm.key_data(self.seed)
        params, state = self._weights(kd), self._state()
        self.step = mk(sample).lower(params, state, sample).compile()
        self.params, self.state = params, state
        self.readings = self.first_steps(self.seed, fresh=False)

    # -- window ---------------------------------------------------------

    def window(self, seconds: float, traced: bool) -> dict:
        params, state = self.params, self.state
        steps, logged = 0, []
        dropped = routed = 0.0
        every = self.traffic["log_every"]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            _, batch = next(self.feed)
            params, state, met = self.step(params, state, self._place(batch))
            steps += 1
            if steps % every == 0:  # as a training loop logs
                logged.append(float(met["loss"]))
                dropped += float(met["moe_dropped"])
                routed += float(met["moe_routed"])
        jax.block_until_ready((params, state))
        dt = time.perf_counter() - t0
        self.params, self.state = params, state
        tokens = steps * self.rows * self.seq
        return {"attempted": steps,
                "failed": sum(not math.isfinite(v) for v in logged),
                "metrics": {"train_tokens_per_s": tokens / dt},
                "info": {"steps": steps, "tokens": tokens, "seconds": dt,
                         "flops_per_token": train_flops_per_token(
                             self.config, self.chips, self.seq),
                         "moe_dropped": dropped, "moe_routed": routed}}

    # -- check ----------------------------------------------------------

    def reference(self, cast=lambda x: x):
        return moe_lm.Reference(self.m, self.opt, self.shapes, self.devices,
                                cast=cast)

    def check(self) -> list[Compared]:
        ref = self.reference().run(self.seed, self.ref_batches(self.seed))
        return compare(dense_lm.gaps(self.readings, ref),
                       self.traffic["limits"])


def build(config, traffic, seed, devices) -> MoETrainCell:
    return MoETrainCell(config, traffic, seed, devices)


def calibrate(cell: MoETrainCell, seeds, control_seeds) -> dict:
    """Readings that the limits are set from: the program's gaps on
    ``seeds``; on ``control_seeds`` the gaps of the reference put in the
    program's place in float8, and with each planted fault: half of each
    chip's rows, an exchange that moves nothing (more than one chip), one
    parameter altered after the first step."""
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
        out["control"][s] = dense_lm.gaps(
            cell.reference(dense_lm.to_fp8).run(s, batches), want)
        out["half_batch"][s] = dense_lm.gaps(ref.run(
            s, batches, grad_rows=lambda t, l: (
                t[:, :t.shape[1] // 2], l[:, :l.shape[1] // 2], 1.0)), want)
        if cell.chips > 1:
            out["no_exchange"][s] = dense_lm.gaps(
                ref.run(s, batches, exchange=False), want)

        def alter(w):
            head = w["head"]["lm_head"]
            return {**w, "head": {"lm_head": head.at[0, 0].add(1.0)}}

        out["altered"][s] = dense_lm.gaps(ref.run(s, batches, alter=alter),
                                          want)
    return out
