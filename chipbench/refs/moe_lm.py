"""Plain reference of the DeepSeek-V2 mixture-of-experts language model and
its optimizer, for the check that decides ``correct`` in the MoE training
cells.

The equations, from the DeepSeek-V2 report (arXiv:2405.04434) and its
published modeling code: token embedding; pre-norm layers of multi-head
latent attention (no query compression: q = h Wq; c = RMSNorm(h Wkv_a[:r])
with r = kv_lora_rank; k_nope, v from c Wkv_b; a rotary part of the keys
shared by the heads from h Wkv_a[r:]), YaRN rotary frequencies and a softmax
scale of mscale(mscale_all_dim)² / sqrt(qk_nope + qk_rope), causal; then
the first layer a SwiGLU MLP, the others a MoE FFN: the gate in float32,
softmax over every routed expert, greedy top-k, weights renormalised only
where ``norm_topk_prob``, times ``routed_scaling_factor``; SwiGLU experts;
shared experts (one SwiGLU of n_shared times the expert width) added for
every token; DeepSeek's per-sequence expert-level balance loss
alpha · mean_b Σ_e (count_be · E / (S·k)) · mean_s p_bse over all k
selections.  A final RMSNorm, an untied head, cross-entropy over the head's
columns plus the balance loss.  AdamW as in :mod:`chipbench.refs.dense_lm`.

Departures from the report, each shared with the program under test:

* Capacity.  The report drops tokens at device level at capacity factor
  1.0; here each source chip's micro-batch gives every expert
  ``C = max(ceil(T·k/E·cf), k)`` slots and assignments past them are
  dropped in token-major order.  Routing and drops are decided per source
  chip over its whole micro-batch before any expert runs.
* Held experts.  Where the weights hold fewer experts than the router
  routes to (a chip's share of an expert-parallel group), assignments to the
  others are left out of the result; routing and the balance loss still
  see every expert.
* No device-level or communication balance loss (``n_group`` is 1).
* The rotary dims use the rotate-half layout; the published code
  de-interleaves them first, which is the same model with the rope columns
  of the query and key projections permuted.
* The balance loss enters the objective and the reported loss alike.

Written from those equations in ``jax.numpy``; it imports nothing of the
program under test.  Matmuls run in float32 at ``HIGHEST`` precision;
``cast`` rounds every matmul input to a lower precision for the control.
The whole batch is one call, its rows grouped by source chip and spread
over the devices by group; the expert weights are spread over the devices
by expert, so XLA's own resharding moves each chip's tokens to its
experts.  Each layer, each block of 512 queries and each block of rows of
the head is recomputed in the backward pass, so the activations of one
layer at a time are held; the AdamW moments wait on the host while the
gradient is computed, so the weights, the gradient and the moments are on
the devices together only for the update.  Parameters are a pytree in the
program's layout (``embed/embedding``, ``head/lm_head``, ``final_norm``,
``prelude0/...`` and ``blocks/slot0/...`` stacked over the MoE layers),
made by :func:`init_weights` from the seed, so the benchmark makes the
weights and both sides start from the same ones.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.refs import dense_lm
from chipbench.refs.dense_lm import (HI, key_data, leaf_norms, path_str,
                                     round_to)

Q_BLOCK = 512
HEAD_ROWS = 2048
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def init_weights(shapes, kd, dtype):
    """Seeded weights for a tree of shapes: every norm (``norm1``, ``norm2``,
    ``kv_norm``, ``final_norm``) one, the embedding N(0, 0.02^2), every
    other matrix N(0, 1/fan_in) over its second last dim, each value one
    that ``dtype`` holds exactly."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    out = []
    for i, (path, s) in enumerate(flat):
        name = path_str(path)
        if "norm" in name.split("/")[-1]:
            out.append(jnp.ones(s.shape, dtype))
            continue
        scale = 0.02 if name.endswith("embedding") else 1 / math.sqrt(
            s.shape[-2])
        z = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
        out.append(round_to(z * scale, dtype).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _yarn_scale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary(m: dict):
    """(inverse frequencies, cos/sin scale, softmax scale) of the
    configuration's attention, YaRN where ``rope_scaling`` says so."""
    d, base = m["qk_rope_head_dim"], m["rope_theta"]
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scale = 1.0 / math.sqrt(m["qk_nope_head_dim"] + d)
    y = m.get("rope_scaling")
    if not y:
        return plain, 1.0, scale

    def dim_of(turns):  # the dim that turns ``turns`` times over the context
        return (d * math.log(y["original_max_position_embeddings"]
                             / (turns * 2 * math.pi)) / (2 * math.log(base)))

    lo = max(math.floor(dim_of(y["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = plain / y["factor"] * ramp + plain * (1.0 - ramp)
    cs = (_yarn_scale(y["factor"], y["mscale"])
          / _yarn_scale(y["factor"], y["mscale_all_dim"]))
    scale *= _yarn_scale(y["factor"], y["mscale_all_dim"]) ** 2
    return inv, cs, scale


def _rope(x, inv, cs):
    """x [..., S, H, d] rotated by position, rotate-half layout."""
    ang = jnp.arange(x.shape[-3], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None] * cs, jnp.sin(ang)[:, None] * cs
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(p, h, m, mm, cast):
    """h [B, S, D] -> [B, S, D]."""
    B, S, _ = h.shape
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rd = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd = m["v_head_dim"]
    inv, cs, scale = rotary(m)
    q = mm(h, p["wq_b"]).reshape(B, S, H, nope + rd)
    kv_a = mm(h, p["wkv_a"])
    c = _rms(kv_a[..., :r], p["kv_norm"], m["rms_norm_eps"])
    kv = mm(c, p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_pe = _rope(kv_a[..., r:][:, :, None, :], inv, cs)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cs)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, rd))], -1)
    v = kv[..., nope:]

    @jax.checkpoint
    def block(args):
        qb, lo = args
        s = jnp.einsum("bqhd,bkhd->bhqk", cast(qb), cast(k),
                       precision=HI) * scale
        qpos = lo + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", cast(a), cast(v), precision=HI)

    nb = min(Q_BLOCK, S)
    qb = q.reshape(B, S // nb, nb, H, nope + rd).swapaxes(0, 1)
    o = jax.lax.map(block, (qb, jnp.arange(0, S, nb)))  # [S/nb, B, nb, H, vd]
    return mm(o.swapaxes(0, 1).reshape(B, S, H * vd), p["wo"])


def _swiglu(h, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)


def capacity(tokens: int, m: dict) -> int:
    return max(math.ceil(tokens * m["num_experts_per_tok"]
                         / m["n_routed_experts"] * m["capacity_factor"]),
               m["num_experts_per_tok"])


def moe_ffn(p, h, m, mm, cast=lambda x: x, exchange=True,
            shard=lambda x, spec: x):
    """h [n_src, r, S, D], the rows of each source chip -> (out like h,
    balance loss, dropped, routed).  ``p["w_*"]`` hold experts 0..Eh-1 in
    ``n_src`` equal shares, one a chip.  Where ``exchange`` is False each
    chip's tokens meet its own share in place of the experts they were
    routed to (the planted fault of an exchange that moved nothing)."""
    n, r, S, D = h.shape
    E, K = m["n_routed_experts"], m["num_experts_per_tok"]
    Eh = p["w_gate"].shape[0]
    T = r * S
    Cap = capacity(T, m)
    x = h.reshape(n, T, D)
    probs = jax.nn.softmax(mm(x, p["router"]), axis=-1)  # [n, T, E]
    w, idx = jax.lax.top_k(probs, K)
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * m["routed_scaling_factor"]

    counts = jnp.sum(jax.nn.one_hot(idx.reshape(n * r, S * K), E), axis=1)
    aux = m["aux_loss_alpha"] * jnp.mean(jnp.sum(
        counts * E / (S * K) * probs.reshape(n * r, S, E).mean(1), -1))

    # slot of each assignment among its expert's, token-major per chip
    e_flat = idx.reshape(n, T * K)
    seen = jnp.cumsum(jax.nn.one_hot(e_flat, E, dtype=jnp.int32), axis=1)
    slot = jnp.take_along_axis(seen, e_flat[..., None], -1)[..., 0] - 1
    held = e_flat < Eh
    kept = held & (slot < Cap)
    row = jnp.where(kept, e_flat * Cap + slot, Eh * Cap).reshape(n, T, K)

    def pack(xs, rows):  # one chip's [T, D] into its [Eh * C, D] buffer
        buf = jnp.zeros((Eh * Cap, D), xs.dtype)
        for j in range(K):
            buf = buf.at[rows[:, j]].add(xs, mode="drop")
        return buf

    def unpack(ys, rows, ws):  # the weighted sum over the k choices
        return sum(ys.at[rows[:, j]].get(mode="fill", fill_value=0.0)
                   * ws[:, j, None] for j in range(K))

    buf = jax.vmap(pack)(x, row)

    # [source chip, chip that holds the expert, its expert, slot, D]
    own = Eh // n
    buf = buf.reshape(n, n, own, Cap, D)
    wts = [p[k].reshape((n, own) + p[k].shape[1:]) for k in EXPERT_LEAVES]
    wts = [shard(a, P("b")) for a in wts]
    if exchange:  # to the chips that hold the experts, by XLA's resharding
        at = P(None, "b")
        spec = "sjecd,jedf->sjecf", "sjecf,jefd->sjecd"
    else:
        at = P("b")
        spec = "sjecd,sedf->sjecf", "sjecf,sefd->sjecd"
    buf = shard(buf, at)
    g = shard(jnp.einsum(spec[0], cast(buf), cast(wts[0]), precision=HI), at)
    u = shard(jnp.einsum(spec[0], cast(buf), cast(wts[1]), precision=HI), at)
    y = shard(jnp.einsum(spec[1], cast(jax.nn.silu(g) * u), cast(wts[2]),
                         precision=HI), at)
    y = shard(y, P("b")).reshape(n, Eh * Cap, D)
    out = jax.vmap(unpack)(y, row, w)
    out = out + _swiglu(x, p["shared_gate"], p["shared_up"],
                        p["shared_down"], mm)
    return (out.reshape(n, r, S, D), aux,
            jnp.sum(held & ~kept, dtype=jnp.float32),
            jnp.sum(held, dtype=jnp.float32))


def loss(w, tokens, labels, m: dict, cast=lambda x: x, exchange=True,
         shard=lambda x, spec: x):
    """Mean next-token cross-entropy plus the balance loss of a batch whose
    rows are grouped by source chip: tokens [n_src, r, S]."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision=HI)

    n, r, S = tokens.shape
    eps = m["rms_norm_eps"]

    def layer(p, x, moe):
        B = n * r
        hx = _rms(x, p["norm1"], eps).reshape(B, S, -1)
        x = x + _mla(p["mixer"], hx, m, mm, cast).reshape(x.shape)
        hx = _rms(x, p["norm2"], eps)
        f = p["ffn"]
        if not moe:
            return (x + _swiglu(hx, f["w_gate"], f["w_up"], f["w_down"], mm),
                    jnp.zeros((), jnp.float32))
        out, aux, _, _ = moe_ffn(f, hx, m, mm, cast, exchange, shard)
        return x + out, aux

    x = cast(w["embed"]["embedding"])[tokens]
    x, aux = jax.checkpoint(layer, static_argnums=2)(w["prelude0"], x, False)

    @jax.checkpoint
    def moe_layer(carry, p):
        x, aux = carry
        x, a = layer(p, x, True)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(moe_layer, (x, aux), w["blocks"]["slot0"])
    x = _rms(x, w["final_norm"], eps).reshape(n, r * S, -1)
    lab = labels.reshape(n, r * S)

    @jax.checkpoint
    def head(xb, lb):
        logits = mm(xb, w["head"]["lm_head"])
        picked = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    rows = min(HEAD_ROWS, r * S)
    nll = sum(head(x[:, i:i + rows], lab[:, i:i + rows])
              for i in range(0, r * S, rows))
    return nll / (n * r * S) + aux


def _is_expert(name: str, ndim: int) -> bool:
    return ndim == 4 and name.split("/")[-1] in EXPERT_LEAVES


class Reference:
    """The reference's training steps, each over the whole batch in one
    call.  Expert leaves, and their gradients and moments, are spread over
    ``devices`` by expert; every other leaf's gradient and moments by their
    first dim that divides, its parameters replicated."""

    def __init__(self, m: dict, opt: dict, shapes, devices, cast=lambda x: x):
        self.opt = opt
        self.dtype = jax.tree.leaves(shapes)[0].dtype
        mesh = Mesh(np.array(devices), ("b",))
        n = len(devices)
        self.n = n

        def spec(path, s, whole_ok):
            name = path_str(path)
            if _is_expert(name, len(s.shape)):
                return P(None, "b")
            if whole_ok:
                return P()
            for d, size in enumerate(s.shape):
                if size % n == 0:
                    return P(*[None] * d, "b")
            return P()

        def shardings(whole_ok):
            return jax.tree_util.tree_map_with_path(
                lambda pth, s: NamedSharding(mesh, spec(pth, s, whole_ok)),
                shapes)

        self._w, self._g = shardings(True), shardings(False)
        self._rows = NamedSharding(mesh, P("b"))
        rep = NamedSharding(mesh, P())

        def shard(x, s):
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))

        f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda a: a.astype(jnp.float32), t)
        self._grad = {
            ex: jax.jit(jax.value_and_grad(
                lambda w, t, lb, ex=ex: loss(w, t, lb, m, cast, ex, shard)),
                out_shardings=(rep, self._g))
            for ex in (True, False)}
        self._init = jax.jit(lambda kd: f32(init_weights(shapes, kd,
                                                         self.dtype)),
                             out_shardings=self._w)
        self._zeros = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                 shapes), out_shardings=self._g)
        self._update = jax.jit(self._adamw, static_argnums=4,
                               out_shardings=(self._w, {"m": self._g,
                                                        "v": self._g}),
                               donate_argnums=(0, 2))
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda w, kd: leaf_norms(jax.tree.map(
            jnp.subtract, w, f32(init_weights(shapes, kd, self.dtype)))))

    # AdamW with clipping and warm-up, parameters rounded to their dtype
    _adamw = dense_lm.Reference._adamw

    def run(self, seed: int, batches, alter=None, grad_rows=None,
            exchange: bool = True) -> dict:
        """Readings of the first ``len(batches)`` steps from the seed's
        weights: each step's loss, the first gradient's norm per leaf, and
        the norm per leaf of the parameters' change.  ``batches`` hold
        ``(tokens, labels)`` of [rows, S], rows in source-chip order.

        Planted faults for the calibration: ``grad_rows(tokens, labels) ->
        (tokens, labels, scale)`` takes the gradient over some rows of
        each chip only, scaled; ``alter(w) -> w`` alters the parameters
        after the first update; ``exchange=False`` runs each chip's tokens
        through its own experts."""
        kd = key_data(seed)
        w = self._init(kd)
        moments = None  # on the host between updates
        losses, first = [], None
        for t, (tokens, labels) in enumerate(batches):
            tk, lb = (np.asarray(a).reshape(self.n, -1, a.shape[-1])
                      for a in (tokens, labels))
            lv, g = self._grad[exchange](w, *self._put(tk, lb))
            scale = 1.0
            if grad_rows is not None:
                del g
                tk, lb, scale = grad_rows(tk, lb)
                _, g = self._grad[exchange](w, *self._put(tk, lb))
            losses.append(float(lv))
            if first is None:
                first = np.asarray(self._norms(g)) * scale
            state = ({"m": self._zeros(), "v": self._zeros()}
                     if moments is None else
                     jax.device_put(moments, {"m": self._g, "v": self._g}))
            w, state = self._update(w, g, state, np.float32(scale), t)
            del g
            if t + 1 < len(batches):
                moments = jax.device_get(state)
            del state
            if alter is not None and t == 0:
                w = alter(w)
        change = np.asarray(self._change(w, kd))
        return {"losses": losses, "grad_norms": first, "change_norms": change}

    def _put(self, tokens, labels):
        return (jax.device_put(tokens, self._rows),
                jax.device_put(labels, self._rows))
