"""Plain reference of the trained model and its optimizer, for the check
that decides ``correct`` in the training cells.

A llama-style decoder: token embedding, pre-norm layers of grouped-query
attention with rotary positions (rotate-half layout) and a causal sliding
window, then a SwiGLU MLP, a final RMSNorm and an untied output head;
cross-entropy over the head's columns.  AdamW with global-norm clipping and
linear warm-up, parameters stored in the configuration's dtype after each
update.  Written from those equations in ``jax.numpy``: it imports nothing
of the program under test.  Matmuls run in float32 at ``HIGHEST``
precision; ``cast`` rounds every matmul input to a lower precision for the
control.

Parameters are a pytree in the program's layout (``embed/embedding``,
``head/lm_head``, ``final_norm`` and ``blocks/slot0/...`` stacked over
layers), made by :func:`init_weights` from the seed, so the benchmark makes
the weights and both sides start from the same ones.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HI = jax.lax.Precision.HIGHEST


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def key_data(seed: int) -> np.ndarray:
    """A threefry key from a seed of up to 64 bits, as data, so that one
    compiled program serves every seed."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision and kept in its own type.

    A cast down and back up may be dropped by the compiler, which is free to
    keep excess precision; ``reduce_precision`` is not."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def init_weights(shapes, kd, dtype):
    """Seeded weights for a tree of shapes: norms one, the embedding
    N(0, 0.02^2), every other matrix N(0, 1/fan_in), each value one that
    ``dtype`` holds exactly."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    out = []
    for i, (path, s) in enumerate(flat):
        name = path_str(path)
        if name.split("/")[-1].endswith("norm"):
            out.append(jnp.ones(s.shape, dtype))
            continue
        scale = 0.02 if name.endswith("embedding") else 1 / math.sqrt(
            s.shape[-2])
        z = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
        out.append(round_to(z * scale, dtype).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _round_fp8(x):
    """Per-tensor scaled rounding to float8 with 4 exponent and 3 mantissa
    bits, as fp8 matmuls scale their operands so that the largest lands at
    the format's largest finite value (240 with these bits)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 240.0, 1.0)
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


@jax.custom_vjp
def to_fp8(x):
    """The control's precision: every matmul operand rounded to float8,
    in the forward pass and, through the cotangent, in the backward."""
    return _round_fp8(x)


to_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(w, tokens, labels, m: dict, cast=lambda x: x):
    """Mean next-token cross-entropy of a batch; ``m`` holds the sizes."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision=HI)

    B, S = tokens.shape
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(S)[None, :]
    mask = k_pos <= q_pos
    if m.get("sliding_window"):
        mask &= k_pos > q_pos - m["sliding_window"]
    x = cast(w["embed"]["embedding"])[tokens]
    layers = w["blocks"]["slot0"]
    for i in range(m["num_layers"]):
        p = jax.tree.map(lambda a: a[i], layers)
        h = _rms(x, p["norm1"], eps)
        q = _rope(mm(h, p["mixer"]["wq"]).reshape(B, S, H, hd), m["rope_theta"])
        k = _rope(mm(h, p["mixer"]["wk"]).reshape(B, S, Hkv, hd),
                  m["rope_theta"])
        v = mm(h, p["mixer"]["wv"]).reshape(B, S, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k), precision=HI)
        s = jnp.where(mask, s / math.sqrt(hd), -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", cast(a), cast(v), precision=HI)
        x = x + mm(o.reshape(B, S, H * hd), p["mixer"]["wo"])
        h = _rms(x, p["norm2"], eps)
        f = jax.nn.silu(mm(h, p["ffn"]["w_gate"])) * mm(h, p["ffn"]["w_up"])
        x = x + mm(f, p["ffn"]["w_down"])
    x = _rms(x, w["final_norm"], eps)
    logits = mm(x, w["head"]["lm_head"])
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


class Reference:
    """The reference's training steps over a batch split in blocks of rows,
    each block spread over ``devices`` by batch rows (XLA's own reduction
    sums the blocks' gradients across them).  Nothing but the weights,
    the optimizer's moments and one gradient sum stays on the devices."""

    def __init__(self, m: dict, opt: dict, shapes, devices, block_rows: int,
                 cast=lambda x: x):
        self.m, self.opt, self.block_rows = m, opt, block_rows
        dtype = jax.tree.leaves(shapes)[0].dtype
        self.dtype = dtype
        mesh = Mesh(np.array(devices), ("b",))
        rep = NamedSharding(mesh, P())
        self._rows = NamedSharding(mesh, P("b"))
        f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda a: a.astype(jnp.float32), t)

        def block_acc(total, w, tokens, labels):
            lv, g = jax.value_and_grad(loss)(w, tokens, labels, m, cast)
            return lv, jax.tree.map(jnp.add, total, g)

        self._acc = jax.jit(block_acc, out_shardings=(rep, rep),
                            donate_argnums=0)
        self._init = jax.jit(lambda kd: f32(init_weights(shapes, kd, dtype)),
                             out_shardings=rep)
        self._zeros = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                 shapes), out_shardings=rep)
        self._update = jax.jit(self._adamw, static_argnums=4,
                               out_shardings=(rep, rep),
                               donate_argnums=(0, 2))
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda w, kd: leaf_norms(jax.tree.map(
            jnp.subtract, w, f32(init_weights(shapes, kd, dtype)))))

    def _adamw(self, w, total, state, gscale, t: int):
        o = self.opt
        g = jax.tree.map(lambda a: a * gscale, total)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        lr = o["learning_rate"] * min(1.0, (t + 1) / o["warmup_steps"])
        b1, b2 = o["beta1"], o["beta2"]

        def upd(p, gr, mo, ve):
            gr = gr * clip
            mo = b1 * mo + (1 - b1) * gr
            ve = b2 * ve + (1 - b2) * gr * gr
            mh, vh = mo / (1 - b1 ** (t + 1)), ve / (1 - b2 ** (t + 1))
            new = p - lr * (mh / (jnp.sqrt(vh) + o["eps"])
                            + o["weight_decay"] * p)
            # the configuration stores parameters in its own dtype
            return round_to(new, self.dtype), mo, ve

        out = jax.tree.map(upd, w, g, state["m"], state["v"])
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda _, o3: o3[i], w, out)
        return pick(0), {"m": pick(1), "v": pick(2)}

    def grad_sum(self, w, tokens, labels):
        """Mean loss over all rows, the sum of the blocks' mean gradients,
        and the number of blocks."""
        rows = min(self.block_rows, tokens.shape[0])
        n = tokens.shape[0] // rows
        total, mean_loss = self._zeros(), 0.0
        for b in range(n):
            sl = slice(b * rows, (b + 1) * rows)
            lv, total = self._acc(total, w,
                                  jax.device_put(tokens[sl], self._rows),
                                  jax.device_put(labels[sl], self._rows))
            mean_loss += float(lv) / n
        return mean_loss, total, n

    def run(self, seed: int, batches, alter=None, grad_rows=None) -> dict:
        """Readings of the first ``len(batches)`` steps from the seed's
        weights: each step's loss, the first gradient's norm per leaf, and
        the norm per leaf of the parameters' change.

        ``grad_rows(tokens, labels) -> (tokens, labels, scale)`` and
        ``alter(w) -> w`` plant faults for the calibration: the gradient
        taken over some rows only and scaled, and the parameters altered
        after the first update.
        """
        kd = key_data(seed)
        w = self._init(kd)
        state = {"m": self._zeros(), "v": self._zeros()}
        losses, first = [], None
        for t, (tokens, labels) in enumerate(batches):
            lv, total, n = self.grad_sum(w, tokens, labels)
            scale = 1.0 / n
            if grad_rows is not None:
                tk, lb, sc = grad_rows(tokens, labels)
                _, total, n = self.grad_sum(w, tk, lb)
                scale = sc / n
            losses.append(lv)
            if first is None:
                first = np.asarray(self._norms(total)) * scale
            w, state = self._update(w, total, state, np.float32(scale), t)
            if alter is not None and t == 0:
                w = alter(w)
        change = np.asarray(self._change(w, kd))
        return {"losses": losses, "grad_norms": first, "change_norms": change}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the largest relative gap of a step's loss, and
    by the worst leaf the gap of the first gradient's norm and of the
    parameters' change, each against the reference's norm of that leaf or
    of the median leaf, whichever is larger.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    g_med = float(np.median(g_ref))
    g_gap = np.abs(np.asarray(prog["grad_norms"]) - g_ref) / np.maximum(
        g_ref, g_med)
    moved = g_ref >= 1e-3 * g_med
    c_ref = np.asarray(ref["change_norms"], np.float64)[moved]
    c_prog = np.asarray(prog["change_norms"], np.float64)[moved]
    c_gap = np.abs(c_prog - c_ref) / np.maximum(c_ref, float(np.median(c_ref)))
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": float(np.max(g_gap)),
            "change_gap": float(np.max(c_gap))}


def batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    """The synthetic token stream's batch ``step``: uniform token ids from a
    Philox stream keyed by the seed and counted by the step, labels the
    next token (the same draw as the program's ``SyntheticLM``)."""
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[0, 0, 0, step]))
    toks = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]
