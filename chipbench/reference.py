"""The plain reference in float32 ``jax.numpy``: the shared parts.

It imports nothing of the program. A model family's equations (one
unit of its stack, and the batch's loss and gradient) are in
``families/<family>.py``, which builds them from the helpers here:
``mm`` (a matrix product at ``highest`` precision, so on the TPU it is
float32 and not bfloat16 passes), ``rms`` (RMS norm scaled by ``1 +
scale``, eps 1e-6), ``rope`` (rotary embedding, halves rotated) and
``row_mean_grads`` (the gradient of a mean over rows, one row at a
time). Here are what every family shares: the optimizer, AdamW with
global-norm clipping and a linear-warmup cosine schedule; the norms a
training cell compares; and serving logits, one unit of the stack at a
time.

``quant_bits=4`` is the control: the same mathematics with every matrix
product's operands (training: activations and weights, straight-through
in the backward pass; serving: weights) rounded to symmetric int4 over
blocks of 128 along the contraction axis. A sound program has to read
closer to the float32 reference than the control does.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 128


# ----------------------------------------------------------- control --
def int4_blocks(x, axis: int):
    """Symmetric int4 rounding over blocks of 128 along ``axis``."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    pad = (-n) % BLOCK
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xp.reshape(*xp.shape[:-1], -1, BLOCK)
    s = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 7.0
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(xb / s), -7, 7) * s
    q = q.reshape(xp.shape)[..., :n]
    return jnp.moveaxis(q, -1, axis)


def _ste(x, axis):
    return x + jax.lax.stop_gradient(int4_blocks(x, axis) - x)


def mm(x, w, bits: int = 0, quant_act: bool = True):
    """x (..., K) @ w (K, N) in float32 at highest precision."""
    if bits == 4:
        w = _ste(w, 0)
        if quant_act:
            x = _ste(x, -1)
    return jnp.matmul(x, w, precision=HIGHEST)


# ------------------------------------------------------------ layers --
def rms(x, scale, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """x (S, H, dh), pos (S,)."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def batch_grads(fam, cfg, params, batch, bits=0, rows=None):
    """(loss, grads) of the family's loss over the batch. ``rows`` keeps
    only the first ``rows`` rows (a planted fault)."""
    toks, labs = batch["tokens"], batch["labels"]
    if rows is not None:
        toks, labs = toks[:rows], labs[:rows]
    return fam.batch_grads(cfg, params, toks, labs, bits)


def row_mean_grads(row_loss, params, toks, labs):
    """(loss, grads) of the mean of ``row_loss(params, tokens, labels)``
    over the rows, accumulated one row at a time."""
    n = toks.shape[0]
    vg = jax.value_and_grad(row_loss)

    def acc(carry, row):
        loss, grads = carry
        l, g = vg(params, row[0], row[1])
        grads = jax.tree.map(lambda a, b: a + b / n, grads, g)
        return (loss + l / n, grads), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(acc, (jnp.float32(0), zero),
                                    (toks, labs))
    return loss, grads


# --------------------------------------------------------- optimizer --
def lr_at(opt: Dict[str, float], t: int) -> float:
    """Linear warm-up to ``peak_lr``, then cosine to ``final_lr``."""
    if t < opt["warmup_steps"]:
        return opt["peak_lr"] * t / max(opt["warmup_steps"], 1)
    prog = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    return opt["final_lr"] + 0.5 * (opt["peak_lr"] - opt["final_lr"]) * (
        1 + np.cos(np.pi * prog))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(tree)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(master, m, v, g, lr, scale, b1, b2, c1, c2, eps, wd):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * master
    return master - lr * delta, m, v


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """Norm of each leaf; a layer-stacked leaf gives one per layer."""
    out = {}
    for path, x in W.flatten(tree).items():
        x = x.astype(jnp.float32)
        if path.startswith("blocks/"):
            out[path] = np.asarray(jnp.sqrt(jnp.sum(
                jnp.square(x), axis=tuple(range(1, x.ndim)))))
        else:
            out[path] = np.asarray(jnp.sqrt(jnp.sum(jnp.square(x))))[None]
    return out


def change_norms(fam, cfg, seed: int, master) -> Dict[str, np.ndarray]:
    """Norms of ``master`` minus the seeded initial weights, each leaf
    made again from the seed (one per layer for a stacked leaf)."""
    key = W.base_key(seed)
    out = {}
    for path, x in W.flatten(master).items():
        @jax.jit
        def norm(x, key, path=path):
            d = x - W.make_leaf(fam, key, cfg, path).astype(jnp.float32)
            axes = tuple(range(1 if path.startswith("blocks/") else 0,
                               d.ndim))
            return jnp.sqrt(jnp.sum(jnp.square(d), axis=axes))

        out[path] = np.atleast_1d(np.asarray(norm(x, key)))
    return out


def train_readings(fam, cfg, job, seed: int, batches: Sequence,
                   bits: int = 0, rows: Optional[int] = None,
                   steps: int = 3):
    """The reference's side of a training cell's check: the loss of each
    of the first ``steps`` steps, the norms of the first step's gradient
    as the optimizer takes it (clipped), and the norms of each
    parameter's change after ``steps`` steps. Adam's moments wait on the
    host between steps, so that params, gradients and moments are never
    on the device together."""
    opt = job["optimizer"]
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    with jax.default_matmul_precision("highest"):
        master = jax.jit(lambda p: jax.tree.map(
            lambda x: x.astype(jnp.float32), p), donate_argnums=0)(
            W.make_params(fam, cfg, seed))
        grads_fn = jax.jit(functools.partial(batch_grads, fam, cfg,
                                             bits=bits, rows=rows))
        losses, g1 = [], None
        m_host: Dict[str, np.ndarray] = {}
        v_host: Dict[str, np.ndarray] = {}
        for t in range(1, steps + 1):
            loss, grads = grads_fn(master, batches[t - 1])
            losses.append(float(loss))
            gn = float(global_norm(grads))
            scale = min(1.0, opt["clip_norm"] / max(gn, 1e-9))
            if t == 1:
                g1 = leaf_norms(jax.tree.map(lambda g: g * scale, grads))
            lr = lr_at(opt, t)
            c1, c2 = 1 - b1 ** t, 1 - b2 ** t
            flat_m, flat_g = W.flatten(master), W.flatten(grads)
            del grads
            for path in list(flat_m):
                g = flat_g.pop(path)
                mm_ = (jnp.asarray(m_host[path]) if path in m_host
                       else jnp.zeros_like(g))
                vv = (jnp.asarray(v_host[path]) if path in v_host
                      else jnp.zeros_like(g))
                decay = wd if g.ndim - path.startswith("blocks/") >= 2 \
                    else 0.0
                new, mm_, vv = _adam_leaf(flat_m[path], mm_, vv, g, lr,
                                          scale, b1, b2, c1, c2, eps, decay)
                flat_m[path] = new
                if t < steps:
                    m_host[path], v_host[path] = (np.asarray(mm_),
                                                  np.asarray(vv))
                del g, mm_, vv
            master = W.nest(flat_m)
        change = change_norms(fam, cfg, seed, master)
    return {"losses": losses, "grad_norms": g1, "change_norms": change}


# ----------------------------------------------------------- serving --
def _layer_params(fam, cfg, key, l, bits):
    """Unit ``l``'s weights for the family's ``layer``, in float32, made
    from the seed; under ``bits=4`` each matrix rounded to int4 along its
    contraction axis."""
    flat = {path: W.make_leaf(fam, key, cfg, path, l)
            for path in fam.leaf_table(cfg) if path.startswith("blocks/")}
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     fam.stack(W.nest(flat)))
    if bits == 4:
        p = jax.tree.map(
            lambda v: int4_blocks(v, v.ndim - 2) if v.ndim >= 2 else v, p)
    return p


def serve_logits(fam, cfg, seed: int, seqs: List[np.ndarray],
                 picks: List[np.ndarray], bits: int = 0,
                 pad_to: int = 2048, head_chunk: int = 8192):
    """Logits (float32) of the reference at chosen positions.

    ``seqs[i]`` is a token sequence and ``picks[i]`` the positions whose
    next-token logits are wanted. The model runs layer by layer over all
    sequences (padded to ``pad_to``; causal attention keeps the padding
    out): the family's ``layer`` once for each unit that the ``blocks/``
    leaves stack, each unit's weights made from the seed as it is needed.
    Returns one (len(picks[i]), vocab) array per sequence."""
    key = W.base_key(seed)
    n = len(seqs)
    toks = np.zeros((n, pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : len(s)] = s
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: W.make_leaf(fam, k, cfg, "embed"))(key)
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(embed,
                                                           jnp.asarray(toks))
        del embed
        gen = jax.jit(functools.partial(_layer_params, fam, cfg, bits=bits))
        run = jax.jit(lambda p, x: jax.lax.map(
            lambda xi: fam.layer(cfg, p, xi, bits, quant_act=False), x))
        for l in range(W.stack_depth(fam, cfg)):
            x = run(gen(key, l), x)
        fin = W.make_leaf(fam, key, cfg, "final_norm/scale")
        rows = [np.asarray(p, np.int32) for p in picks]
        flat_i = np.concatenate([np.full(len(r), i) for i, r in
                                 enumerate(rows)])
        flat_p = np.concatenate(rows)
        h = rms(x[flat_i, flat_p], fin.astype(jnp.float32))
        del x
        head = jax.jit(lambda k: W.make_leaf(fam, k, cfg, "lm_head"))(key)

        @jax.jit
        def logits_of(h, head):
            def chunk(c):
                w = jax.lax.dynamic_slice_in_dim(head, c, head_chunk, 1)
                w = w.astype(jnp.float32)
                if bits == 4:
                    w = int4_blocks(w, 0)
                return jnp.matmul(h, w, precision=HIGHEST)

            starts = jnp.arange(0, head.shape[1], head_chunk)
            out = jax.lax.map(chunk, starts)  # (nc, T, chunk)
            return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)

        vp = head.shape[1]
        if vp % head_chunk:
            head = jnp.pad(head, ((0, 0), (0, head_chunk - vp % head_chunk)))
        logits = np.asarray(logits_of(h, head))[:, : cfg.vocab]
    out, at = [], 0
    for r in rows:
        out.append(logits[at: at + len(r)])
        at += len(r)
    return out
