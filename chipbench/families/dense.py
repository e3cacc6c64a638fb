"""The dense decoder: its configuration keys, its seeded leaves, its
float32 equations and its operation counts.

A block is pre-norm attention then a pre-norm MLP, each added to the
residual: RMS norm scaled by ``1 + scale`` (eps 1e-6), rotary embedding
over the whole head (halves rotated, theta from the configuration),
causal grouped-query attention in float32 with scale ``head_dim **
-0.5``, and a squared-ReLU MLP; a final norm and an untied output head
follow the stack. Under ``bits=4`` every matrix product's operands go
through ``reference.mm``'s int4 control.

A family file gives ``arch_config``, ``leaf_table``, ``stack``,
``layer`` (one unit of the stack: here one dense block), ``batch_grads``,
``train_step_flops`` and ``decode_step_flops``; ``spec.family_of`` loads
it by the configuration's ``family``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference import HIGHEST, mm, rms, rope, row_mean_grads

# Source keys of a dense decoder's config.json -> the repository's
# ArchConfig fields. A configuration file states its model in the
# source's own keys; this table is the only place that maps them.
_DENSE_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "hidden_act": "act",
    "rope_theta": "rope_theta",
}


def arch_config(conf):
    """The repository's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig

    kw = {field: conf[key] for key, field in _DENSE_KEYS.items()}
    return ArchConfig(
        name=conf["name"], family="dense", unit=("dense",),
        norm="rms", dtype="bfloat16", **kw,
    )


def leaf_table(cfg):
    """``path -> (shape, dtype, std)`` of a dense decoder's params; std 0
    marks a zero-initialised norm scale. Paths follow the tree of
    ``repro.models.init_params``."""
    d, L, f = cfg.d_model, cfg.n_layers, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    vp = W.padded_vocab(cfg)
    out_std = W.STD / max(1.0, (2 * L) ** 0.5)
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((vp, d), bf, W.STD),
        "final_norm/scale": ((d,), f32, 0.0),
        "lm_head": ((d, vp), bf, W.STD),
        "blocks/dense/wqkv": ((L, d, (hq + 2 * hkv) * hd), bf, W.STD),
        "blocks/dense/wo": ((L, hq * hd, d), bf, out_std),
        "blocks/dense/mlp/wi": ((L, d, f), bf, W.STD),
        "blocks/dense/mlp/wo": ((L, f, d), bf, out_std),
        "blocks/dense/ln1/scale": ((L, d), f32, 0.0),
        "blocks/dense/ln2/scale": ((L, d), f32, 0.0),
    }


# ------------------------------------------------------------- model --
def layer(cfg, p, x, bits=0, quant_act=True):
    """One dense block over one sequence x (S, d); p holds float32
    matrices wqkv, wo, wi, wo2 and norm scales ln1, ln2."""
    S = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    pos = jnp.arange(S)
    xn = rms(x, p["ln1"])
    qkv = mm(xn, p["wqkv"], bits, quant_act)
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    q = rope(q.reshape(S, hq, hd), pos, cfg.rope_theta)
    k = rope(k.reshape(S, hkv, hd), pos, cfg.rope_theta)
    v = v.reshape(S, hkv, hd)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)
    x = x + mm(o.reshape(S, hq * hd), p["wo"], bits, quant_act)
    xn = rms(x, p["ln2"])
    h = jnp.square(jax.nn.relu(mm(xn, p["wi"], bits, quant_act)))
    return x + mm(h, p["wo2"], bits, quant_act)


def stack(params):
    """``layer``'s weights from the program's tree: layer-stacked for the
    whole params, one layer's for a tree of one layer's leaves."""
    b = params["blocks"]["dense"]
    return {"wqkv": b["wqkv"], "wo": b["wo"], "wi": b["mlp"]["wi"],
            "wo2": b["mlp"]["wo"], "ln1": b["ln1"]["scale"],
            "ln2": b["ln2"]["scale"]}


def row_loss(cfg, params, tokens, labels, bits=0):
    """Mean cross-entropy of one sequence; params float32, program tree."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(x, p):
        return layer(cfg, p, x, bits), None

    x, _ = jax.lax.scan(body, x, stack(params))
    x = rms(x, params["final_norm"]["scale"])
    logits = mm(x, params["lm_head"], bits)[:, : cfg.vocab]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def batch_grads(cfg, params, tokens, labels, bits=0):
    """(loss, grads) of the batch's mean ``row_loss``, one row at a time.
    A family whose loss has a term over the whole batch (a router's load
    balance over the batch's tokens) gives its own."""
    return row_mean_grads(functools.partial(row_loss, cfg, bits=bits),
                          params, tokens, labels)


# ------------------------------------------------------------ counts --
def matmul_params_per_layer(cfg) -> int:
    """Weights of one dense block's four matrix products."""
    d, f = cfg.d_model, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    return d * (hq + 2 * hkv) * hd + hq * hd * d + 2 * d * f


def matmul_params(cfg) -> int:
    """All matrix-product weights: the blocks and the output head. The
    embedding is a lookup, not a product, and is not counted."""
    return (cfg.n_layers * matmul_params_per_layer(cfg)
            + cfg.d_model * cfg.vocab)


def causal_attention_flops(cfg, seq: int) -> float:
    """Forward operations of causal attention over one sequence, all
    layers: scores and the weighted sum over the lower triangle."""
    return cfg.n_layers * 2.0 * seq * seq * cfg.n_heads * cfg.head_dim


def train_step_flops(cfg, batch: int, seq: int) -> float:
    """Forward and backward operations of one step (3x forward);
    recomputation for remat is not counted."""
    fwd = 2.0 * matmul_params(cfg) * batch * seq \
        + batch * causal_attention_flops(cfg, seq)
    return 3.0 * fwd


def decode_step_flops(cfg, cache_lens: Sequence[int]) -> float:
    """Operations of one decode step over the slots that decode: each
    reads its own cache of ``len`` positions (the new one included)."""
    n = len(cache_lens)
    attn = sum(4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * l
               for l in cache_lens)
    return 2.0 * matmul_params(cfg) * n + attn
