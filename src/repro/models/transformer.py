"""Unified model assembly for all assigned architectures.

One forward covers: dense/MoE decoder LMs, Hymba hybrids, xLSTM stacks,
Whisper enc-dec, and PaliGemma prefix-LM -- assembled from the block types
in ``cfg.unit`` and scanned over layers (homogeneous stacks compile to one
HLO body regardless of depth; xLSTM's (mlstm, slstm) unit scans pairs).

Modes: 'train' (full-seq causal/prefix forward), 'prefill' (forward +
emit caches), 'decode' (new tokens against caches at per-row positions:
one token per step, or an S-token chunk for chunked prefill).

Vocab handling: embeddings are padded to a multiple of 128 so the vocab
axis shards evenly at TP=16; padded logit columns are masked to -inf
before softmax (Megatron-style), so quality is unaffected.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import N_BWD_EVENTS, STATS_WIDTH, MoRDotPolicy
from repro.core.linear import mor_dot
from repro.kernels import ops as kops

from . import blocks as B
from . import recurrent as R
from .common import constrain, sinusoidal_positions

__all__ = [
    "init_params", "make_tokens", "cache_specs", "forward", "padded_vocab",
]


def padded_vocab(cfg: ArchConfig) -> int:
    return -(-cfg.vocab // 128) * 128


# ================================================================== init ==
def _norm_p(key, d, cfg, out_scale=False):
    p = {"scale": jnp.zeros((d,), jnp.float32)}
    if cfg.norm == "ln":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def _lin(key, shape, std=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16
    )


def _ffin(cfg: ArchConfig, f: int) -> int:
    return 2 * f if cfg.act in ("swiglu", "geglu") else f


def _attn_params(key, cfg: ArchConfig):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    k1, k2 = jax.random.split(key)
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "wqkv": _lin(k1, (d, (hq + 2 * hkv) * hd)),
        "wo": _lin(k2, (hq * hd, d), std=depth_std),
    }


def _mlp_params(key, cfg: ArchConfig, d=None, f=None):
    d = d or cfg.d_model
    f = f or cfg.d_ff
    k1, k2 = jax.random.split(key)
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "wi": _lin(k1, (d, _ffin(cfg, f))),
        "wo": _lin(k2, (f, d), std=depth_std),
    }


def _dense_layer(key, cfg: ArchConfig):
    ka, km, kn = jax.random.split(key, 3)
    p = _attn_params(ka, cfg)
    p["mlp"] = _mlp_params(km, cfg)
    p["ln1"] = _norm_p(kn, cfg.d_model, cfg)
    p["ln2"] = _norm_p(kn, cfg.d_model, cfg)
    return p


def _moe_layer(key, cfg: ArchConfig):
    ka, kr, k1, k2, kn = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    p = _attn_params(ka, cfg)
    p["moe"] = {
        "router": (jax.random.normal(kr, (d, E), jnp.float32) * 0.02).astype(
            jnp.float32
        ),
        "w1": _lin(k1, (E, d, _ffin(cfg, f))),
        "w2": _lin(k2, (E, f, d), std=depth_std),
    }
    p["ln1"] = _norm_p(kn, d, cfg)
    p["ln2"] = _norm_p(kn, d, cfg)
    return p


def _mamba_params(key, cfg: ArchConfig):
    di, N, cw = cfg.mamba_d_inner, cfg.ssm_state, cfg.conv_width
    d = cfg.d_model
    dt_rank = max(1, d // 16)
    keys = jax.random.split(key, 6)
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "w_in": _lin(keys[0], (d, 2 * di)),
        "conv_w": (jax.random.normal(keys[1], (cw, di)) * 0.02).astype(
            jnp.float32
        ),
        "w_bc": _lin(keys[2], (di, 2 * N)).astype(jnp.float32),
        "w_dt_down": _lin(keys[3], (di, dt_rank)).astype(jnp.float32),
        "w_dt_up": _lin(keys[4], (dt_rank, di)).astype(jnp.float32),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "A_log": jnp.log(
            jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))
        ),
        "D": jnp.ones((di,), jnp.float32),
        "w_out": _lin(keys[5], (di, d), std=depth_std),
    }


def _hymba_layer(key, cfg: ArchConfig):
    ka, ks, km, kn = jax.random.split(key, 4)
    p = _attn_params(ka, cfg)
    p["ssm"] = _mamba_params(ks, cfg)
    p["mlp"] = _mlp_params(km, cfg)
    p["ln1"] = _norm_p(kn, cfg.d_model, cfg)
    p["ln2"] = _norm_p(kn, cfg.d_model, cfg)
    return p


def _mlstm_layer(key, cfg: ArchConfig):
    d = cfg.d_model
    di = 2 * d
    H = cfg.n_heads
    keys = jax.random.split(key, 5)
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "ln1": _norm_p(keys[0], d, cfg),
        "w_up": _lin(keys[0], (d, 2 * di)),
        "w_qkv": _lin(keys[1], (di, 3 * di)),
        "w_gate": _lin(keys[2], (di, 2 * H)),
        "gate_bias": jnp.concatenate(
            [jnp.zeros((H,)), jnp.full((H,), 3.0)]
        ).astype(jnp.float32),
        "out_norm": jnp.zeros((di,), jnp.float32),
        "w_down": _lin(keys[3], (di, d), std=depth_std),
    }


def _slstm_layer(key, cfg: ArchConfig):
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    ff = -(-int(d * 4 / 3) // 64) * 64
    keys = jax.random.split(key, 4)
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "ln1": _norm_p(keys[0], d, cfg),
        "w_x": _lin(keys[0], (d, 4 * d)),
        "r": _lin(keys[1], (H, dh, 4 * dh)),
        "out_norm": jnp.zeros((d,), jnp.float32),
        "w_ff1": _lin(keys[2], (d, 2 * ff)),
        "w_ff2": _lin(keys[3], (ff, d), std=depth_std),
    }


def _wdec_layer(key, cfg: ArchConfig):
    """Whisper decoder layer: self-attn + cross-attn + mlp."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    keys = jax.random.split(key, 6)
    depth_std = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    p = _attn_params(keys[0], cfg)
    p["xwq"] = _lin(keys[1], (d, hq * hd))
    p["xwkv"] = _lin(keys[2], (d, 2 * hkv * hd))
    p["xwo"] = _lin(keys[3], (hq * hd, d), std=depth_std)
    p["mlp"] = _mlp_params(keys[4], cfg)
    p["ln1"] = _norm_p(keys[5], d, cfg)
    p["lnx"] = _norm_p(keys[5], d, cfg)
    p["ln2"] = _norm_p(keys[5], d, cfg)
    return p


_LAYER_INIT = {
    "dense": _dense_layer,
    "moe": _moe_layer,
    "hymba": _hymba_layer,
    "mlstm": _mlstm_layer,
    "slstm": _slstm_layer,
    "wdec": _wdec_layer,
}


def init_params(cfg: ArchConfig, key) -> Dict[str, Any]:
    kE, kH, kB, kEnc = jax.random.split(key, 4)
    Vp = padded_vocab(cfg)
    embed = jax.random.normal(kE, (Vp, cfg.d_model), jnp.float32) * 0.02
    embed = embed.at[cfg.vocab :].set(0.0)
    params: Dict[str, Any] = {
        "embed": embed.astype(jnp.bfloat16),
        "final_norm": _norm_p(kE, cfg.d_model, cfg),
    }
    if not cfg.tie_embed:
        params["lm_head"] = _lin(kH, (cfg.d_model, Vp))

    unit = _unit_types(cfg)
    params["blocks"] = {}
    for t in unit:
        # crc32, not hash(): str hashing is salted per process
        # (PYTHONHASHSEED), which made init_params draw *different
        # parameters in every process* -- differential tests comparing
        # runs across processes, and anything pinning draw-dependent
        # values, were silently seeded by the interpreter.
        keys = jax.random.split(
            jax.random.fold_in(kB, zlib.crc32(t.encode()) % 2**31),
            cfg.n_units,
        )
        params["blocks"][t] = jax.vmap(
            lambda k: _LAYER_INIT[t](k, cfg)
        )(keys)

    if cfg.family == "audio":  # whisper encoder stack
        keys = jax.random.split(kEnc, cfg.enc_layers)
        params["enc"] = {
            "blocks": jax.vmap(lambda k: _dense_layer(k, cfg))(keys),
            "final_norm": _norm_p(kEnc, cfg.d_model, cfg),
        }
    return params


def _unit_types(cfg: ArchConfig) -> Tuple[str, ...]:
    if cfg.family == "audio":
        return ("wdec",)
    return cfg.unit


# ================================================================ tokens ==
def _tok():
    return jnp.zeros((N_BWD_EVENTS, STATS_WIDTH), jnp.float32)


def _layer_tokens(t: str, cfg: ArchConfig):
    if t == "dense":
        names = ["qkv", "proj", "fc1", "fc2"]
    elif t == "moe":
        return {
            "qkv": _tok(),
            "proj": _tok(),
            "w1": jnp.zeros(
                (cfg.n_experts, N_BWD_EVENTS, STATS_WIDTH), jnp.float32
            ),
            "w2": jnp.zeros(
                (cfg.n_experts, N_BWD_EVENTS, STATS_WIDTH), jnp.float32
            ),
        }
    elif t == "hymba":
        names = ["qkv", "proj", "ssm_in", "ssm_out", "fc1", "fc2"]
    elif t == "mlstm":
        names = ["up", "qkv", "down"]
    elif t == "slstm":
        names = ["wx", "ff1", "ff2"]
    elif t == "wdec":
        names = ["qkv", "proj", "xq", "xkv", "xproj", "fc1", "fc2"]
    else:
        raise ValueError(t)
    return {n: _tok() for n in names}


def make_tokens(cfg: ArchConfig):
    """Zero-valued bwd-stat tokens; grads w.r.t. these carry the backward
    quantization stats out of the train step (see repro.core.linear)."""
    stack = lambda tree: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_units, *x.shape)), tree
    )
    toks = {
        "blocks": {
            t: stack(_layer_tokens(t, cfg)) for t in _unit_types(cfg)
        }
    }
    if cfg.family == "audio":
        enc = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.enc_layers, *x.shape)),
            _layer_tokens("dense", cfg),
        )
        toks["enc"] = enc
    return toks


# ================================================================= cache ==
def _layer_cache_spec(t: str, cfg: ArchConfig, b: int, s: int,
                      kv_fp8: bool = False, kv_mor: bool = False):
    hkv, hd = cfg.n_kv, cfg.head_dim
    if kv_fp8 and kv_mor:
        raise ValueError("kv_fp8 and kv_mor are mutually exclusive")
    if kv_mor:
        # MoR cache tier (docs/numerics.md): uint8 payload lanes with
        # per-(position, head) representation tags + GAM scales --
        # per-block E4M3/E5M2 selection hot, NVFP4 sub4 when pages go
        # cold (tags/scales are the MixedOperand lanes of a page).
        kv = {
            "k": jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.uint8),
            "v": jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.uint8),
            "k_tags": jax.ShapeDtypeStruct((b, s, hkv), jnp.uint8),
            "v_tags": jax.ShapeDtypeStruct((b, s, hkv), jnp.uint8),
            "k_scale": jax.ShapeDtypeStruct((b, s, hkv), jnp.float32),
            "v_scale": jax.ShapeDtypeStruct((b, s, hkv), jnp.float32),
        }
    elif kv_fp8:
        # Beyond-paper: E4M3 payload + per-(position, head) f32 scales
        # (halves the decode cache; see models.attention.decode_attention).
        kv = {
            "k": jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.float8_e4m3fn),
            "v": jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.float8_e4m3fn),
            "k_scale": jax.ShapeDtypeStruct((b, s, hkv), jnp.float32),
            "v_scale": jax.ShapeDtypeStruct((b, s, hkv), jnp.float32),
        }
    else:
        kv = {
            "k": jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.bfloat16),
            "v": jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.bfloat16),
        }
    if t in ("dense", "moe"):
        return kv
    if t == "hymba":
        di, cw = cfg.mamba_d_inner, cfg.conv_width
        return {
            **kv,
            "ssm": {
                "h": jax.ShapeDtypeStruct(
                    (b, di, cfg.ssm_state), jnp.float32
                ),
                "conv": jax.ShapeDtypeStruct((b, cw - 1, di), jnp.bfloat16),
            },
        }
    if t == "mlstm":
        di = 2 * cfg.d_model
        H = cfg.n_heads
        dh = di // H
        return {
            "C": jax.ShapeDtypeStruct((b, H, dh, dh), jnp.float32),
            "n": jax.ShapeDtypeStruct((b, H, dh), jnp.float32),
            "m": jax.ShapeDtypeStruct((b, H), jnp.float32),
        }
    if t == "slstm":
        d = cfg.d_model
        return {
            n: jax.ShapeDtypeStruct((b, d), jnp.float32)
            for n in ("h", "c", "n", "m")
        }
    if t == "wdec":
        return {
            **kv,
            "xk": jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, hkv, hd), jnp.bfloat16
            ),
            "xv": jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, hkv, hd), jnp.bfloat16
            ),
        }
    raise ValueError(t)


def cache_specs(cfg: ArchConfig, batch: int, seq: int,
                kv_fp8: bool = False, kv_mor: bool = False):
    """ShapeDtypeStruct pytree for the decode cache (stacked over units)."""
    stack = lambda spec: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((cfg.n_units, *x.shape), x.dtype), spec
    )
    return {
        t: stack(_layer_cache_spec(t, cfg, batch, seq, kv_fp8, kv_mor))
        for t in _unit_types(cfg)
    }


def init_cache(cfg: ArchConfig, batch: int, seq: int, kv_fp8: bool = False,
               kv_mor: bool = False):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        cache_specs(cfg, batch, seq, kv_fp8, kv_mor),
    )


# =============================================================== forward ==
def _block_fn(t: str):
    if t == "dense":
        return B.dense_block
    if t == "moe":
        return B.moe_block
    if t == "hymba":
        return _hymba_block
    if t == "mlstm":
        return _mlstm_block
    if t == "slstm":
        return _slstm_block
    if t == "wdec":
        return _wdec_block
    raise ValueError(t)


def _hymba_block(p, x, tok, policy, cfg, mode, cache, cur_index, **attn_kw):
    xn = B.norm(p["ln1"], x, cfg)
    kv_cache = (
        {"k": cache["k"], "v": cache["v"]} if cache is not None else None
    )
    a, new_kv, st_a = B.attn_sublayer(
        p, xn, tok, policy, cfg, mode, kv_cache, cur_index, **attn_kw
    )
    s, new_ssm, st_s = R.mamba_mix(
        p["ssm"], xn, tok, policy, cfg, mode,
        cache["ssm"] if cache is not None else None,
    )
    x = B.residual(x, a, s)
    xn2 = B.norm(p["ln2"], x, cfg)
    m, st_m = B.mlp_sublayer(p["mlp"], xn2, tok, policy, cfg)
    x = B.residual(x, m)
    new_cache = (
        {**new_kv, "ssm": new_ssm} if new_kv is not None else None
    )
    return x, new_cache, {**st_a, **st_s, **st_m}


def _mlstm_block(p, x, tok, policy, cfg, mode, cache, cur_index, **attn_kw):
    xn = B.norm(p["ln1"], x, cfg)
    y, new_cache, st = R.mlstm_mix(p, xn, tok, policy, cfg, mode, cache)
    return B.residual(x, y), new_cache, st


def _slstm_block(p, x, tok, policy, cfg, mode, cache, cur_index, **attn_kw):
    xn = B.norm(p["ln1"], x, cfg)
    y, new_cache, st = R.slstm_mix(p, xn, tok, policy, cfg, mode, cache)
    return B.residual(x, y), new_cache, st


def _wdec_block(p, x, tok, policy, cfg, mode, cache, cur_index,
                enc_out=None, **attn_kw):
    # Self-attention (causal, sinusoidal positions -> no rope).
    xn = B.norm(p["ln1"], x, cfg)
    kv_cache = (
        {"k": cache["k"], "v": cache["v"]} if cache is not None else None
    )
    a, new_kv, st_a = B.attn_sublayer(
        p, xn, tok, policy, cfg, mode, kv_cache, cur_index,
        kind="causal", use_rope=False,
    )
    x = B.residual(x, a)
    # Cross-attention against encoder output (cached at prefill).
    xq = B.norm(p["lnx"], x, cfg)
    Bsz, S, _ = xq.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q, st_xq = mor_dot(xq, p["xwq"], tok["xq"], policy)
    q = q.reshape(Bsz, S, hq, hd)
    if mode == "decode":
        xk, xv = cache["xk"], cache["xv"]
        st_xkv = jnp.zeros_like(st_xq)
    else:
        kvx, st_xkv = mor_dot(enc_out, p["xwkv"], tok["xkv"], policy)
        xk, xv = jnp.split(kvx, 2, axis=-1)
        xk = xk.reshape(Bsz, -1, hkv, hd)
        xv = xv.reshape(Bsz, -1, hkv, hd)
    from .attention import flash_attention

    xo = flash_attention(q, xk, xv, kind="full")
    xo = xo.reshape(Bsz, S, hq * hd)
    xa, st_xo = mor_dot(xo, p["xwo"], tok["xproj"], policy)
    x = B.residual(x, xa)
    xn2 = B.norm(p["ln2"], x, cfg)
    m, st_m = B.mlp_sublayer(p["mlp"], xn2, tok, policy, cfg)
    x = B.residual(x, m)
    new_cache = None
    if new_kv is not None:
        new_cache = {
            **new_kv,
            "xk": xk.astype(jnp.bfloat16),
            "xv": xv.astype(jnp.bfloat16),
        }
    return x, new_cache, {
        **st_a, "xq": st_xq, "xkv": st_xkv, "xproj": st_xo, **st_m
    }


def _run_stack(
    types, cfg, policy, block_params, block_tokens, x, mode, cache,
    cur_index, attn_kw, enc_out=None, remat=True,
):
    """Scan ``x`` through a stacked block group. Returns (x, caches, stats)."""

    def body(x, xs):
        p_all, tok_all, cache_all = xs
        new_caches = {}
        stats = {}
        # Sequence parallelism (Megatron SP): the residual stream lives
        # sharded over ('model' x seq) between layers; GSPMD inserts the
        # all-gather on the *quantized* qkv/fc1 inputs and reduce-scatters
        # after proj/fc2. Cuts checkpointed activations and norm-backward
        # traffic by the TP degree (Perf iteration 3).
        if mode != "decode" and x.shape[1] > 1:
            x = constrain(x, "batch", "model", None)
        for t in types:
            fn = _block_fn(t)
            kw = dict(attn_kw)
            if t == "wdec":
                kw["enc_out"] = enc_out
            x, nc, st = fn(
                p_all[t], x, tok_all[t], policy, cfg, mode,
                None if cache_all is None else cache_all[t],
                cur_index, **kw,
            )
            new_caches[t] = nc
            stats[t] = st
        return x, (new_caches, stats)

    if remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (block_params, block_tokens, cache)
    # ``stack``: the scan's own work (each layer's slice of the stacked
    # weights, its outputs and gradients written back), apart from the
    # layers' scopes inside the body.
    with jax.named_scope("stack"):
        x, (new_caches, stats) = jax.lax.scan(body, x, xs)
    if mode == "train":
        new_caches = None
    return x, new_caches, stats


def _sinusoidal_at(index, d_model: int) -> jnp.ndarray:
    """Sinusoidal position embedding at a (possibly traced) position."""
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)
    ang = index.astype(jnp.float32) / (10000.0 ** (dim / d_model))
    out = jnp.zeros((d_model,), jnp.float32)
    out = out.at[0::2].set(jnp.sin(ang))
    out = out.at[1::2].set(jnp.cos(ang))
    return out


def forward(
    cfg: ArchConfig,
    policy: MoRDotPolicy,
    params,
    tokens,
    batch: Dict[str, jnp.ndarray],
    *,
    mode: str = "train",
    cache=None,
    cur_index=None,
    remat: bool = True,
):
    """Returns (logits, new_cache, stats).

    batch keys: 'tokens' (B,S) [train/prefill], 'token' (B,S) [decode:
    S == 1 for plain decode, S > 1 for a prefill chunk against the
    cache], plus 'frames' (audio) / 'patches' (vlm) stubs. In decode
    mode ``cur_index`` -- scalar or (B,) vector -- is the position of
    the last incoming token per batch row (docs/serving.md).
    """
    embed = params["embed"]

    ids = batch["token"] if mode == "decode" else batch["tokens"]
    with jax.named_scope("embed"):
        x = embed[ids]  # gather, (B, S, d)
        if cfg.family in ("dense", "vlm") and cfg.tie_embed:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)  # gemma-style

    attn_kw: Dict[str, Any] = {"kind": "causal"}
    enc_out = None
    all_stats: Dict[str, Any] = {}

    if cfg.family == "vlm" and mode != "decode":
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        attn_kw = {"kind": "prefix", "prefix_len": cfg.img_tokens}
    if cfg.family == "hybrid" and cfg.window:
        # Hymba: sliding-window attention + global SSM state
        # (docs/architecture.md).
        attn_kw = {"kind": "sliding", "window": cfg.window}
    if cfg.family == "audio":
        attn_kw = {"use_rope": False, "kind": "causal"}
        if mode == "decode":
            # cur_index: () or (B,) position of the last incoming token
            # (same convention as decode_attention); the S incoming
            # tokens sit at cur - (S-1) .. cur per batch row.
            S = x.shape[1]
            cur = jnp.atleast_1d(jnp.asarray(cur_index, jnp.int32))
            posn = cur[:, None] - (S - 1) + jnp.arange(S)  # (b, S)
            pos = jax.vmap(jax.vmap(
                lambda i: _sinusoidal_at(i, cfg.d_model)
            ))(posn)  # (b, S, d)
        else:
            pos = sinusoidal_positions(x.shape[1], cfg.d_model)[None]
        x = x + pos.astype(x.dtype)
        if mode != "decode":
            frames = batch["frames"].astype(x.dtype)
            ep = sinusoidal_positions(frames.shape[1], cfg.d_model)
            e = frames + ep[None].astype(x.dtype)
            e, _, enc_stats = _run_stack(
                ("dense",), cfg, policy, {"dense": params["enc"]["blocks"]},
                {"dense": tokens["enc"]}, e, "train", None, None,
                {"kind": "full", "use_rope": False}, remat=remat,
            )
            enc_out = B.norm(params["enc"]["final_norm"], e, cfg)
            all_stats["enc"] = enc_stats

    x = constrain(x, "batch", None, None)
    x, new_cache, stats = _run_stack(
        _unit_types(cfg), cfg, policy, params["blocks"], tokens["blocks"],
        x, mode, cache, cur_index, attn_kw, enc_out=enc_out, remat=remat,
    )
    all_stats["blocks"] = stats

    x = B.norm(params["final_norm"], x, cfg)
    with jax.named_scope("head"):
        logits = _head(cfg, policy, params, x)
    return logits, new_cache, all_stats


def _head(cfg: ArchConfig, policy: MoRDotPolicy, params, x):
    """Logits over the padded vocabulary, padded columns masked; the
    product itself is scoped ``head/gemm``."""
    head = params["embed"].T if cfg.tie_embed else params["lm_head"]
    with jax.named_scope("gemm"):
        if hasattr(head, "as_mixed_operand"):
            # Real-quantized serving head (serve.quantized.QTensor): feed
            # the stored per-block payloads straight into the mixed GEMM.
            mo = head.as_mixed_operand()  # (Vp, d) quantization view
            bsz, seq = x.shape[0], x.shape[1]
            logits = kops.mixed_dot(
                x.reshape(-1, x.shape[-1]), mo,
                out_dtype=jnp.float32, backend=policy.weight.backend,
            ).reshape(bsz, seq, head.shape[1])
        else:
            logits = jnp.einsum(
                "bsd,dv->bsv", x, head, preferred_element_type=jnp.float32
            )
    # Mask padded vocab columns (Megatron-style; no resharding slice).
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, padded_vocab(cfg)), 2)
    logits = jnp.where(col < cfg.vocab, logits, -1e30)
    return constrain(logits, "batch", None, "model")
