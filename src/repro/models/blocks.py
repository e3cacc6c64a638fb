"""Transformer sublayers with MoR-quantized linears.

Every GEMM the paper quantizes (linear_qkv, linear_proj, fc1, fc2, and the
MoE expert FFNs) goes through :func:`repro.core.mor_dot`; routers, norms and
embeddings stay BF16, matching the paper's policy.

Named scopes mark the layers in the compiled program: ``norm``,
``residual``, ``attn/{qkv,rope,core,proj}`` and ``mlp/{fc1,act,fc2}``
here, ``mor_quant/<role>`` and ``gemm/<which>`` inside each linear
(``repro.core.linear``), so a device trace can be read per layer.

Block functions share the signature
    f(p, x, tok, policy, cfg, mode, cache, cur_index) -> (x, cache, stats)
where ``p``/``tok``/``cache`` are this layer's slices of the stacked
per-layer pytrees (see transformer.py).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import MoRDotPolicy, mor_dot
from repro.configs.base import ArchConfig

from .attention import decode_attention, flash_attention
from .common import (
    activation,
    apply_rope,
    constrain,
    glu_split,
    layer_norm,
    pick_chunk,
    rms_norm,
)

__all__ = [
    "norm", "residual", "attn_sublayer", "mlp_sublayer", "moe_sublayer",
    "dense_block", "moe_block",
]


@jax.named_scope("norm")
def norm(p_norm, x, cfg: ArchConfig):
    if cfg.norm == "ln":
        return layer_norm(x, p_norm["scale"], p_norm["bias"])
    return rms_norm(x, p_norm["scale"])


@jax.named_scope("residual")
def residual(x, *branches):
    """The residual stream plus each sublayer output, in order."""
    for b in branches:
        x = x + b
    return x


def _split_qkv(qkv, cfg: ArchConfig):
    B, S = qkv.shape[:2]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    return (
        q.reshape(B, S, hq, hd),
        k.reshape(B, S, hkv, hd),
        v.reshape(B, S, hkv, hd),
    )


@jax.named_scope("attn")
def attn_sublayer(
    p,
    xn,
    tok,
    policy: MoRDotPolicy,
    cfg: ArchConfig,
    mode: str,
    cache: Optional[Dict[str, jnp.ndarray]],
    cur_index,
    *,
    kind: str = "causal",
    prefix_len: int = 0,
    window: int = 0,
    use_rope: bool = True,
):
    """Self-attention with GQA + RoPE + KV cache. Returns (y, cache, stats)."""
    B, S, _ = xn.shape
    with jax.named_scope("qkv"):
        qkv, st_qkv = mor_dot(xn, p["wqkv"], tok["qkv"], policy)
        # Pin the SP->TP transition on the BF16 GEMM output: without
        # this GSPMD reshards f32 rope/quant intermediates (2x
        # collective bytes, Perf iteration 5).
        if mode != "decode" and S > 1:
            qkv = constrain(qkv, "batch", None, "model")
        q, k, v = _split_qkv(qkv, cfg)

    if mode == "decode":
        # cur_index is the position of the LAST query token: a scalar
        # shared across the batch, or a (B,) vector of per-slot
        # positions (the serving engine's mixed-length batches). The
        # incoming S tokens land at positions cur - (S-1) .. cur, each
        # row at its own offset, written *before* attention so a row's
        # own keys are always visible (docs/serving.md).
        cur = jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(cur_index, jnp.int32)), (B,)
        )
        pos = cur[:, None] - (S - 1) + jnp.arange(S, dtype=jnp.int32)[None]
        if use_rope:
            q, k = _rope(q, k, pos, cfg.rope_theta)
        out, new_cache = _decode_core(q, k, v, cache, cur, pos, window)
    else:
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        if use_rope:
            q, k = _rope(q, k, pos, cfg.rope_theta)
        with jax.named_scope("core"):
            out = flash_attention(
                q, k, v, kind=kind, prefix_len=prefix_len, window=window
            )
        new_cache = (
            {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
            if mode == "prefill"
            else None
        )

    with jax.named_scope("proj"):
        out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
        y, st_proj = mor_dot(out, p["wo"], tok["proj"], policy)
    return y, new_cache, {"qkv": st_qkv, "proj": st_proj}


@jax.named_scope("rope")
def _rope(q, k, pos, theta: float):
    return apply_rope(q, pos, theta), apply_rope(k, pos, theta)


@jax.named_scope("core")
def _decode_core(q, k, v, cache, cur, pos, window: int):
    """Writes the incoming keys and values into the cache at ``pos`` and
    attends against it. Returns (out, new cache)."""
    rows = jnp.arange(q.shape[0], dtype=jnp.int32)[:, None]
    upd = lambda buf, val: buf.at[rows, pos].set(val.astype(buf.dtype))
    mor_cache = "k_tags" in cache
    fp8_cache = (not mor_cache) and "k_scale" in cache
    if mor_cache:
        # MoR cache tier: per-(position, head) tag-select between
        # the fp8 arms + GAM scales (docs/numerics.md); decode
        # folds the scales into score space per tag.
        from .attention import quantize_kv_mor

        k_pay, k_t, k_s = quantize_kv_mor(k)
        v_pay, v_t, v_s = quantize_kv_mor(v)
        new_cache = {
            "k": upd(cache["k"], k_pay),
            "v": upd(cache["v"], v_pay),
            "k_tags": upd(cache["k_tags"], k_t),
            "v_tags": upd(cache["v_tags"], v_t),
            "k_scale": upd(cache["k_scale"], k_s),
            "v_scale": upd(cache["v_scale"], v_s),
        }
        out = decode_attention(
            q, new_cache["k"], new_cache["v"], cur,
            window=window, k_scale=new_cache["k_scale"],
            v_scale=new_cache["v_scale"],
            k_tags=new_cache["k_tags"], v_tags=new_cache["v_tags"],
        )
    elif fp8_cache:
        from .attention import quantize_kv

        k_pay, k_s = quantize_kv(k)
        v_pay, v_s = quantize_kv(v)
        new_cache = {
            "k": upd(cache["k"], k_pay),
            "v": upd(cache["v"], v_pay),
            "k_scale": upd(cache["k_scale"], k_s),
            "v_scale": upd(cache["v_scale"], v_s),
        }
        out = decode_attention(
            q, new_cache["k"], new_cache["v"], cur,
            window=window, k_scale=new_cache["k_scale"],
            v_scale=new_cache["v_scale"],
        )
    else:
        k_cache = upd(cache["k"], k)
        v_cache = upd(cache["v"], v)
        out = decode_attention(
            q, k_cache, v_cache, cur, window=window
        )
        new_cache = {"k": k_cache, "v": v_cache}
    return out, new_cache


@jax.named_scope("mlp")
def mlp_sublayer(p, xn, tok, policy: MoRDotPolicy, cfg: ArchConfig,
                 d_ff: Optional[int] = None):
    gated = cfg.act in ("swiglu", "geglu")
    act_fn = activation(cfg.act)
    with jax.named_scope("fc1"):
        h, st1 = mor_dot(xn, p["wi"], tok["fc1"], policy)
    with jax.named_scope("act"):
        h = glu_split(h, gated, act_fn)
    with jax.named_scope("fc2"):
        y, st2 = mor_dot(h, p["wo"], tok["fc2"], policy)
    return y, {"fc1": st1, "fc2": st2}


# -------------------------------------------------------------------- MoE --
def moe_sublayer(p, xn, tok, policy: MoRDotPolicy, cfg: ArchConfig):
    """Capacity-based MoE with per-(example, chunk) grouping.

    Tokens are chunked along the sequence axis (scan => bounded transients);
    each (example, chunk) group dispatches into an (E, C, d) buffer via
    one-hot einsums (GSPMD-friendly: group dim rides the data axis, expert
    dim rides the model axis). Expert FFN GEMMs are MoR-quantized per
    expert via vmap(mor_dot).
    """
    B, S, d = xn.shape
    E, K = cfg.n_experts, cfg.top_k
    gated = cfg.act in ("swiglu", "geglu")
    act_fn = activation(cfg.act)

    s_sub = pick_chunk(S, 256)
    n_sub = S // s_sub
    C = max(1, int(K * s_sub / E * cfg.capacity_factor))

    w1, w2, router = p["w1"], p["w2"], p["router"]
    tok_w1, tok_w2 = tok["w1"], tok["w2"]

    xc = xn.reshape(B, n_sub, s_sub, d)
    xc = jnp.moveaxis(xc, 1, 0)  # (n_sub, B, s_sub, d)

    def chunk_fn(_, x_c):
        # x_c: (B, t, d)
        logits = jnp.einsum(
            "btd,de->bte", x_c, router, preferred_element_type=jnp.float32
        )
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, K)  # (B, t, K)
        vals = vals / jnp.maximum(
            jnp.sum(vals, -1, keepdims=True), 1e-9
        )
        # Flatten the K token-copies.
        t = x_c.shape[1]
        ids = idx.reshape(B, t * K)
        gate = vals.reshape(B, t * K)
        oh = jax.nn.one_hot(ids, E, dtype=jnp.float32)  # (B, tK, E)
        pos = jnp.cumsum(oh, axis=1) - oh
        slot = jnp.sum(pos * oh, axis=-1)  # (B, tK)
        keep = (slot < C).astype(jnp.float32)
        slot_oh = jax.nn.one_hot(
            jnp.minimum(slot, C - 1).astype(jnp.int32), C, dtype=jnp.float32
        ) * keep[..., None]
        x_rep = jnp.repeat(
            x_c.astype(jnp.float32), K, axis=1
        )  # (B, tK, d)

        xbuf = jnp.einsum("bse,bsc,bsd->ebcd", oh, slot_oh, x_rep)
        xbuf = constrain(xbuf, "model", "batch", None, None)
        xbuf = xbuf.astype(xn.dtype)

        h, st1 = jax.vmap(
            lambda a, w, tk: mor_dot(a, w, tk, policy)
        )(xbuf, w1, tok_w1)
        h = glu_split(h, gated, act_fn)
        ybuf, st2 = jax.vmap(
            lambda a, w, tk: mor_dot(a, w, tk, policy)
        )(h, w2, tok_w2)

        y = jnp.einsum(
            "bse,bsc,bs,ebcd->bsd",
            oh, slot_oh, gate, ybuf.astype(jnp.float32),
        )
        y = y.reshape(B, t, K, d).sum(axis=2)

        # Load-balance aux loss (Switch-style) + drop fraction.
        me = jnp.mean(oh.reshape(B, t, K, E).sum(2), axis=(0, 1))
        ce = jnp.mean(probs, axis=(0, 1))
        aux = jnp.sum(me * ce) * E
        dropped = 1.0 - jnp.mean(keep)
        return None, (y.astype(xn.dtype), st1, st2, aux, dropped)

    _, (ys, st1, st2, aux, dropped) = jax.lax.scan(chunk_fn, None, xc)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, d)
    stats = {
        "w1": jnp.mean(st1, axis=0),  # (E, 2, W) averaged over chunks
        "w2": jnp.mean(st2, axis=0),
        "aux_loss": jnp.mean(aux),
        "dropped": jnp.mean(dropped),
    }
    return y, stats


# ------------------------------------------------------------ full blocks --
def dense_block(p, x, tok, policy, cfg, mode, cache, cur_index, **attn_kw):
    xn = norm(p["ln1"], x, cfg)
    a, new_cache, st_a = attn_sublayer(
        p, xn, tok, policy, cfg, mode, cache, cur_index, **attn_kw
    )
    x = residual(x, a)
    xn2 = norm(p["ln2"], x, cfg)
    m, st_m = mlp_sublayer(p["mlp"], xn2, tok, policy, cfg)
    x = residual(x, m)
    return x, new_cache, {**st_a, **st_m}


def moe_block(p, x, tok, policy, cfg, mode, cache, cur_index, **attn_kw):
    xn = norm(p["ln1"], x, cfg)
    a, new_cache, st_a = attn_sublayer(
        p, xn, tok, policy, cfg, mode, cache, cur_index, **attn_kw
    )
    x = residual(x, a)
    xn2 = norm(p["ln2"], x, cfg)
    m, st_m = moe_sublayer(p["moe"], xn2, tok, policy, cfg)
    x = residual(x, m)
    return x, new_cache, {**st_a, **st_m}
