"""Transformer sublayers with MoR-quantized linears.

Every GEMM the paper quantizes (linear_qkv, linear_proj, fc1, fc2, and the
MoE expert FFNs) goes through :func:`repro.core.mor_dot`; routers, norms and
embeddings stay BF16, matching the paper's policy.

Named scopes mark the layers in the compiled program: ``norm``,
``residual``, ``attn/{qkv,rope,core,proj}`` (latent attention adds
``attn/latent``), ``mlp/{fc1,act,fc2}`` and, for an MoE layer,
``mlp/moe/{route,dispatch,experts,shared,combine}`` here, ``mor_quant/<role>``
and ``gemm/<which>`` inside each linear (``repro.core.linear``,
``repro.core.grouped``), so a device trace can be read per layer.

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
from repro.core.grouped import (
    grouped_ffn, make_groups, over_live_rows, row_align,
)
from repro.configs.base import ArchConfig

from .attention import decode_attention, flash_attention
from .common import (
    activation,
    apply_rope,
    constrain,
    glu_split,
    layer_norm,
    rms_norm,
)

__all__ = [
    "norm", "residual", "attn_sublayer", "mla_sublayer", "mlp_sublayer",
    "moe_sublayer", "dense_block", "moe_block",
]


@jax.named_scope("norm")
def norm(p_norm, x, cfg: ArchConfig):
    if cfg.norm == "ln":
        return layer_norm(x, p_norm["scale"], p_norm["bias"])
    return rms_norm(x, p_norm["scale"], cfg.norm_eps)


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
    """Self-attention with GQA + RoPE + KV cache. Returns (y, cache, stats).
    Under ``cfg.mla`` the latent attention of :func:`mla_sublayer`."""
    if cfg.mla:
        return mla_sublayer(p, xn, tok, policy, cfg, mode, cache, cur_index,
                            kind=kind, prefix_len=prefix_len, window=window)
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


def mla_sublayer(p, xn, tok, policy: MoRDotPolicy, cfg: ArchConfig,
                 mode: str, cache, cur_index, *, kind: str = "causal",
                 prefix_len: int = 0, window: int = 0):
    """Multi-head latent attention (DeepSeek-V2/V3) without a query
    low-rank: q = x wq (heads of nope + rope columns); the latent x wkva
    gives ``kv_lora_rank`` columns, RMS-normed and up-projected by wkvb
    to each head's nope key and value, and ``qk_rope_dim`` rotary key
    columns shared by every head. Scores scale by the query width**-0.5.
    Runs under the caller's ``attn`` scope. Returns (y, cache, stats);
    the cache holds the heads' keys and values, as for GQA."""
    B, S, _ = xn.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    dv, r = cfg.head_dim, cfg.kv_lora_rank
    with jax.named_scope("qkv"):
        q, st_q = mor_dot(xn, p["wq"], tok["q"], policy)
        lat, st_kva = mor_dot(xn, p["wkva"], tok["kva"], policy)
        q = q.reshape(B, S, H, dn + dr)
        c, k_rope = jnp.split(lat, [r], axis=-1)
    with jax.named_scope("latent"):
        c = rms_norm(c, p["kv_norm"]["scale"], cfg.norm_eps)
    with jax.named_scope("qkv"):
        kv, st_kvb = mor_dot(c, p["wkvb"], tok["kvb"], policy)
        k_nope, v = jnp.split(kv.reshape(B, S, H, dn + dv), [dn], axis=-1)

    if mode == "decode":
        cur = jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(cur_index, jnp.int32)), (B,)
        )
        pos = cur[:, None] - (S - 1) + jnp.arange(S, dtype=jnp.int32)[None]
    else:
        pos = jnp.arange(S, dtype=jnp.int32)[None]
    q_rope, k_rope = _rope(q[..., dn:], k_rope[:, :, None, :], pos,
                           cfg.rope_theta)
    with jax.named_scope("latent"):
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)

    if mode == "decode":
        out, new_cache = _decode_core(q, k, v, cache, cur, pos, window)
    else:
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
        out = out.reshape(B, S, H * dv)
        y, st_proj = mor_dot(out, p["wo"], tok["proj"], policy)
    return y, new_cache, {"q": st_q, "kva": st_kva, "kvb": st_kvb,
                          "proj": st_proj}


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
def _route(p, x, cfg: ArchConfig, B: int, S: int):
    """Scores over all ``n_experts`` in f32, the chosen experts (T, K),
    their gates, and the sequence-wise balance loss.

    Selection takes the top-k of the scores plus the bias (DeepSeek-V3's
    aux-loss-free bias: it picks experts and weighs nothing, and gets no
    gradient); the gates are the chosen scores, renormalised to sum 1
    and scaled by ``routed_scale``. The balance loss
    is, per sequence, sum_i f_i P_i with f_i = E / (K S) times the
    copies sent to expert i and P_i the mean of its score normalised
    over the experts; the mean over sequences is returned."""
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["bias"]), K)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * cfg.routed_scale

    sel = jnp.sum(jax.nn.one_hot(idx.reshape(B, S * K), E,
                                 dtype=jnp.float32), axis=1)  # (B, E)
    f = sel * (E / (K * S))
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    aux = jnp.mean(jnp.sum(f * jnp.mean(share.reshape(B, S, E), 1), -1))
    return idx, gate, aux


def _dispatch(idx, cfg: ArchConfig, T: int, align: int):
    """Lays the token-copies that chose a held expert out by expert.

    Returns (Groups, ``src`` (R,) the token of each buffer row, T for
    padding and the tail, ``dest`` (T, K) the buffer row of each copy, R
    for a copy of an expert held elsewhere). R = T * min(K, held) rows
    plus a block of padding per held expert, a static bound that every
    routing fits."""
    K, H = cfg.top_k, cfg.n_held
    R = -(-(T * min(K, H) + H * (align - 1)) // align) * align
    local = idx.reshape(T * K) - cfg.first_expert
    key = jnp.where((local >= 0) & (local < H), local, H)
    rows = jnp.bincount(key, length=H + 1)[:H]
    groups = make_groups(rows, align)
    order = jnp.argsort(key, stable=True)
    ks = key[order]
    first = jnp.cumsum(rows) - rows  # each group's first sorted rank
    offset = jnp.cumsum(groups.sizes) - groups.sizes
    rank = jnp.arange(T * K, dtype=jnp.int32)
    held = ks < H
    at = jnp.where(held, offset[jnp.minimum(ks, H - 1)] + rank
                   - first[jnp.minimum(ks, H - 1)], R)
    dest = jnp.zeros((T * K,), jnp.int32).at[order].set(at.astype(jnp.int32))
    src = jnp.full((R + 1,), T, jnp.int32).at[dest].set(
        rank // K, mode="drop")[:R]
    return groups, src, dest.reshape(T, K)


def _rows_of(x, src, live):
    """(T, d) tokens -> (R, d) buffer: row r holds ``x[src[r]]``, zero
    where ``src`` is T (padding) and past the live rows."""
    buf = jnp.zeros((src.shape[0], x.shape[1]), x.dtype)
    return over_live_rows(
        lambda _, s: (x.at[s].get(mode="fill", fill_value=0),), live,
        (buf,), src)[0]


def _sum_copies(buf, dest):
    """(R, d) buffer -> (T, d) f32: each token's sum over its copies'
    rows, zero for a copy held elsewhere (``dest`` R)."""
    y = 0.0
    for k in range(dest.shape[1]):
        y = y + buf.at[dest[:, k]].get(mode="fill",
                                       fill_value=0).astype(jnp.float32)
    return y


# Dispatch and combine are each other's transposes: both backward passes
# are gathers, never a scatter-add into the buffer.
@jax.custom_vjp
def _to_rows(x, src, dest, live):
    return _rows_of(x, src, live)


def _to_rows_fwd(x, src, dest, live):
    return _rows_of(x, src, live), (src, dest)


def _to_rows_bwd(res, ct):
    src, dest = res
    return _sum_copies(ct, dest).astype(ct.dtype), None, None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _to_tokens(buf, src, dest, live):
    return _sum_copies(buf, dest)


def _to_tokens_fwd(buf, src, dest, live):
    # An empty array carries the buffer's dtype to the backward pass.
    return _sum_copies(buf, dest), (src, live, jnp.zeros((0,), buf.dtype))


def _to_tokens_bwd(res, ct):
    src, live, like = res
    return _rows_of(ct.astype(like.dtype), src, live), None, None, None


_to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


@jax.named_scope("mlp")
@jax.named_scope("moe")
def moe_sublayer(p, xn, tok, policy: MoRDotPolicy, cfg: ArchConfig):
    """Dropless MoE over the experts this program holds.

    The router scores all ``cfg.n_experts`` experts (``_route``); the
    program holds ``cfg.n_held`` of them from ``cfg.first_expert`` on
    (an expert-parallel shard) and computes every token-copy that chose
    one of them, none dropped, and nothing of the others: their part of
    the output belongs to the shards that hold them. The copies are laid
    out by expert in one buffer of static bound (``_dispatch``), the
    expert FFNs run as grouped MoR products over it
    (``repro.core.grouped``: each expert's rows and weights are their
    own MoR tensors; ``grouped_ffn``), the activation weighs each row by
    its copy's gate, and each token sums its copies. The work of every
    pass over the buffer, forward and backward, follows the routed rows:
    the products and quantization stop at the last group, the gathers
    into the buffer and the activation at the chunk that holds it, and
    the sums read T * K rows. Shared experts, if any, run on every token
    as one MLP. Scopes: ``moe/route``, ``moe/dispatch``, ``moe/experts``,
    ``moe/shared``, ``moe/combine``, under the block's feed-forward
    scope ``mlp`` (so that an op XLA hoists out of the layer scan still
    names a layer).

    Stats: the experts' MoR rows, ``aux_loss`` (the balance loss), and
    the counters ``routed_rows`` (copies computed here), ``padded_rows``
    (zero rows that align the groups) and ``load_max_over_mean`` (the
    busiest held expert's rows over the mean).
    """
    B, S, d = xn.shape
    T = B * S
    x = xn.reshape(T, d)
    with jax.named_scope("route"):
        idx, gate, aux = _route(p, x, cfg, B, S)
    with jax.named_scope("dispatch"):
        groups, src, dest = _dispatch(idx, cfg, T, row_align(policy))
        live = jnp.sum(groups.sizes)
        xbuf = _to_rows(x, src, dest, live)
        row_gate = jnp.zeros(src.shape, jnp.float32).at[
            dest.reshape(-1)].set(gate.reshape(-1), mode="drop")
    with jax.named_scope("experts"):
        gated, act_fn = cfg.act in ("swiglu", "geglu"), activation(cfg.act)
        ybuf, st1, st2 = grouped_ffn(
            xbuf, p["w1"], p["w2"], row_gate, tok["w1"], tok["w2"], groups,
            policy, lambda h: glu_split(h, gated, act_fn))
    stats = {"w1": st1, "w2": st2}
    with jax.named_scope("combine"):
        y = _to_tokens(ybuf, src, dest, live)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            ys, stats["shared"] = mlp_sublayer(p["shared"], x, tok["shared"],
                                               policy, cfg)
        y = y + ys.astype(jnp.float32)
    rows = groups.rows.astype(jnp.float32)
    stats.update(
        aux_loss=aux,
        routed_rows=jnp.sum(rows),
        padded_rows=jnp.sum(groups.sizes).astype(jnp.float32)
        - jnp.sum(rows),
        load_max_over_mean=jnp.max(rows)
        / jnp.maximum(jnp.mean(rows), 1.0),
    )
    return y.astype(xn.dtype).reshape(B, S, d), stats


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
