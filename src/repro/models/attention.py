"""Chunked flash-style attention (pure JAX) + decode attention.

Training/prefill attention never materializes the (S, T) score matrix:
queries are processed in chunks (lax.map) and keys/values are streamed with
an online-softmax scan -- O(q_chunk * k_chunk) live memory per (batch, head).
This is the XLA-portable analogue of the Pallas flash kernel in
repro/kernels/flash_attention.py (used on real TPUs); both match the
reference oracle in tests.

Supports GQA/MQA (grouped heads), causal / full / prefix-LM / sliding-window
masking, all of which the assigned architectures need.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.formats import (
    E2M1_AMAX,
    E4M3,
    E5M2,
    NVFP4,
    NVFP4_MICRO,
    cast_to_format,
    decode_e2m1,
    encode_e2m1,
    round_to_e2m1,
)
from repro.core.gam import scales_from_bmax
from repro.kernels.ref import (
    TAG_BF16,
    TAG_E4M3,
    TAG_E5M2,
    TAG_NVFP4,
    pack_mixed,
)

from .common import constrain, pick_chunk

__all__ = [
    "flash_attention",
    "decode_attention",
    "quantize_kv",
    "quantize_kv_mor",
    "recompress_kv_nvfp4",
    "kv_bytes_per_element",
    "kv_stats_row",
]

_NEG = -1e30


def _mask(kind: str, q_pos, k_pos, prefix_len: int, window: int):
    """(qc, kc) bool mask. q_pos: (qc,), k_pos: (kc,)."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    if kind == "full":
        m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    elif kind == "causal":
        m = kp <= qp
    elif kind == "prefix":
        m = (kp <= qp) | (kp < prefix_len)
    elif kind == "sliding":
        m = (kp <= qp) & (qp - kp < window)
    else:
        raise ValueError(kind)
    return m


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kind: str = "causal",
    prefix_len: int = 0,
    window: int = 0,
    q_chunk: int = 512,
    k_chunk: int = 1024,
) -> jnp.ndarray:
    """q, k: (B, S|T, Hq|Hkv, dh); v: (B, T, Hkv, dv) with Hq % Hkv == 0
    (dv == dh but for latent attention). Scores scale by dh**-0.5.

    Returns (B, S, Hq, dv) in q.dtype. Softmax in f32.
    """
    B, S, Hq, dh = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = Hq // Hkv
    qc = pick_chunk(S, q_chunk)
    kc = pick_chunk(T, k_chunk)
    nq, nk = S // qc, T // kc

    scale = dh**-0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, nq, qc, Hkv, G, dh)
    qg = jnp.moveaxis(qg, 1, 0)  # (nq, B, qc, Hkv, G, dh)
    kcs = jnp.moveaxis(k.reshape(B, nk, kc, Hkv, dh), 1, 0)
    vcs = jnp.moveaxis(v.reshape(B, nk, kc, Hkv, dv), 1, 0)

    # Sliding-window: each q chunk only sees a static-size band of kv
    # chunks (dynamic start). Without this the scan visits all nk chunks
    # and masks ~(T/window)x of them away -- measured 8x wasted traffic
    # for hymba prefill_32k (Perf iteration H2).
    band = nk
    if kind == "sliding" and window > 0:
        band = min((window + qc - 2) // kc + 2, nk)

    def q_chunk_fn(args):
        qi, q_i = args  # q_i: (B, qc, Hkv, G, dh)
        q_pos = qi * qc + jnp.arange(qc)

        m0 = jnp.full((B, Hkv, G, qc), _NEG, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, dv), jnp.float32)

        if band < nk:
            start = jnp.clip(
                (qi * qc - window + 1) // kc, 0, nk - band
            )
            k_sel = jax.lax.dynamic_slice_in_dim(kcs, start, band, axis=0)
            v_sel = jax.lax.dynamic_slice_in_dim(vcs, start, band, axis=0)
            k_idx = start + jnp.arange(band)
        else:
            k_sel, v_sel, k_idx = kcs, vcs, jnp.arange(nk)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, k_j, v_j = inp
            k_pos = kj * kc + jnp.arange(kc)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", q_i, k_j.astype(jnp.float32)
            )
            msk = _mask(kind, q_pos, k_pos, prefix_len, window)
            s = jnp.where(msk[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, v_j.astype(jnp.float32)
            )
            return (m_new, l, acc), None

        # Remat each kv step: the backward recomputes the (qc, kc) score
        # chunk from q/k instead of saving every probability chunk -- the
        # flash-attention backward. Without this, autodiff stores the full
        # S x S score matrix in f32 (measured: ~40% of HBM traffic).
        kv_step = jax.checkpoint(kv_step, prevent_cse=False)

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (k_idx, k_sel, v_sel)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)  # (B, qc, Hkv, G, dh)

    outs = jax.lax.map(q_chunk_fn, (jnp.arange(nq), qg))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, dv)
    return out.astype(q.dtype)


def _mor_kv_values(payload: jnp.ndarray, tags: jnp.ndarray) -> jnp.ndarray:
    """Tag-select decode of a MoR KV payload into scaled-space f32.

    ``payload``: (..., dh) uint8; ``tags``: (...) per-(position, head)
    representation tags. E4M3/E5M2 bytes bitcast per tag (the mixture
    generalization of the fp8 path's monolithic e4m3 cast). TAG_NVFP4
    rows (cold sub4 pages) keep packed E2M1 nibbles in bytes
    [0, dh/2) and E4M3 micro-scale bytes (one per NVFP4_MICRO elements)
    at [dh/2, dh/2 + dh/16) -- decoded here with micro scales folded in
    (they vary along the contraction axis so they cannot fold into
    score space; the per-block scale can, and does, downstream).
    Values stay in scaled space: the caller divides scores (or
    probabilities) by the per-(position, head) block scale.
    """
    e4 = jax.lax.bitcast_convert_type(
        payload, jnp.float8_e4m3fn
    ).astype(jnp.float32)
    e5 = jax.lax.bitcast_convert_type(
        payload, jnp.float8_e5m2
    ).astype(jnp.float32)
    t = tags[..., None]
    vals = jnp.where(t == TAG_E5M2, e5, e4)
    dh = payload.shape[-1]
    if dh % NVFP4_MICRO == 0:
        nh = dh // 2
        codes = payload[..., :nh]
        lo = decode_e2m1(codes & jnp.uint8(0xF))
        hi = decode_e2m1(codes >> 4)
        pairs = jnp.stack([lo, hi], axis=-1).reshape(payload.shape)
        ms = jax.lax.bitcast_convert_type(
            payload[..., nh:nh + dh // NVFP4_MICRO], jnp.float8_e4m3fn
        ).astype(jnp.float32)
        micro = jnp.repeat(
            jnp.where(ms > 0, ms, 1.0), NVFP4_MICRO, axis=-1
        )
        vals = jnp.where(t == TAG_NVFP4, pairs * micro, vals)
    return vals


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cur_index: jnp.ndarray,
    *,
    window: int = 0,
    k_scale: jnp.ndarray = None,
    v_scale: jnp.ndarray = None,
    k_tags: jnp.ndarray = None,
    v_tags: jnp.ndarray = None,
) -> jnp.ndarray:
    """Attention for decode / chunked prefill against a KV cache.

    q: (B, S, Hq, dh) with S >= 1 (S == 1 is plain decode; S > 1 is a
    prefill chunk); caches: (B, T, Hkv, dh). ``cur_index`` is the
    position of the *last* query token, either a scalar () shared by
    the batch or a (B,) vector of per-sequence positions -- each row of
    the batch masks against its own position, so mixed-length batches
    never attend across another request's length (docs/serving.md).
    Query s of row b sits at position cur_index[b] - (S - 1) + s; only
    cache entries at k_pos <= that position are visible. Entries beyond
    a row's own position are garbage by contract and must stay masked:
    zero-filled keys are NOT harmless (exp(0) = 1 takes real softmax
    mass).

    FP8 caches (beyond-paper, docs/serving.md): payloads are
    float8_e4m3 with per-(position, head) scales (B, T, Hkv). The
    scales factor out of both einsums -- scores divide by k_scale after
    the QK dot, and v_scale folds into the probabilities -- so the
    dequant never materializes a full-precision cache copy.

    MoR caches (docs/numerics.md): uint8 payloads + per-(position,
    head) ``k_tags``/``v_tags`` choose E4M3 / E5M2 / NVFP4 per block;
    scales fold into score space exactly as the fp8 path, the payload
    decode is the tag-select in :func:`_mor_kv_values`.

    Garbage hygiene (quantized caches): the score dequant divide is
    folded *inside* the validity mask (garbage scales from trash/stale
    pages never touch a surviving score), and value rows beyond each
    row's own position are zeroed before the PV einsum -- a masked
    probability is exactly 0, but ``0 * NaN`` (NaN/Inf payload bytes in
    the trash page) is NaN and would otherwise poison the whole output
    row. A bf16 cache only ever holds finite computed values, so its
    path keeps the original (guard-free) graph.
    """
    B, S, Hq, dh = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = dh**-0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, S, Hkv, G, dh)
    kv = (_mor_kv_values(k_cache, k_tags) if k_tags is not None
          else k_cache.astype(jnp.float32))
    s = jnp.einsum("bshgd,bkhd->bhgsk", qg, kv)
    cur = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(cur_index, jnp.int32)), (B,)
    )
    q_pos = cur[:, None] - (S - 1) + jnp.arange(S)  # (B, S)
    k_pos = jnp.arange(T)
    valid = k_pos[None, None, :] <= q_pos[:, :, None]  # (B, S, T)
    if window:
        valid &= k_pos[None, None, :] > q_pos[:, :, None] - window
    vmask = valid[:, None, None]  # (B, 1, 1, S, T)
    if k_scale is not None:
        # Mask-before-divide: garbage scales read from trash/stale
        # pages (NaN, denormal, inf) must never reach a kept score.
        ks = jnp.where(k_scale > 0, k_scale, 1.0)  # empty slots: scale 0
        s = jnp.where(
            vmask, s / jnp.moveaxis(ks, 1, 2)[:, :, None, None, :], _NEG
        )
    else:
        s = jnp.where(vmask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        vs = jnp.where(v_scale > 0, v_scale, 1.0)
        p = jnp.where(
            vmask, p / jnp.moveaxis(vs, 1, 2)[:, :, None, None, :], 0.0
        )
    vv = (_mor_kv_values(v_cache, v_tags) if v_tags is not None
          else v_cache.astype(jnp.float32))
    if v_tags is not None or v_scale is not None:
        # Value rows no query of this step can see are garbage by
        # contract; zero them so 0-probability lanes cannot contribute
        # 0 * NaN. Only quantized caches need this: their payload bytes
        # / scales can decode to NaN or Inf (trash page, stale rows),
        # while a bf16 cache only ever holds finite computed values.
        k_any = k_pos[None, :] <= cur[:, None]  # (B, T)
        vv = jnp.where(k_any[:, :, None, None], vv, 0.0)
    out = jnp.einsum("bhgsk,bkhd->bshgd", p, vv)
    return out.reshape(B, S, Hq, vv.shape[-1]).astype(q.dtype)


def quantize_kv(x: jnp.ndarray):
    """(B, S, H, dh) -> (float8_e4m3 payload, (B, S, H) f32 scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    payload = jnp.clip(
        x.astype(jnp.float32) * s[..., None], -448.0, 448.0
    ).astype(jnp.float8_e4m3fn)
    return payload, s


# ------------------------------------------------------- MoR KV cache --
# The cache tier's MoR block is one (position, head) row: the
# contraction axis of both attention einsums is dh, so a block scale is
# constant across everything a score sums over and folds into score
# space -- the same property the fp8 path's per-(position, head) scales
# exploit. Pages tile this grid exactly (a page is page_size * Hkv
# whole blocks), so per-page requantization never splits a block.
#
# The hot mixture is the two fp8 arms of the §3.2 cascade (Eq. 3 error
# comparison per block); the BF16 fallback arm is deliberately absent
# from the *storage*: a serving cache must bound bytes per token, and
# the E5M2 arm already covers the high-dynamic-range blocks BF16 would
# catch. TAG_BF16 remains representable (decode treats unknown tags as
# E4M3 only through explicit tag equality, so a BF16 tag simply never
# matches) and TAG_NVFP4 marks cold sub4-recompressed pages.


def quantize_kv_mor(x: jnp.ndarray, with_stats: bool = False):
    """MoR-quantize KV rows: (B, S, H, dh) -> (payload, tags, scales).

    Per (position, head) block: both GAM fp8 candidates, the Eq. 3
    relative-error comparison, and the winner's real payload bytes --
    routed through the same ``scales_from_bmax`` / ``pack_mixed``
    primitives as ``quantize_pack``, so cache bytes are bit-identical
    to what the GEMM-side packer would emit for the same tags.

    Returns ``(payload (B,S,H,dh) u8, tags (B,S,H) u8, scales (B,S,H)
    f32)``; scales are always > 0 for written rows (unwritten cache
    rows keep their zero-initialized scale, the emptiness marker the
    decode guard keys on). With ``with_stats``, also returns a
    STATS_WIDTH stats row (:func:`kv_stats_row`).
    """
    B, S, H, dh = x.shape
    x2 = x.astype(jnp.float32).reshape(B * S * H, dh)
    bmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)  # (R, 1)
    s4 = scales_from_bmax(bmax, E4M3, "gam").scale
    s5 = scales_from_bmax(bmax, E5M2, "gam").scale

    nz = x2 != 0
    safe = jnp.where(nz, x2, 1.0)

    def err(s, fmt):
        dq = cast_to_format(
            jnp.clip(x2 * s, -fmt.amax, fmt.amax), fmt
        ) / s
        return jnp.sum(
            jnp.where(nz, jnp.abs((x2 - dq) / safe), 0.0), axis=-1
        )

    e4 = err(s4, E4M3)
    e5 = err(s5, E5M2)
    sel = jnp.where(e4 < e5, TAG_E4M3, TAG_E5M2)  # Eq. 3, two fp8 arms
    mo = pack_mixed(x2, sel.reshape(-1, 1), (1, dh))
    payload = mo.payload_q.reshape(B, S, H, dh)
    tags = sel.astype(jnp.uint8).reshape(B, S, H)
    scales = mo.scales.astype(jnp.float32).reshape(B, S, H)
    if with_stats:
        return payload, tags, scales, kv_stats_row(tags)
    return payload, tags, scales


def recompress_kv_nvfp4(payload: jnp.ndarray, tags: jnp.ndarray,
                        scales: jnp.ndarray):
    """Sub4-recompress cold KV rows in place of their fp8 payloads.

    ``payload`` (..., H, dh) u8, ``tags``/``scales`` (..., H): any
    leading shape (the pool passes whole page slabs). Each
    (position, head) block re-encodes from its stored hot-tier values
    to the two-level NVFP4 representation -- packed E2M1 nibble pairs
    in payload bytes [0, dh/2), E4M3 micro-scale bytes (one per
    NVFP4_MICRO elements) at [dh/2, dh/2 + dh/16), remainder zero --
    so a cold page occupies 0.5625 logical bytes per element inside
    the same lane. Requires ``dh % NVFP4_MICRO == 0``.
    """
    dh = payload.shape[-1]
    if dh % NVFP4_MICRO:
        raise ValueError(
            f"sub4 KV recompression needs head_dim divisible by "
            f"{NVFP4_MICRO}, got {dh}"
        )
    ss = jnp.where(scales > 0, scales, 1.0)[..., None]
    vals = _mor_kv_values(payload, tags) / ss  # stored true values
    bmax = jnp.max(jnp.abs(vals), axis=-1, keepdims=True)
    s_nv = scales_from_bmax(bmax, NVFP4, "gam").scale
    xs = vals * s_nv
    g = xs.reshape(*xs.shape[:-1], dh // NVFP4_MICRO, NVFP4_MICRO)
    d = jnp.max(jnp.abs(g), axis=-1) / E2M1_AMAX
    d_q = cast_to_format(d, E4M3)
    safe_d = jnp.where(d_q > 0, d_q, 1.0)
    codes = encode_e2m1(
        round_to_e2m1(g / safe_d[..., None])
    ).reshape(xs.shape).astype(jnp.uint8)
    nib = (codes[..., 0::2] | (codes[..., 1::2] << 4)).astype(jnp.uint8)
    ms = jax.lax.bitcast_convert_type(
        safe_d.astype(jnp.float8_e4m3fn), jnp.uint8
    )
    pad = jnp.zeros(
        (*payload.shape[:-1], dh - dh // 2 - dh // NVFP4_MICRO),
        jnp.uint8,
    )
    new_payload = jnp.concatenate([nib, ms, pad], axis=-1)
    new_tags = jnp.full_like(tags, TAG_NVFP4)
    return new_payload, new_tags, s_nv[..., 0].astype(jnp.float32)


# Logical payload bytes per cache element by tag (fp8 byte, BF16 pair,
# NVFP4 nibble + its amortized micro-scale byte).
_TAG_BPE = {
    TAG_E4M3: 1.0,
    TAG_E5M2: 1.0,
    TAG_BF16: 2.0,
    TAG_NVFP4: 0.5 + 1.0 / NVFP4_MICRO,
}


def kv_bytes_per_element(tags: jnp.ndarray) -> jnp.ndarray:
    """Mean logical payload bytes per element implied by ``tags``."""
    t = jnp.asarray(tags).reshape(-1).astype(jnp.int32)
    bpe = jnp.zeros(t.shape, jnp.float32)
    for tag, b in _TAG_BPE.items():
        bpe = jnp.where(t == tag, b, bpe)
    return jnp.mean(bpe)


def kv_stats_row(tags: jnp.ndarray) -> jnp.ndarray:
    """One STATS_WIDTH (layout v4) stats row for a KV-cache
    quantization event.

    Same layout as the GEMM events (core.mor): [0] decision (1.0, the
    cache tier always quantizes), [3..5] frac_e4m3/e5m2/bf16, [6] block
    count, [7] m_g slot (1.0 -- per-event group), [8] frac_nvfp4,
    [9] micro-scale bytes per element, [11] payload bytes/element of
    the tag mixture. [1]/[2] (rel_err, amax) are 0: the cache path
    never re-reads its operand to price the error. [10] (event_kind)
    stays 0 -- cache rows ride the GEMM-event channel.
    """
    from repro.core.mor import (
        STAT_DECISION,
        STAT_FRAC_BF16,
        STAT_FRAC_E4M3,
        STAT_FRAC_E5M2,
        STAT_FRAC_NVFP4,
        STAT_GROUP_MANTISSA,
        STAT_MICRO_SCALE_BPE,
        STAT_NONZERO_FRAC,
        STAT_PAYLOAD_BPE,
        STATS_WIDTH,
    )

    t = jnp.asarray(tags).reshape(-1).astype(jnp.int32)
    n = t.size
    frac = lambda tag: jnp.mean((t == tag).astype(jnp.float32))
    f_nv = frac(TAG_NVFP4)
    row = jnp.zeros((STATS_WIDTH,), jnp.float32)
    row = row.at[STAT_DECISION].set(1.0)
    row = row.at[STAT_FRAC_E4M3].set(frac(TAG_E4M3))
    row = row.at[STAT_FRAC_E5M2].set(frac(TAG_E5M2))
    row = row.at[STAT_FRAC_BF16].set(frac(TAG_BF16))
    row = row.at[STAT_NONZERO_FRAC].set(float(n))
    row = row.at[STAT_GROUP_MANTISSA].set(1.0)
    row = row.at[STAT_FRAC_NVFP4].set(f_nv)
    row = row.at[STAT_MICRO_SCALE_BPE].set(f_nv / NVFP4_MICRO)
    row = row.at[STAT_PAYLOAD_BPE].set(
        frac(TAG_E4M3) + frac(TAG_E5M2) + 2.0 * frac(TAG_BF16)
        + (0.5 + 1.0 / NVFP4_MICRO) * f_nv
    )
    return row
