"""Fused sub-tensor MoR selection kernel (Pallas, TPU target).

One VMEM-resident pass per block realizes the whole §3.2 per-block
decision that the XLA lowering previously spread over three full passes
of the operand (E4M3 quant+error, E5M2 quant+error, abs/min/max range
pass). Per (bm, bk) block the kernel computes:

  * both fp8 candidates, each GAM-scaled (Alg. 1) with its format's own
    group mantissa (reconstructed from the shared exponent-bitcast
    arithmetic used by ``gam_quant_blocks`` -- Mosaic has no frexp),
  * the per-block relative-error sums of both candidates (Eq. 3),
  * the nonzero min/max dynamic-range ratio for the Eq. 4 E5M2 gate,

and emits, per ``emit``:

  * ``emit='select'`` -- the *selected* fake-quantized block (E4M3 /
    E5M2 / original BF16 passthrough) plus the per-block selection id
    and stats. The operand is read from HBM exactly once and only the
    winner is written back (fake-quantization, training numerics).
  * ``emit='pack'`` -- the *real* mixed block layout instead of the
    fake-quant values: the selected candidate's raw fp8 bits
    (``payload_q``), the BF16 passthrough buffer (``payload_bf16``),
    per-block GAM scales, and for ``mode='sub4'`` the packed E2M1
    nibbles + E4M3 micro-scale bytes -- byte-identical to
    ``ref.pack_mixed`` on the selection's tags, with no second XLA
    pass over the operand. The in-register candidates the select mode
    throws away are exactly what packing needs, so the whole
    ``quantize_for_gemm`` event becomes this one kernel.

Selection ids: 0 = E4M3, 1 = E5M2, 2 = BF16 (original values),
3 = NVFP4 (sub4 only).

Modes mirror the paper's recipes (+ the §5 NVFP4 outlook):
  * ``sub2``: E4M3 iff it beats the E5M2 benchmark (Eq. 3), else BF16.
  * ``sub3``: E4M3 -> E5M2 (Eq. 4 range gate) -> BF16.
  * ``sub4``: NVFP4 (Eq. 3 vs the E4M3 benchmark + the Eq. 4-style
    NVFP4 range gate) -> the sub3 cascade. The NVFP4 candidate is the
    two-level scheme of ``core.formats.cast_to_nvfp4``: GAM block scale
    targeting 448*6, then one E4M3 micro scale per 16 contraction
    elements. Per-16 micro amaxes ride in as a (bm, bk/16) input block
    (one cheap XLA segment reduce, like the group mantissas); inside
    the kernel they are broadcast back to (bm, bk) with a one-hot f32
    matmul (exact: one summand per output lane), which Mosaic lowers
    where a lane-splitting reshape/repeat would not.

Grid: (M/bm, K/bk). Group mantissas for all formats plus the
zero-guarded group amax come in as a (1, 4) block computed outside the
kernel from the global amax (one cheap XLA reduce), exactly like
``gam_quant_blocks``. The group amax backs the ``scales_from_bmax``
zero-block guard (all-zero blocks scale as if their amax were the
group's), so pack-mode GAM scales match the XLA packer bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import (
    E2M1_AMAX,
    NVFP4_MICRO,
    encode_e2m1,
    round_to_e2m1,
)

from .ref import expand_micro_onehot

__all__ = ["mor_select_blocks"]

_F32_BIG = 3.4028235e38  # finfo(f32).max


def _split_me(s):
    """Bit-level (mantissa in [1,2), exponent) of positive f32 s.

    s must be a (1, 1) vector, not a scalar: Mosaic's tpu.bitcast only
    accepts vector operands.
    """
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    m = jax.lax.bitcast_convert_type(
        (bits & 0x7FFFFF) | (127 << 23), jnp.float32
    )
    return m, e


def _exp2i(e):
    # Full E8M0 domain [-126, 127], matching core.gam (the 126 clamp
    # was the double-rounding bug on tiny-amax blocks).
    e = jnp.clip(e, -126, 127)
    return jax.lax.bitcast_convert_type(
        (e + 127) << 23, jnp.float32
    )


def _kernel(mg_ref, *refs, q_amax4: float, q_amax5: float,
            q_amax_nv: float, dt4, dt5, mode: str, algo: str,
            range_ratio: float, nv_range_ratio: float, emit: str):
    if mode == "sub4":
        ma_ref, x_ref, *outs = refs
    else:
        ma_ref = None
        x_ref, *outs = refs
    if emit == "select":
        nib_ref = ms_ref = scl_ref = None
        if mode == "sub4":
            y_ref, sel_ref, e4_ref, e5_ref, cnt_ref, nv_ref = outs
        else:
            y_ref, sel_ref, e4_ref, e5_ref, cnt_ref = outs
    else:
        y_ref = None
        if mode == "sub4":
            (pq_ref, pbf_ref, sel_ref, scl_ref, e4_ref, e5_ref, cnt_ref,
             nv_ref, nib_ref, ms_ref) = outs
        else:
            nib_ref = ms_ref = None
            (pq_ref, pbf_ref, sel_ref, scl_ref, e4_ref, e5_ref,
             cnt_ref) = outs
    i, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    ax = jnp.abs(x)
    bmax = jnp.max(ax)
    # (1, 1) view of the block amax: the exponent/mantissa bit arithmetic
    # must run on vectors (Mosaic's tpu.bitcast rejects scalars).
    bmax11 = jnp.max(ax, axis=(0, 1), keepdims=True)
    # scales_from_bmax zero guard: an all-zero block scales as if its
    # amax were the group's (quantizing zeros is exact either way, but
    # the *reconstructed scale* must match the XLA packer bit-for-bit).
    safe_b = jnp.where(bmax11 > 0, bmax11, mg_ref[0, 3])
    nz = x != 0.0
    cnt = jnp.sum(nz.astype(jnp.float32))

    def gam_scale(q_amax, m_g):
        s_b = jnp.minimum(q_amax / safe_b, _F32_BIG)  # core.gam's cap
        m_b, e_b = _split_me(s_b)
        if algo == "gam":
            # Alg. 1 rounding: avoid saturation when m_g > m_b.
            e_b = jnp.where(m_g <= m_b, e_b, e_b - 1)
            return m_g * _exp2i(e_b)
        if algo == "e8m0":
            return _exp2i(e_b)
        return s_b  # fp32_amax

    def rel_err_sum(xq_stored):
        # Eq. 3 compares errors of the *stored* (Fig. 4: BF16) values.
        xqf = xq_stored.astype(jnp.float32)
        rel = jnp.where(nz, jnp.abs((x - xqf) / jnp.where(nz, x, 1.0)), 0.0)
        return jnp.sum(rel)

    def candidate(q_amax, m_g, out_dtype):
        scale = gam_scale(q_amax, m_g)
        xs = jnp.clip(x * scale, -q_amax, q_amax)
        xq8 = xs.astype(out_dtype)
        xq = xq8.astype(jnp.float32) / scale
        xq_stored = xq.astype(x_ref.dtype)
        return xq_stored, rel_err_sum(xq_stored), xq8, scale

    q4, e4, q4_bits, s4 = candidate(q_amax4, mg_ref[0, 0], dt4)
    q5, e5, q5_bits, s5 = candidate(q_amax5, mg_ref[0, 1], dt5)

    m1 = e4 < e5  # Eq. 3: E4M3 beats the E5M2 benchmark on total rel-err.
    if mode == "sub2":
        use5 = jnp.bool_(False)
    else:  # sub3/sub4: Eq. 4 dynamic-range gate for the E5M2 fallback.
        anynz = cnt > 0
        bmin = jnp.min(jnp.where(nz, ax, _F32_BIG))
        ratio = jnp.where(anynz, bmax / jnp.where(anynz, bmin, 1.0), 1.0)
        use5 = jnp.logical_and(jnp.logical_not(m1), ratio < range_ratio)

    sel = jnp.where(
        m1, jnp.int32(0), jnp.where(use5, jnp.int32(1), jnp.int32(2))
    )
    if emit == "select":
        y = jnp.where(m1, q4, jnp.where(use5, q5, x_ref[...]))

    use_nv = None
    s_nv = None
    if mode == "sub4":
        # Two-level NVFP4 candidate: GAM block scale targeting 448*6,
        # then one E4M3 micro scale per 16 contraction elements (the
        # micro amaxes arrive as a whole row stripe -- see
        # _expand_micro; micro_amax(x)*scale == micro_amax(x*scale)
        # bit-exactly: f32 multiply by a positive scale is monotone
        # and commutes with abs).
        g16 = x.shape[-1] // NVFP4_MICRO
        s_nv = gam_scale(q_amax_nv, mg_ref[0, 2])
        ma = ma_ref[...]  # (bm, K/16) raw micro-group amax stripe
        d = ma * s_nv / E2M1_AMAX
        d_q = jnp.clip(d, -448.0, 448.0).astype(
            jnp.float8_e4m3fn
        ).astype(jnp.float32)
        safe_d = jnp.where(d_q > 0, d_q, 1.0)
        d_exp = expand_micro_onehot(safe_d, x.shape[-1], j * g16)
        xs = x * s_nv
        e2 = round_to_e2m1(xs / d_exp)  # E2M1 grid values
        qn_stored = ((e2 * d_exp) / s_nv).astype(x_ref.dtype)
        env = rel_err_sum(qn_stored)
        # Eq. 4-style gate on this block's micro-group amaxes (what
        # the E4M3 micro scales must represent; intra-group range is
        # already priced into env by Eq. 3). Mask the stripe to grid
        # step j's group window.
        gcol = jax.lax.broadcasted_iota(jnp.int32, ma.shape, 1)
        in_blk = jnp.logical_and(gcol >= j * g16, gcol < (j + 1) * g16)
        ga_min = jnp.min(
            jnp.where(jnp.logical_and(in_blk, ma > 0), ma, _F32_BIG)
        )
        g_ratio = jnp.where(anynz, bmax / jnp.where(anynz, ga_min, 1.0),
                            1.0)
        use_nv = jnp.logical_and(env < e4, g_ratio < nv_range_ratio)
        sel = jnp.where(use_nv, jnp.int32(3), sel)
        if emit == "select":
            y = jnp.where(use_nv, qn_stored, y)
        else:
            # Packed-nibble lane (row-halves packing within the block)
            # + the micro-scale byte stripe, masked to NVFP4 winners --
            # byte-identical to ref._nvfp4_lanes. Byte selects run in
            # the i32 domain and narrow at the store: Mosaic lowers
            # i32 selects and i32 -> u8 casts, but not u8 constants.
            codes = encode_e2m1(e2)  # (bm, bk) int32 in [0, 15]
            half = x.shape[0] // 2
            nib = codes[:half, :] | (codes[half:, :] << 4)
            nib_ref[...] = jnp.where(use_nv, nib, jnp.int32(0)).astype(
                jnp.uint8
            )
            ms_bits = jax.lax.bitcast_convert_type(
                safe_d.astype(jnp.float8_e4m3fn), jnp.uint8
            ).astype(jnp.int32)
            ms_win = jnp.where(
                jnp.logical_and(in_blk, use_nv), ms_bits, jnp.int32(0)
            )
            # The micro-scale stripe block is revisited across the j
            # sweep (index (i, 0)); each step owns its group window.
            @pl.when(j == 0)
            def _():
                ms_ref[...] = ms_win.astype(jnp.uint8)

            @pl.when(j > 0)
            def _():
                ms_ref[...] = jnp.where(
                    in_blk, ms_win, ms_ref[...].astype(jnp.int32)
                ).astype(jnp.uint8)
        nv_ref[i, j] = env

    if emit == "select":
        y_ref[...] = y
    else:
        # Real payload lanes of the winner: raw fp8 bits for fp8 tags,
        # the original values for BF16 tags, zeros (don't-care) in the
        # lanes the tag does not reference -- pack_mixed's layout. The
        # byte select runs in i32 (Mosaic has no u8 constants).
        b4 = jax.lax.bitcast_convert_type(q4_bits, jnp.uint8).astype(
            jnp.int32
        )
        b5 = jax.lax.bitcast_convert_type(q5_bits, jnp.uint8).astype(
            jnp.int32
        )
        pq_ref[...] = jnp.where(
            sel == 0, b4, jnp.where(sel == 1, b5, jnp.int32(0))
        ).astype(jnp.uint8)
        pbf_ref[...] = jnp.where(
            sel == 2, x_ref[...], jnp.zeros_like(x_ref[...])
        )
        scale_sel = jnp.where(
            sel == 0, s4, jnp.where(sel == 1, s5, jnp.float32(1.0))
        )
        if mode == "sub4":
            scale_sel = jnp.where(sel == 3, s_nv, scale_sel)
        scl_ref[i, j] = jnp.sum(scale_sel)  # exact: (1, 1) -> scalar
    # The (nm, nk) stat outputs live whole in SMEM across the grid (TPU
    # tiling forbids (1, 1) VMEM blocks and VMEM rejects scalar stores);
    # each step writes its own cell.
    sel_ref[i, j] = sel
    e4_ref[i, j] = e4
    e5_ref[i, j] = e5
    cnt_ref[i, j] = cnt


@functools.partial(
    jax.jit,
    static_argnames=(
        "block", "q_amax4", "q_amax5", "q_amax_nv", "dt4", "dt5", "mode",
        "algo", "range_ratio", "nv_range_ratio", "emit", "interpret",
    ),
)
def mor_select_blocks(
    x: jnp.ndarray,
    group_mantissas: jnp.ndarray,
    group_amax: jnp.ndarray | None = None,
    *,
    block: Tuple[int, int] = (128, 128),
    q_amax4: float = 448.0,
    q_amax5: float = 57344.0,
    q_amax_nv: float = 448.0 * 6.0,
    dt4=jnp.float8_e4m3fn,
    dt5=jnp.float8_e5m2,
    mode: str = "sub3",
    algo: str = "gam",
    range_ratio: float = 57344.0 / 2.0**-14,
    nv_range_ratio: float = 12.0 * 448.0 / 2.0**-9,  # NVFP4_RANGE_RATIO
    emit: str = "select",
    interpret: bool = False,
):
    """x: (M, K) with M % bm == 0, K % bk == 0 (and bk % 16 == 0 for
    ``mode='sub4'``; ``emit='pack'`` on sub4 additionally wants bm % 2
    == 0 for the nibble row pairing).

    group_mantissas: (3,) f32 -- [m_g(E4M3), m_g(E5M2), m_g(NVFP4)]
    (all 1.0 for the e8m0 / fp32_amax ablations; the NVFP4 slot is
    ignored outside sub4 but always present so the operand layout is
    mode-independent). A legacy (2,) vector is accepted for
    sub2/sub3 callers and padded with 1.0.

    group_amax: () f32 zero-guarded group (tensor) amax -- the
    ``scales_from_bmax`` guard value for all-zero blocks. Computed here
    with one XLA reduce when omitted; recipe callers pass the (possibly
    mesh-allreduced) value they already have.

    emit='select' returns (y selected fake-quant in x.dtype, sel
    (nm, nk) i32, e4_err_sums (nm, nk) f32, e5_err_sums (nm, nk) f32,
    counts (nm, nk) f32[, nv_err_sums (nm, nk) f32 -- sub4 only]).

    emit='pack' returns (payload_q (M, K) uint8, payload_bf16 (M, K)
    x.dtype, sel, scales (nm, nk) f32, e4_err_sums, e5_err_sums,
    counts[, nv_err_sums, payload_nib (M/2, K) uint8, micro_scales
    (M, K/16) uint8 -- sub4 only]) -- the ``ref.MixedOperand`` buffer
    lanes, byte-identical to ``ref.pack_mixed`` on this selection.
    """
    M, K = x.shape
    bm, bk = block
    assert M % bm == 0 and K % bk == 0, (x.shape, block)
    assert mode in ("sub2", "sub3", "sub4"), mode
    assert emit in ("select", "pack"), emit
    nm, nk = M // bm, K // bk
    gm = jnp.reshape(group_mantissas.astype(jnp.float32), (-1,))
    if gm.shape[0] == 2:  # legacy sub2/sub3 callers: no NVFP4 slot
        assert mode != "sub4", "sub4 needs the NVFP4 group mantissa"
        gm = jnp.concatenate([gm, jnp.ones((1,), jnp.float32)])
    if group_amax is None:
        g = jnp.max(jnp.abs(x.astype(jnp.float32)))
        group_amax = jnp.where(g > 0, g, 1.0)
    mg = jnp.reshape(
        jnp.concatenate(
            [gm, jnp.reshape(group_amax.astype(jnp.float32), (1,))]
        ),
        (1, 4),
    )

    kernel = functools.partial(
        _kernel, q_amax4=q_amax4, q_amax5=q_amax5, q_amax_nv=q_amax_nv,
        dt4=dt4, dt5=dt5, mode=mode, algo=algo, range_ratio=range_ratio,
        nv_range_ratio=nv_range_ratio, emit=emit,
    )
    in_specs = [
        pl.BlockSpec((1, 4), lambda i, j: (0, 0)),  # mantissas + amax
    ]
    operands = [mg]
    if mode == "sub4":
        assert bk % NVFP4_MICRO == 0, (block, NVFP4_MICRO)
        if emit == "pack":
            assert bm % 2 == 0, (block, "nibble packing pairs rows")
        # Per-16-element micro amaxes: one XLA segment reduce outside
        # the kernel (like the group mantissas). The stripe rides in
        # whole along the contraction axis -- its (K/16) lane count is
        # not 128-divisible, and TPU tiling only accepts a
        # non-divisible lane dim when it equals the whole array's.
        ma = jnp.max(
            jnp.abs(x.astype(jnp.float32)).reshape(
                M, K // NVFP4_MICRO, NVFP4_MICRO
            ),
            axis=-1,
        )
        in_specs.append(
            pl.BlockSpec((bm, K // NVFP4_MICRO), lambda i, j: (i, 0))
        )
        operands.append(ma)
    in_specs.append(
        pl.BlockSpec((bm, bk), lambda i, j: (i, j))  # x block (VMEM)
    )
    operands.append(x)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    nmk_f32 = jax.ShapeDtypeStruct((nm, nk), jnp.float32)
    if emit == "select":
        out_shapes = [jax.ShapeDtypeStruct((M, K), x.dtype)]
        out_specs = [pl.BlockSpec((bm, bk), lambda i, j: (i, j))]
    else:
        out_shapes = [
            jax.ShapeDtypeStruct((M, K), jnp.uint8),   # payload_q
            jax.ShapeDtypeStruct((M, K), x.dtype),     # payload_bf16
        ]
        out_specs = [
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        ]
    out_shapes.append(jax.ShapeDtypeStruct((nm, nk), jnp.int32))  # sel
    out_specs.append(smem)
    if emit == "pack":
        out_shapes.append(nmk_f32)  # reconstructed GAM scales
        out_specs.append(smem)
    out_shapes += [nmk_f32, nmk_f32, nmk_f32]  # e4 / e5 / counts
    out_specs += [smem, smem, smem]
    if mode == "sub4":
        out_shapes.append(nmk_f32)  # nv err sums
        out_specs.append(smem)
        if emit == "pack":
            out_shapes += [
                jax.ShapeDtypeStruct((M // 2, K), jnp.uint8),
                jax.ShapeDtypeStruct((M, K // NVFP4_MICRO), jnp.uint8),
            ]
            out_specs += [
                pl.BlockSpec((bm // 2, bk), lambda i, j: (i, j)),
                # Whole-row micro-scale stripe, revisited across j
                # (each step writes its own group window): the (K/16)
                # lane count is not 128-divisible, so blocks must span
                # the full lane extent, exactly like the ma input.
                pl.BlockSpec(
                    (bm, K // NVFP4_MICRO), lambda i, j: (i, 0)
                ),
            ]

    return pl.pallas_call(
        kernel,
        grid=(nm, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shapes),
        interpret=interpret,
        name="mor_select_blocks",
    )(*operands)
