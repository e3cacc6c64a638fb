"""Fused GAM quantize kernel (Pallas, TPU target).

One VMEM-resident pass per 128x128 block: block amax -> GAM scale
reconstruction (shared group mantissa + per-block E8M0 exponent, Alg. 1)
-> saturating cast -> dequant -> per-block relative-error sums. On TPU
this replaces the ~6 HBM passes of the XLA lowering (see §Perf).

Exponent/mantissa arithmetic uses integer bit manipulation only (Mosaic
has no frexp); `exp2i` is an exponent-field bitcast, exactly as in
repro.core.gam.

Grid: (M/bm, K/bk). The group (tensor) mantissa is computed outside the
kernel from the global amax (one cheap XLA reduce) and broadcast in as a
(1, 1) block.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gam_quant_blocks"]

_F32_MAX = 3.4028235e38  # finfo(f32).max


def _split_me(s):
    """Bit-level (mantissa in [1,2), exponent) of positive f32 (1, 1) s.

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


def _kernel(mg_ref, x_ref, out_ref, exp_ref, err_ref, cnt_ref,
            *, q_amax: float, out_dtype, algo: str):
    i, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    m_g = mg_ref[0, 0]

    # (1, 1) block amax: the exponent/mantissa bit arithmetic must run on
    # vectors (Mosaic's tpu.bitcast rejects scalars).
    bmax = jnp.max(jnp.abs(x), axis=(0, 1), keepdims=True)
    safe_b = jnp.where(bmax > 0, bmax, 1.0)
    s_b = jnp.minimum(q_amax / safe_b, _F32_MAX)  # core.gam's cap
    m_b, e_b = _split_me(s_b)

    if algo == "gam":
        # Alg. 1 rounding: avoid saturation when m_g > m_b.
        e_b = jnp.where(m_g <= m_b, e_b, e_b - 1)
        scale = m_g * _exp2i(e_b)
    elif algo == "e8m0":
        scale = _exp2i(e_b)
    else:  # fp32_amax
        scale = s_b

    xs = jnp.clip(x * scale, -q_amax, q_amax)
    xq = xs.astype(out_dtype).astype(jnp.float32) / scale
    # Error is measured on the *stored* (Fig. 4: BF16) dequantized value.
    xq_stored = xq.astype(out_ref.dtype)
    xq = xq_stored.astype(jnp.float32)

    nz = x != 0.0
    rel = jnp.where(nz, jnp.abs((x - xq) / jnp.where(nz, x, 1.0)), 0.0)

    out_ref[...] = xq_stored
    # The (nm, nk) stat outputs live whole in SMEM across the grid (TPU
    # tiling forbids (1, 1) VMEM blocks and VMEM rejects scalar stores);
    # each step writes its own cell.
    exp_ref[i, j] = e_b[0, 0].astype(jnp.int32)
    err_ref[i, j] = jnp.sum(rel)
    cnt_ref[i, j] = jnp.sum(nz.astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("block", "q_amax", "fmt_dtype", "algo", "interpret"),
)
def gam_quant_blocks(
    x: jnp.ndarray,
    group_mantissa: jnp.ndarray,
    *,
    block: Tuple[int, int] = (128, 128),
    q_amax: float = 448.0,
    fmt_dtype=jnp.float8_e4m3fn,
    algo: str = "gam",
    interpret: bool = False,
):
    """x: (M, K) with M % bm == 0, K % bk == 0.

    Returns (xq fake-quantized in x.dtype, block_exp (nm, nk) i32,
    err_sums (nm, nk) f32, counts (nm, nk) f32).
    """
    M, K = x.shape
    bm, bk = block
    assert M % bm == 0 and K % bk == 0, (x.shape, block)
    nm, nk = M // bm, K // bk
    mg = jnp.reshape(group_mantissa.astype(jnp.float32), (1, 1))

    kernel = functools.partial(
        _kernel, q_amax=q_amax, out_dtype=fmt_dtype, algo=algo
    )
    out_shapes = (
        jax.ShapeDtypeStruct((M, K), x.dtype),
        jax.ShapeDtypeStruct((nm, nk), jnp.int32),
        jax.ShapeDtypeStruct((nm, nk), jnp.float32),
        jax.ShapeDtypeStruct((nm, nk), jnp.float32),
    )
    grid = (nm, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),  # group mantissa
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),  # x block (VMEM)
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=out_shapes,
        interpret=interpret,
        name="gam_quant_blocks",
    )(mg, x)
