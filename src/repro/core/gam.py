"""Group Amax Mantissa (GAM) scaling -- Algorithm 1 of the paper.

GAM decouples the FP32 scaling factor ``s = q_amax / amax`` into

  * one group-level mantissa ``m_g in [1, 2)`` shared by every block of the
    group (group = whole tensor in all paper experiments), kept at full
    FP32-mantissa precision, and
  * one per-block E8M0 exponent ``e_b`` (8-bit, bias-127 storage).

The reconstructed per-block scale is ``m_g * 2^{e_b}``. The rounding step
(``e_b -= 1`` when ``m_g > m_b``) guarantees the *no-saturation invariant*::

    block_amax * (m_g * 2^{e_b}) <= q_amax        for every block,

which property tests assert for random tensors (tests/test_gam.py).

Ablation variants (paper §4.1.2):
  * ``gam``       -- the above (default).
  * ``e8m0``      -- per-block scale 2^{floor(log2 s_b)} (no mantissa; also
                     saturation-free since it only rounds the scale down).
  * ``fp32_amax`` -- standard per-block full-FP32 amax scaling s_b.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .formats import FormatSpec
from .partition import Partition, block_amax

__all__ = ["GamScales", "split_mantissa_exponent", "compute_scales", "scales_from_bmax", "exp2i", "E8M0_BIAS"]

E8M0_BIAS = 127
_F32_MAX = float(jnp.finfo(jnp.float32).max)


class GamScales(NamedTuple):
    """Scale metadata for one quantization event.

    scale:      (nm, nk) f32 reconstructed per-block scale factors.
    group_mantissa: () f32 in [1, 2) -- the shared 23-bit mantissa m_g
                    (1.0 for the e8m0 / fp32_amax ablations).
    block_exp:  (nm, nk) int32 per-block exponent (E8M0 payload, unbiased).
    group_amax: () f32 -- amax of the whole group (tensor).
    """

    scale: jnp.ndarray
    group_mantissa: jnp.ndarray
    block_exp: jnp.ndarray
    group_amax: jnp.ndarray


def exp2i(e: jnp.ndarray) -> jnp.ndarray:
    """Exact 2^e for integer e in [-126, 127] via exponent-field bitcast.

    jnp.exp2 is an approximate transcendental on some backends; scale
    reconstruction must be *exact* power-of-two arithmetic or the shared
    mantissa property of GAM is destroyed.
    """
    e = jnp.clip(e.astype(jnp.int32), -126, 127)
    bits = (e + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_mantissa_exponent(s: jnp.ndarray):
    """s = m * 2^e with m in [1, 2) (element-wise, s > 0). Exact (frexp)."""
    fr, e = jnp.frexp(s.astype(jnp.float32))  # fr in [0.5, 1)
    return (fr * 2.0).astype(jnp.float32), (e - 1).astype(jnp.int32)


def compute_scales(
    x2d: jnp.ndarray,
    part: Partition,
    fmt: FormatSpec,
    algo: str = "gam",
) -> GamScales:
    """Algorithm 1 with a single group covering the whole tensor.

    Returns per-block f32 scales such that ``x * scale`` is guaranteed not to
    saturate ``fmt`` (for 'gam' and 'e8m0'; 'fp32_amax' maps block amax to
    q_amax exactly).
    """
    return scales_from_bmax(block_amax(x2d, part), fmt, algo)


def scales_from_bmax(
    bmax: jnp.ndarray, fmt: FormatSpec, algo: str = "gam",
    group_amax: jnp.ndarray | None = None,
) -> GamScales:
    """Algorithm 1 from precomputed per-block amax (fused callers).

    ``group_amax`` overrides the group amax (default: max over the
    supplied block amaxes). Mesh-sharded events pass the allreduced
    global amax here so the shared mantissa ``m_g`` -- and with it every
    per-block scale -- is bit-identical across any sharding of the
    group (docs/sharding.md).
    """
    g_amax = jnp.max(bmax) if group_amax is None else group_amax

    # Zero guards: all-zero tensor / all-zero (or padding-only) blocks get
    # scale 1.0 -- quantizing zeros is exact under any scale. Nonfinite
    # guards ride the same selects: an Inf/NaN amax (poisoned operand)
    # would otherwise zero out or NaN the scale of every block sharing
    # the group mantissa. Sanitizing keeps clean blocks' scales exact;
    # poisoned blocks are contained downstream (BF16 selection arm /
    # skip-step) and reported via the stats guard lanes.
    g_ok = (g_amax > 0) & jnp.isfinite(g_amax)
    safe_g = jnp.where(g_ok, g_amax, 1.0)
    safe_b = jnp.where((bmax > 0) & jnp.isfinite(bmax), bmax, safe_g)

    # Ideal FP32 scales, capped at f32 max: below an amax of
    # fmt.amax / f32max (~1e-36 for E4M3) the quotient overflows to Inf,
    # and an Inf scale maps the block to Inf/NaN. The capped scale
    # still lands in exp2i's domain and keeps the invariant.
    s_g = jnp.minimum(fmt.amax / safe_g, _F32_MAX)
    s_b = jnp.minimum(fmt.amax / safe_b, _F32_MAX)

    if algo == "fp32_amax":
        scale = s_b.astype(jnp.float32)
        return GamScales(
            scale=scale,
            group_mantissa=jnp.float32(1.0),
            block_exp=split_mantissa_exponent(s_b)[1],
            group_amax=g_amax.astype(jnp.float32),
        )

    m_b, e_b = split_mantissa_exponent(s_b)
    if algo == "e8m0":
        # Round scale down to a pure power of two -> saturation-free.
        # Clamp matches exp2i's full [-126, 127] domain: clipping at 126
        # (the old off-by-one) halved the scale of tiny-amax blocks a
        # second time for no reason (the "double rounding" bug) --
        # 2^127 is exactly representable and m_g * 2^127 <= f32max
        # since m_g <= 2 - 2^-23.
        e_b = jnp.clip(e_b, -126, 127)
        scale = exp2i(e_b)
        return GamScales(
            scale=scale,
            group_mantissa=jnp.float32(1.0),
            block_exp=e_b,
            group_amax=g_amax.astype(jnp.float32),
        )

    if algo != "gam":
        raise ValueError(f"unknown scaling algo: {algo}")

    m_g, _ = split_mantissa_exponent(s_g)
    # Saturation-prevention rounding (Algorithm 1): if the shared mantissa
    # exceeds this block's ideal mantissa, m_g * 2^{e_b} > s_b would map
    # block_amax above q_amax; drop the exponent by one.
    e_b = jnp.where(m_g <= m_b, e_b, e_b - 1)
    e_b = jnp.clip(e_b, -126, 127)  # exp2i's full domain (see e8m0 note)
    scale = m_g * exp2i(e_b)
    return GamScales(
        scale=scale.astype(jnp.float32),
        group_mantissa=m_g,
        block_exp=e_b,
        group_amax=g_amax.astype(jnp.float32),
    )
