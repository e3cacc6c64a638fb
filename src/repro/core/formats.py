"""Numeric format specifications for MoR (paper §1-2 and the NVFP4
outlook in §5).

E4M3:  4 exponent bits, 3 mantissa bits. Positive range [2^-9, 448]
       (min subnormal to max). No inf; NaN only.
E5M2:  5 exponent bits, 2 mantissa bits. Positive range [2^-16, 57344].
NVFP4: E2M1 4-bit payload (magnitudes {0, 0.5, 1, 1.5, 2, 3, 4, 6})
       with one E4M3 micro-block scale per NVFP4_MICRO=16 contiguous
       elements of the contraction axis, *two-level* with the GAM block
       scale: the block scale targets ``q_amax = 448 * 6 = 2688`` so
       every micro scale ``micro_amax_scaled / 6`` lands inside E4M3's
       finite range (the NVIDIA NVFP4 recipe, with the per-tensor FP32
       scale replaced by the per-block Alg. 1 GAM scale).
BF16:  passthrough (the "original precision" fallback).

FP8 fake quantization (:func:`round_to_fp8`) is round-to-nearest-even
bit arithmetic, bit-identical to the ml_dtypes-backed jnp casts that
real packing uses; we clamp to +-max first so no overflow-to-NaN can
occur (GAM scaling guarantees no saturation anyway -- the clamp is a
safety net and is what real TPU/NV cast units do in saturating mode).
The E2M1 payload has no jnp storage dtype on this jax, so
:func:`round_to_e2m1` implements the RNE grid snap with exact
power-of-two bit arithmetic (validated bit-for-bit against
``ml_dtypes.float4_e2m1fn`` in ``tests/test_nvfp4.py``); the same
formula lowers inside the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "FormatSpec", "E4M3", "E5M2", "BF16", "NVFP4", "FORMATS",
    "cast_to_format", "cast_to_nvfp4", "round_to_e2m1", "round_to_fp8",
    "encode_e2m1", "decode_e2m1",
    "NVFP4_MICRO", "E2M1_AMAX",
]

# NVFP4 micro-block geometry: one E4M3 scale per 16 contiguous elements
# along the contraction (last) axis, E2M1 max magnitude 6.
NVFP4_MICRO = 16
E2M1_AMAX = 6.0


@dataclasses.dataclass(frozen=True)
class FormatSpec:
    """A quantization target format."""

    name: str
    # Largest finite magnitude (q_amax in Algorithm 1).
    amax: float
    # Smallest positive *normal* magnitude (used by Eq. 4's range metric).
    min_normal: float
    # Smallest positive subnormal magnitude.
    min_subnormal: float
    # Storage dtype for the real-quantization path (None => passthrough).
    dtype: Any
    # Number of explicit mantissa bits (relative error of RNE quantization
    # for in-range values is bounded by 2^-(mantissa_bits+1)).
    mantissa_bits: int
    # Bits per element when stored for real.
    bits: int

    @property
    def is_passthrough(self) -> bool:
        return self.dtype is None or self.name == "bf16"

    @property
    def eps(self) -> float:
        """Max relative rounding error for in-range normal values."""
        return 2.0 ** -(self.mantissa_bits + 1)


E4M3 = FormatSpec(
    name="e4m3",
    amax=448.0,
    min_normal=2.0**-6,
    min_subnormal=2.0**-9,
    dtype=jnp.float8_e4m3fn,
    mantissa_bits=3,
    bits=8,
)

E5M2 = FormatSpec(
    name="e5m2",
    amax=57344.0,
    min_normal=2.0**-14,
    min_subnormal=2.0**-16,
    dtype=jnp.float8_e5m2,
    mantissa_bits=2,
    bits=8,
)

BF16 = FormatSpec(
    name="bf16",
    amax=3.3895314e38,
    min_normal=2.0**-126,
    min_subnormal=2.0**-133,
    dtype=None,
    mantissa_bits=7,
    bits=16,
)

# NVFP4's FormatSpec drives the *block-level* GAM scale of the
# two-level scheme: q_amax = E4M3.amax * E2M1_AMAX, so the Alg. 1
# no-saturation invariant (block_amax * scale <= 2688) guarantees every
# per-16-element micro scale (micro_amax_scaled / 6 <= 448) is finite
# in E4M3 without saturation. min_normal/min_subnormal describe the
# E2M1 payload itself (4 binades of magnitudes: 0.5 .. 6).
NVFP4 = FormatSpec(
    name="nvfp4",
    amax=E4M3.amax * E2M1_AMAX,  # 2688.0: two-level block-scale target
    min_normal=1.0,
    min_subnormal=0.5,
    dtype=None,  # sub-byte: packed nibbles, no jnp storage dtype
    mantissa_bits=1,
    bits=4,  # payload bits; +8/16 micro-scale bits per element on top
)

FORMATS = {f.name: f for f in (E4M3, E5M2, BF16, NVFP4)}


def _e2m1_ulp(a: jnp.ndarray) -> jnp.ndarray:
    """Distance between adjacent E2M1 magnitudes at |a| (a in [0, 6]).

    Exact bit arithmetic, no transcendentals: the ulp is 2^{e-1} with
    e = floor(log2(max(a, 1))) read from the f32 exponent field
    (0.5 for the subnormal/first binade, 1 in [2, 4), 2 in [4, 6]).
    """
    a1 = jnp.maximum(a, 1.0)
    bits = jax.lax.bitcast_convert_type(a1.astype(jnp.float32), jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127  # floor(log2 a1): 0, 1 or 2
    return jax.lax.bitcast_convert_type(
        (e - 1 + 127) << 23, jnp.float32
    )


def round_to_e2m1(x: jnp.ndarray) -> jnp.ndarray:
    """RNE snap of f32 ``x`` to the E2M1 grid, saturating at +-6.

    Pure vector bit arithmetic + one ``jnp.round`` (RNE), so the same
    formula runs in XLA and inside the Pallas kernels, and matches
    ``ml_dtypes.float4_e2m1fn`` casts bit-for-bit (tests/test_nvfp4.py).
    """
    a = jnp.minimum(jnp.abs(x.astype(jnp.float32)), E2M1_AMAX)
    ulp = _e2m1_ulp(a)
    mag = jnp.round(a / ulp) * ulp  # a/ulp exact (power-of-two divide)
    return jnp.where(x < 0, -mag, mag)


def encode_e2m1(v: jnp.ndarray) -> jnp.ndarray:
    """E2M1 grid values -> 4-bit codes (sign<<3 | magnitude code).

    ``v`` must already lie on the grid (output of :func:`round_to_e2m1`).
    Magnitude codes: 0..3 = {0, 0.5, 1, 1.5}, 4..7 = {2, 3, 4, 6}.
    Returns int32 in [0, 15] (callers narrow/pack to nibbles).
    """
    m = jnp.abs(v.astype(jnp.float32))
    ulp = _e2m1_ulp(m)
    bits = jax.lax.bitcast_convert_type(
        jnp.maximum(m, 1.0).astype(jnp.float32), jnp.int32
    )
    e = ((bits >> 23) & 0xFF) - 127  # 0, 1, 2
    hi = 4 + 2 * (e - 1) + (m / ulp).astype(jnp.int32) - 2
    code = jnp.where(
        m < 2.0, (m * 2.0).astype(jnp.int32), hi
    )
    sign = (v < 0).astype(jnp.int32)
    return code | (sign << 3)


def decode_e2m1(code: jnp.ndarray) -> jnp.ndarray:
    """4-bit E2M1 codes (int) -> f32 grid values. Select-only
    (kernel-safe); every grid value is exact in bf16 and wider."""
    c = code.astype(jnp.int32)
    m = c & 7
    mag = jnp.where(
        m < 4,
        m.astype(jnp.float32) * 0.5,
        (1.0 + 0.5 * (m & 1).astype(jnp.float32))
        * jnp.where(m >= 6, 4.0, 2.0),
    )
    return jnp.where((c >> 3) == 1, -mag, mag)


def cast_to_nvfp4(xs: jnp.ndarray) -> jnp.ndarray:
    """Two-level NVFP4 fake-quantization of a *block-scaled* array.

    ``xs`` is ``x * scale`` with the GAM block scale targeting
    ``NVFP4.amax`` (so ``|xs| <= 2688`` and every micro scale fits
    E4M3). Along the last axis, per group of ``NVFP4_MICRO`` elements:

        d   = micro_amax(|xs|) / 6          (<= 448 by the invariant)
        d_q = RNE E4M3 round-trip of d      (1.0 for all-zero groups)
        q   = round_to_e2m1(xs / d_q)       (saturating at +-6)
        out = q * d_q                       (same scale domain as xs)

    The last axis is zero-padded to a multiple of NVFP4_MICRO
    internally (zeros quantize exactly), so any block width works; the
    *packed* payload path additionally requires 16-divisible blocks
    (see kernels/ref.py pack_mixed).
    """
    xs = xs.astype(jnp.float32)
    k = xs.shape[-1]
    pad = (-k) % NVFP4_MICRO
    if pad:
        xs = jnp.concatenate(
            [xs, jnp.zeros((*xs.shape[:-1], pad), jnp.float32)], axis=-1
        )
    g = xs.reshape(*xs.shape[:-1], -1, NVFP4_MICRO)
    d = jnp.max(jnp.abs(g), axis=-1, keepdims=True) / E2M1_AMAX
    d_q = cast_to_format(d, E4M3)
    safe_d = jnp.where(d_q > 0, d_q, 1.0)
    out = round_to_e2m1(g / safe_d) * safe_d
    out = out.reshape(*xs.shape[:-1], xs.shape[-1])
    return out[..., :k]


def cast_to_format(x: jnp.ndarray, fmt: FormatSpec) -> jnp.ndarray:
    """Round-trip ``x`` (f32) through ``fmt`` with saturating cast.

    Returns an f32 array carrying the information loss of ``fmt``
    (the paper's fake-quantization primitive, Fig. 4). For BF16 the
    round-trip goes through jnp.bfloat16; for NVFP4 through the
    two-level micro-scaled E2M1 snap (:func:`cast_to_nvfp4` -- ``x``
    is then the block-scaled value, as for the fp8 formats).
    """
    if fmt.name == "nvfp4":
        return cast_to_nvfp4(x)
    if fmt.is_passthrough:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return round_to_fp8(x, fmt)


def round_to_fp8(x: jnp.ndarray, fmt: FormatSpec) -> jnp.ndarray:
    """RNE snap of f32 ``x`` to the fp8 grid of ``fmt``, saturating at
    +-``fmt.amax``; NaN stays NaN.

    Bit arithmetic on the f32 pattern (normal range: round the mantissa
    to ``fmt.mantissa_bits`` bits) plus one ``jnp.round`` on the fixed
    subnormal grid, bit-identical to the saturating ``ml_dtypes`` cast
    (tests/test_core_gam.py). Not ``x.astype(fp8).astype(f32)``: XLA's
    TPU compiler drops a convert pair f32 -> narrower float -> f32 (it
    allows excess precision; measured on a TPU v5e for fp8 and bf16), so
    the XLA lowering would not quantize at all on the chip.
    """
    x = jnp.clip(x.astype(jnp.float32), -fmt.amax, fmt.amax)
    shift = 23 - fmt.mantissa_bits
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32((1 << (shift - 1)) - 1) + ((bits >> shift) & 1)
    normal = jax.lax.bitcast_convert_type(
        bits & jnp.uint32((0xFFFFFFFF << shift) & 0xFFFFFFFF), jnp.float32
    )
    sub = jnp.round(x / fmt.min_subnormal) * fmt.min_subnormal
    out = jnp.where(jnp.abs(x) < fmt.min_normal, sub, normal)
    return jnp.where(jnp.isnan(x), x, out)
