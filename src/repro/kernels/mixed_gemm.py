"""Mixed-representation block GEMM kernel (Pallas, TPU target).

C = A @ B^T where both operands arrive in their *quantization view*
(rows x contraction, blocks aligned with the dot-product direction,
paper §3.1) and every (bm, bk) block carries its own representation tag
and GAM scale -- the per-block decisions of the fused ``mor_select``
kernel finally reach the matmul instead of being erased by a
dequantize-then-bf16-GEMM round trip.

Tri-lane payload layout (see ``kernels/README.md``):

  * ``payload_q``   (R, K) uint8   -- raw fp8 bits. E4M3 bit patterns for
    tag 0 blocks, E5M2 bit patterns for tag 1 blocks, zero (don't-care)
    for other tags. One byte per element regardless of which fp8
    format the block chose, so the buffer is a single dense array.
  * ``payload_bf16``(R, K) bf16    -- original values for tag 2 (BF16
    passthrough) blocks, zero (don't-care) elsewhere.
  * ``payload_nib`` (R/2, K) uint8 -- packed E2M1 nibbles for tag 3
    (NVFP4) blocks: within block (i, j), byte row r carries logical row
    r in its low nibble and row r + br/2 in its high nibble (row-halves
    packing -- decode is two vector nibble extracts + one sublane
    concat, no lane interleave).
  * ``micro_scales``(R, K/16) uint8 -- E4M3 bits of the NVFP4
    per-16-element micro scales.

Per (bm, bk) block the kernel bitcasts the uint8 payload to *both* fp8
dtypes, decodes the E2M1 nibbles arithmetically straight to the storage
dtype (every grid value and every vals*micro-scale product is exact in
bf16, so no f32 staging is needed) and expands the micro scales with an
exact one-hot f32 matmul, selects by tag, divides by the block's
reconstructed GAM scale, rounds to the stored dtype (Fig. 4: stored
values are BF16 -- this makes the fused GEMM consume exactly the
fake-quantization values of the training path), and upcasts to f32 for
the MXU. Accumulation is f32 in a VMEM scratch tile over the K grid
dimension (innermost, 'arbitrary').

Decode amortization: the naive (i, j, k) grid re-decodes A block
(i, k) once per N tile -- n_n times. Two static counter-measures,
chosen by ``ops.mixed_gemm``'s autotune table:

  * ``decode_cache`` -- a (n_k, bm, bk) f32 VMEM scratch keyed on the
    k step: the A stripe is decoded once per (i, k) (at j == 0) and
    re-read from VMEM for every other j. The j dimension demotes to
    'arbitrary' so the sweep order is guaranteed.
  * ``bn_mult`` -- the wider-bn fallback when the cache would not fit
    VMEM: one kernel step covers ``bn_mult`` B row blocks (each decoded
    with its own tag/scale cell), cutting A re-decodes by the same
    factor with no extra scratch.

Both are bit-exact: the cache replays identical decoded values, and a
wider N tile only concatenates B slabs whose per-output-element FMA
order is unchanged.

Tags (0 = E4M3, 1 = E5M2, 2 = BF16, 3 = NVFP4) and scales are (nr, nk)
arrays that live whole in SMEM; each grid step reads its own cells.
Selection by tag is a vectorized ``where`` over in-register candidates
-- no divergent control flow, which Mosaic would reject anyway.

Grid: (R_a/bm, R_b/(bn*bn_mult), K/bk).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import NVFP4_MICRO, decode_e2m1

from .ref import (
    TAG_BF16,
    TAG_E5M2,
    TAG_NVFP4,
    _ms_compact_shape,
    _nib_compact_shape,
    expand_micro_onehot,
    nvfp4_block_capable,
)

__all__ = ["mixed_gemm_blocks", "DECODE_CACHE_BUDGET", "decode_cache_bytes"]

# VMEM budget for the k-keyed A-decode cache (f32 stripes); past this
# the autotune falls back to the wider-bn sweep. ~4 MiB leaves room for
# the payload blocks + accumulator in the ~16 MiB/core VMEM.
DECODE_CACHE_BUDGET = 4 * 1024 * 1024


def decode_cache_bytes(n_k: int, bm: int, bk: int) -> int:
    """Bytes of the (n_k, bm, bk) f32 decoded-A VMEM cache."""
    return n_k * bm * bk * 4


def _decode(q, bf, nib, ms, tag, scale, has_nv: bool, g0=0):
    """One block's payload lane values -> f32 stored values."""
    st_dtype = bf.dtype
    q4 = jax.lax.bitcast_convert_type(
        q, jnp.float8_e4m3fn
    ).astype(jnp.float32)
    q5 = jax.lax.bitcast_convert_type(
        q, jnp.float8_e5m2
    ).astype(jnp.float32)
    # Stored-value semantics (Fig. 4): the dequantized fp8 value is
    # rounded to the storage dtype before entering the matmul, exactly
    # like the fake-quantization path.
    f8 = (jnp.where(tag == TAG_E5M2, q5, q4) / scale).astype(st_dtype)
    out = jnp.where(tag == TAG_BF16, bf, f8)
    if has_nv:
        # Unpack row-halved E2M1 nibbles and narrow them to the storage
        # dtype (grid values and the vals * micro-scale products are
        # exact in bf16 -- <= 5 significand bits), expand micro scales,
        # apply the two-level dequant. The nibbles decode in f32: Mosaic
        # cannot relayout the selects of a bf16 decode of a half-height
        # nibble block. The only f32 step left is the final division by
        # the block scale, whose 23-bit mantissa a bf16 divide could
        # double-round -- same op order as ref.decode_mixed_ref after
        # the exact-cast steps, so interpret/xla stay bit-exact.
        n32 = nib.astype(jnp.int32)
        lo = decode_e2m1(n32 & 15)
        hi = decode_e2m1(n32 >> 4)
        vals = jnp.concatenate([lo, hi], axis=0).astype(st_dtype)
        d = jax.lax.bitcast_convert_type(
            ms, jnp.float8_e4m3fn
        ).astype(jnp.float32)
        d_exp = expand_micro_onehot(d, vals.shape[-1], g0).astype(
            st_dtype
        )
        nv = ((vals * d_exp).astype(jnp.float32) / scale).astype(
            st_dtype
        )
        out = jnp.where(tag == TAG_NVFP4, nv, out)
    return out.astype(jnp.float32)


def _kernel(a_tag_ref, a_sc_ref, b_tag_ref, b_sc_ref,
            a_q_ref, a_bf_ref, a_nib_ref, a_ms_ref,
            b_q_ref, b_bf_ref, b_nib_ref, b_ms_ref, o_ref, acc_ref,
            *cache,
            n_k: int, g16: int, a_has_nv: bool, b_has_nv: bool,
            bn: int, bn_mult: int, b_dense: Tuple[bool, ...]):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Micro-scale stripes ride whole along the contraction axis; the
    # one-hot expansion selects grid step k's group window.
    def decode_a():
        return _decode(
            a_q_ref[...], a_bf_ref[...], a_nib_ref[...], a_ms_ref[...],
            a_tag_ref[i, k], a_sc_ref[i, k], a_has_nv, k * g16,
        )

    if cache:
        # Decode-once cache: the A stripe for this (i, k) is decoded at
        # the first N tile and replayed from VMEM for every other j
        # (the j grid dim is 'arbitrary', so the sweep order holds).
        a_cache_ref = cache[0]

        @pl.when(j == 0)
        def _():
            a_cache_ref[k] = decode_a()

        a = a_cache_ref[k]
    else:
        a = decode_a()

    qd, bfd, nibd, msd = b_dense

    def slab(ref, rows, s, dense):
        # A compact lane's pinned single block serves every sub-tile;
        # dense lanes carve the sub-tile's rows out of the wide block.
        if not dense or bn_mult == 1:
            return ref[...]
        return ref[s * rows:(s + 1) * rows, :]

    slabs = []
    for s in range(bn_mult):
        jj = j * bn_mult + s
        slabs.append(_decode(
            slab(b_q_ref, bn, s, qd),
            slab(b_bf_ref, bn, s, bfd),
            slab(b_nib_ref, bn // 2, s, nibd),
            slab(b_ms_ref, bn, s, msd),
            b_tag_ref[jj, k], b_sc_ref[jj, k], b_has_nv, k * g16,
        ))
    b = slabs[0] if bn_mult == 1 else jnp.concatenate(slabs, axis=0)
    # A (bm, bk) contracted with B (bn*bn_mult, bk) on the K axis:
    # C = A @ B^T.
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block", "out_dtype", "interpret", "a_has_nvfp4", "b_has_nvfp4",
        "decode_cache", "bn_mult",
    ),
)
def mixed_gemm_blocks(
    a_q: jnp.ndarray,
    a_bf: jnp.ndarray,
    a_nib: jnp.ndarray,
    a_ms: jnp.ndarray,
    a_tags: jnp.ndarray,
    a_scales: jnp.ndarray,
    b_q: jnp.ndarray,
    b_bf: jnp.ndarray,
    b_nib: jnp.ndarray,
    b_ms: jnp.ndarray,
    b_tags: jnp.ndarray,
    b_scales: jnp.ndarray,
    *,
    block: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
    a_has_nvfp4: bool | None = None,
    b_has_nvfp4: bool | None = None,
    decode_cache: bool | None = None,
    bn_mult: int = 1,
) -> jnp.ndarray:
    """a: (M, K)/(M/2, K)/(M, K/16) tri-lane payloads + (M/bm, K/bk)
    tags/scales; b: (N, K) quantization view (contraction last)
    likewise.

    Any payload lane of an operand may be *compact* -- a single
    don't-care block (see ``ref.MixedOperand.compact``) -- in which
    case its BlockSpec pins index (0, 0): the block stays VMEM-resident
    and contributes no per-step HBM traffic. The NVFP4 decode is
    skipped entirely (statically) when the ``{a,b}_has_nvfp4`` hint
    says no TAG_NVFP4 block exists (``MixedOperand.has_nvfp4``), when
    an operand's block geometry cannot hold NVFP4, or -- hint-less
    legacy callers -- when both sub-byte lanes are compact (for a
    single-block operand the compact and full shapes coincide, so only
    the hint can prove the lane dead).

    ``decode_cache`` (None = auto: on when the (n_k, bm, bk) f32 cache
    fits :data:`DECODE_CACHE_BUDGET` and more than one N tile exists)
    decodes each A stripe once per (i, k); ``bn_mult`` widens the N
    tile to ``bn_mult`` B blocks per step (the fallback when the cache
    would not fit). Both preserve bit-exactness; see the module
    docstring.

    Returns (M, N) = A @ B^T in out_dtype, f32-accumulated.
    """
    bm, bn, bk = block
    n_m, n_k = a_tags.shape
    n_n, n_k2 = b_tags.shape
    assert n_k == n_k2, (a_tags.shape, b_tags.shape)
    assert n_n % bn_mult == 0, (b_tags.shape, bn_mult)
    M, N, K = n_m * bm, n_n * bn, n_k * bk
    n_j = n_n // bn_mult

    def payload_spec(buf, compact_shape, blk_shape, idx):
        if buf.shape == compact_shape:  # compact: one shared block
            return pl.BlockSpec(compact_shape, lambda i, j, k: (0, 0))
        return pl.BlockSpec(blk_shape, idx)

    assert a_q.shape in ((M, K), (bm, bk)), (a_q.shape, (M, K), block)
    assert a_bf.shape in ((M, K), (bm, bk)), (a_bf.shape, (M, K), block)
    assert b_q.shape in ((N, K), (bn, bk)), (b_q.shape, (N, K), block)
    assert b_bf.shape in ((N, K), (bn, bk)), (b_bf.shape, (N, K), block)

    def nib_spec(buf, br, mult, idx):
        return payload_spec(
            buf, _nib_compact_shape((br, bk)), (mult * br // 2, bk), idx
        )

    def ms_spec(buf, br, mult, row_idx):
        # Micro-scale stripes ride whole along the contraction axis:
        # their (K/16) lane count is not 128-divisible, and TPU tiling
        # only accepts a non-divisible lane dim when it equals the
        # whole array's (the kernel windows the stripe per grid step).
        if buf.shape == _ms_compact_shape((br, bk)):
            return pl.BlockSpec(buf.shape, lambda i, j, k: (0, 0))
        return pl.BlockSpec(
            (mult * br, buf.shape[-1]),
            lambda i, j, k: (row_idx(i, j, k), 0),
        )

    def has_nv(br, n_r, nib, ms, hint):
        if not nvfp4_block_capable((br, bk)):
            return False
        if hint is not None:
            # The pack layer knows: packs built without the NVFP4
            # lanes, passthrough/transposed packs and compacted packs
            # with no TAG_NVFP4 all skip the decode outright -- this is
            # what resolves the single-block ambiguity below.
            return bool(hint)
        # Legacy heuristic: decode when the operand carries full
        # (dense) sub-byte buffers. For a single-block operand the
        # full and compact shapes coincide -- decode then too (a truly
        # compact don't-care lane has no TAG_NVFP4 to select it, so
        # the extra work is dead but correct).
        full_nib = (n_r * (br // 2), n_k * bk)
        full_ms = (n_r * br, n_k * bk // NVFP4_MICRO)
        return nib.shape == full_nib or tuple(ms.shape) == full_ms

    a_has_nv = has_nv(bm, n_m, a_nib, a_ms, a_has_nvfp4)
    b_has_nv = has_nv(bn, n_n, b_nib, b_ms, b_has_nvfp4)

    if decode_cache is None:
        decode_cache = (
            n_j > 1
            and decode_cache_bytes(n_k, bm, bk) <= DECODE_CACHE_BUDGET
        )

    b_dense = (
        b_q.shape == (N, K),
        b_bf.shape == (N, K),
        tuple(b_nib.shape) == (N // 2, K),
        tuple(b_ms.shape) == (N, K // NVFP4_MICRO),
    )
    kernel = functools.partial(
        _kernel, n_k=n_k, g16=bk // NVFP4_MICRO if a_has_nv or b_has_nv
        else 0, a_has_nv=a_has_nv, b_has_nv=b_has_nv, bn=bn,
        bn_mult=bn_mult, b_dense=b_dense,
    )
    a_idx = lambda i, j, k: (i, k)  # noqa: E731
    b_idx = lambda i, j, k: (j, k)  # noqa: E731
    scratch_shapes = [pltpu.VMEM((bm, bn * bn_mult), jnp.float32)]
    if decode_cache:
        scratch_shapes.append(pltpu.VMEM((n_k, bm, bk), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(n_m, n_j, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # a_tags (nm, nk)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # a_scales (nm, nk)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # b_tags (nn, nk)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # b_scales (nn, nk)
            payload_spec(a_q, (bm, bk), (bm, bk), a_idx),
            payload_spec(a_bf, (bm, bk), (bm, bk), a_idx),
            nib_spec(a_nib, bm, 1, a_idx),
            ms_spec(a_ms, bm, 1, lambda i, j, k: i),
            payload_spec(b_q, (bn, bk), (bn_mult * bn, bk), b_idx),
            payload_spec(b_bf, (bn, bk), (bn_mult * bn, bk), b_idx),
            nib_spec(b_nib, bn, bn_mult, b_idx),
            ms_spec(b_ms, bn, bn_mult, lambda i, j, k: j),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn * bn_mult), lambda i, j, k: (i, j)
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel",
                # The A-decode cache is filled at j == 0 and replayed
                # across the N sweep: j must stay sequential then.
                "arbitrary" if decode_cache else "parallel",
                "arbitrary",
            )
        ),
        interpret=interpret,
        name="mixed_gemm_blocks",
    )(a_tags, a_scales, b_tags, b_scales,
      a_q, a_bf, a_nib, a_ms, b_q, b_bf, b_nib, b_ms)
