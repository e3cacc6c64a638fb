"""Fused GAM quantize kernel (Pallas, TPU target).

One VMEM-resident pass per 128x128 scale block: block amax -> GAM scale
reconstruction (shared group mantissa + per-block E8M0 exponent, Alg. 1)
-> saturating cast -> dequant -> per-block relative-error sums. On TPU
this replaces the ~6 HBM passes of the XLA lowering (see §Perf).

Exponent/mantissa arithmetic uses integer bit manipulation only (Mosaic
has no frexp); `exp2i` is an exponent-field bitcast, exactly as in
repro.core.gam.

Grid: (M/tm, K/tk). Each grid step moves one (tm, tk) tile holding
many (bm, bk) scale blocks (`tile_for`: the largest tile that divides
the operand and fits the VMEM budget, one block where nothing larger
does) and quantizes its blocks one at a time: a loop over the tile's
block rows, the block columns unrolled. A grid step has a fixed cost:
with one 128x128 block a step it was a quarter of the kernel's time on
a TPU v5e.
The group (tensor) mantissa is computed outside the kernel from the
global amax (one cheap XLA reduce) and broadcast in as a (1, 1) block.

`gam_quant_groups` is the same pass over an operand whose rows hold
several tensors (the rows routed to each expert of an MoE layer, each
group starting on a block row): every block row takes its own group's
mantissa, and row tiles past the last live block row are not read or
quantized or written, so the kernel's work follows the live rows and
not the operand's static bound. It runs in two passes: the first reads
the live rows for the blocks' error sums (from which each group takes
its decision), the second fake-quantizes the live rows of the groups
that accept the low-precision format, in place, so that no second
buffer of the operand's bound is needed.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gam_quant_blocks", "gam_quant_groups", "tile_for"]

_F32_MAX = 3.4028235e38  # finfo(f32).max

# VMEM for the double-buffered input and output tiles: half of the 16 MiB
# a v5e kernel may use by default, the rest for one block's f32
# temporaries (a (1024, 2048) bf16 tile, 16 MiB of buffers, is refused).
VMEM_TILE_BUDGET = 8 << 20
# Scale blocks along K in one tile; each is a copy of the block body.
_MAX_TILE_COLS = 16


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


def _quant_block(x_ref, out_ref, rows, cols, m_g, *, q_amax: float,
                 out_dtype, algo: str):
    """Quantize the (bm, bk) block of the tile at (rows, cols) into
    ``out_ref`` (None: write nothing); returns its (exponent, error sum,
    non-zero count) as scalars."""
    x = x_ref[rows, cols].astype(jnp.float32)

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
    xq_stored = xq.astype(x_ref.dtype)  # the output keeps x's type
    xq = xq_stored.astype(jnp.float32)

    nz = x != 0.0
    rel = jnp.where(nz, jnp.abs((x - xq) / jnp.where(nz, x, 1.0)), 0.0)

    if out_ref is not None:
        out_ref[rows, cols] = xq_stored
    return (e_b[0, 0].astype(jnp.int32), jnp.sum(rel),
            jnp.sum(nz.astype(jnp.float32)))


def _kernel(mg_ref, x_ref, out_ref, exp_ref, err_ref, cnt_ref,
            *, block, **quant):
    bm, bk = block
    tm, tk = x_ref.shape
    i0 = pl.program_id(0) * (tm // bm)
    j0 = pl.program_id(1) * (tk // bk)
    m_g = mg_ref[0, 0]

    def block_row(r, carry):
        rows = pl.ds(pl.multiple_of(r * bm, bm), bm)
        for c in range(tk // bk):
            e, err, cnt = _quant_block(
                x_ref, out_ref, rows, pl.ds(c * bk, bk), m_g, **quant
            )
            # The (nm, nk) stat outputs live whole in SMEM across the
            # grid (TPU tiling forbids (1, 1) VMEM blocks and VMEM
            # rejects scalar stores); each block writes its own cell.
            exp_ref[i0 + r, j0 + c] = e
            err_ref[i0 + r, j0 + c] = err
            cnt_ref[i0 + r, j0 + c] = cnt
        return carry

    jax.lax.fori_loop(0, tm // bm, block_row, 0)


def _divisors(n: int, most: int):
    return [d for d in range(1, min(n, most) + 1) if n % d == 0]


def tile_for(shape, block, dtype) -> Tuple[int, int]:
    """The (tm, tk) tile of one grid step for an operand of ``shape`` and
    ``dtype`` in ``block`` scale blocks: the largest whole number of
    blocks that divides the operand, keeps the TPU's (8, 128) tiling
    (or spans the dimension), holds at most ``_MAX_TILE_COLS`` blocks
    along K, and whose double-buffered input and output tiles fit
    ``VMEM_TILE_BUDGET``. Of equal sizes the widest wins (longer DMA
    rows). One block where nothing larger fits."""
    M, K = shape
    bm, bk = block
    nm, nk = M // bm, K // bk
    per_elt = 2 * 2 * jnp.dtype(dtype).itemsize  # in + out, two buffers
    best = (1, 1)
    for a in _divisors(nm, nm):
        tm = a * bm
        if tm % 8 and tm != M:
            continue
        for b in _divisors(nk, _MAX_TILE_COLS):
            tk = b * bk
            if tk % 128 and tk != K:
                continue
            if tm * tk * per_elt > VMEM_TILE_BUDGET:
                continue
            best = max(best, (a, b), key=lambda t: (t[0] * t[1], t[1]))
    return best[0] * bm, best[1] * bk


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

    tm, tk = tile_for(x.shape, block, x.dtype)
    kernel = functools.partial(
        _kernel, block=block, q_amax=q_amax, out_dtype=fmt_dtype, algo=algo
    )
    out_shapes = (
        jax.ShapeDtypeStruct((M, K), x.dtype),
        jax.ShapeDtypeStruct((nm, nk), jnp.int32),
        jax.ShapeDtypeStruct((nm, nk), jnp.float32),
        jax.ShapeDtypeStruct((nm, nk), jnp.float32),
    )
    return pl.pallas_call(
        kernel,
        grid=(M // tm, K // tk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),  # group mantissa
            pl.BlockSpec((tm, tk), lambda i, j: (i, j)),  # x tile (VMEM)
        ],
        out_specs=[
            pl.BlockSpec((tm, tk), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=out_shapes,
        interpret=interpret,
        name="gam_quant_blocks",
    )(mg, x)


def _group_err_kernel(live_ref, mg_ref, x_ref, err_ref, cnt_ref, *, block,
                      nk, **quant):
    bm, bk = block
    tm, tk = x_ref.shape
    i0 = pl.program_id(0) * (tm // bm)
    j0 = pl.program_id(1) * (tk // bk)

    @pl.when(i0 < live_ref[0])
    def _():
        def block_row(r, carry):
            rows = pl.ds(pl.multiple_of(r * bm, bm), bm)
            m_g = mg_ref[i0 + r]
            for c in range(tk // bk):
                _, err, cnt = _quant_block(
                    x_ref, None, rows, pl.ds(c * bk, bk), m_g, **quant
                )
                err_ref[(i0 + r) * nk + j0 + c] = err
                cnt_ref[(i0 + r) * nk + j0 + c] = cnt
            return carry

        jax.lax.fori_loop(0, tm // bm, block_row, 0)


def _group_apply_kernel(live_ref, mg_ref, keep_ref, x_ref, out_ref, *,
                        block, **quant):
    bm, bk = block
    tm, tk = x_ref.shape
    i0 = pl.program_id(0) * (tm // bm)

    @pl.when(i0 < live_ref[0])
    def _():
        def block_row(r, carry):
            rows = pl.ds(pl.multiple_of(r * bm, bm), bm)

            @pl.when(keep_ref[i0 + r] != 0)
            def _():
                for c in range(tk // bk):
                    _quant_block(x_ref, out_ref, rows, pl.ds(c * bk, bk),
                                 mg_ref[i0 + r], **quant)

            @pl.when(keep_ref[i0 + r] == 0)
            def _():
                out_ref[rows, :] = x_ref[rows, :]

            return carry

        jax.lax.fori_loop(0, tm // bm, block_row, 0)


def _live_tile_index(per_tile: int, last_j: int):
    def index(i, j, live, *_):
        # Steps past the live rows stay on the last live tile: the
        # pipeline neither fetches nor writes back a tile again.
        last = jnp.maximum(live[0] - 1, 0) // per_tile
        dead = i > last
        return (jnp.where(dead, last, i), jnp.where(dead, last_j, j))

    return index


@functools.partial(
    jax.jit,
    static_argnames=("block", "q_amax", "fmt_dtype", "algo", "interpret"),
)
def gam_quant_groups(
    x: jnp.ndarray,
    row_mantissa: jnp.ndarray,
    live_rows: jnp.ndarray,
    keep: Optional[jnp.ndarray] = None,
    *,
    block: Tuple[int, int] = (128, 128),
    q_amax: float = 448.0,
    fmt_dtype=jnp.float8_e4m3fn,
    algo: str = "gam",
    interpret: bool = False,
):
    """x: (M, K) with M % bm == 0, K % bk == 0; ``row_mantissa``: (M/bm,)
    f32, the group mantissa of each block row; ``live_rows``: () i32, how
    many block rows from the first on hold rows of a group. Past the
    live block rows nothing is read or written.

    Without ``keep``, the error pass: returns (err_sums (nm, nk) f32,
    counts (nm, nk) f32), zero past the live rows, and writes no rows.
    With ``keep`` (nm,), the apply pass: returns x with each live block
    row fake-quantized where ``keep`` is set and left as it is elsewhere,
    written over x in place (the rows past the live ones are x's).
    """
    M, K = x.shape
    bm, bk = block
    assert M % bm == 0 and K % bk == 0, (x.shape, block)
    nm, nk = M // bm, K // bk
    tm, tk = tile_for(x.shape, block, x.dtype)
    quant = dict(q_amax=q_amax, out_dtype=fmt_dtype, algo=algo)
    live = jnp.reshape(live_rows.astype(jnp.int32), (1,))
    mg = row_mantissa.astype(jnp.float32)
    if keep is not None:
        # Whole rows a step: a block row left as it is is one store.
        tm = tile_for(x.shape, (bm, K), x.dtype)[0]
        tk = K
        index = _live_tile_index(tm // bm, 0)
        return pl.pallas_call(
            functools.partial(_group_apply_kernel, block=block, **quant),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(M // tm, 1),
                in_specs=[pl.BlockSpec((tm, tk), index)],
                out_specs=pl.BlockSpec((tm, tk), index),
            ),
            out_shape=jax.ShapeDtypeStruct((M, K), x.dtype),
            input_output_aliases={3: 0},
            interpret=interpret,
            name="gam_group_quant",
        )(live, mg, keep.astype(jnp.int32), x)
    index = _live_tile_index(tm // bm, K // tk - 1)
    err, cnt = pl.pallas_call(
        functools.partial(_group_err_kernel, block=block, nk=nk, **quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, K // tk),
            in_specs=[pl.BlockSpec((tm, tk), index)],
            out_specs=[
                # Flat: a 2-D SMEM array pads its last axis to 128 words.
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nm * nk,), jnp.float32),
            jax.ShapeDtypeStruct((nm * nk,), jnp.float32),
        ),
        interpret=interpret,
        name="gam_group_err",
    )(live, mg, x)
    # The SMEM sums of blocks that no step visited hold whatever was
    # there before.
    row_live = (jnp.arange(nm) < live[0])[:, None]
    zero = jnp.float32(0.0)
    return (jnp.where(row_live, err.reshape(nm, nk), zero),
            jnp.where(row_live, cnt.reshape(nm, nk), zero))
