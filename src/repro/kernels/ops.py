"""Jitted public wrappers for the Pallas kernels with backend dispatch.

backend='auto' uses the Pallas kernels on TPU and interpret mode under
REPRO_KERNEL_INTERPRET=1 (CI/CPU validation); otherwise falls back to the
pure-jnp reference path so the library works everywhere.

This module is the single quantization entry point for the MoR recipes:
``repro.core.mor`` routes every quantization event through
:func:`quant_err` (tensor-level / static recipes) and :func:`mor_select`
(sub-tensor recipes), so the Pallas kernels and the XLA lowering can
never drift apart (the refs in :mod:`repro.kernels.ref` ARE the XLA
path). See ``src/repro/kernels/README.md`` for the dispatch matrix.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.collectives import pmax_over, shard_map_unchecked
from repro.core.formats import E4M3, E5M2, NVFP4, NVFP4_MICRO, FormatSpec
from repro.core.gam import split_mantissa_exponent
from repro.core.metrics import E5M2_RANGE_RATIO, NVFP4_RANGE_RATIO
from repro.core.partition import Partition, _pad2d

from . import ref as _ref
from .flash_attention import flash_attention_fwd
from .fp8_gemm import fp8_gemm as _fp8_gemm_kernel
from .gam_quant import gam_quant_blocks, gam_quant_groups
from .mixed_gemm import (
    DECODE_CACHE_BUDGET,
    decode_cache_bytes,
    mixed_gemm_blocks,
)
from .mor_select import mor_select_blocks
from .ref import MixedOperand, MorSelect, QuantErr

__all__ = [
    "gam_quant",
    "quant_err",
    "quant_err_groups",
    "quantize_groups",
    "mor_select",
    "quantize_pack",
    "fp8_gemm",
    "mixed_gemm",
    "mixed_dot",
    "sharded_mixed_gemm",
    "flash_attention",
    "resolve_backend",
    "GemmTile",
    "gemm_tile_for",
    "register_gemm_tile",
    "register_decode_tiles",
    "decode_row_block",
    "QuantErr",
    "MorSelect",
    "MixedOperand",
]


def resolve_backend(backend: str = "auto") -> str:
    """'auto' -> 'pallas' on TPU, 'interpret' under
    REPRO_KERNEL_INTERPRET=1, 'xla' otherwise. Interpret mode is a CPU
    validation mode: asking for it (by name or through the variable)
    while the default backend is a TPU raises, so kernels never run
    interpreted on the chip unnoticed."""
    if backend not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(
            f"unknown backend: {backend!r} "
            "(want 'auto', 'pallas', 'interpret', or 'xla')"
        )
    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto" and os.environ.get("REPRO_KERNEL_INTERPRET") == "1":
        backend = "interpret"
    if backend == "interpret" and on_tpu:
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend (unset "
            "REPRO_KERNEL_INTERPRET or pass backend='pallas'/'xla')"
        )
    if backend != "auto":
        return backend
    return "pallas" if on_tpu else "xla"


def _kernel_backend(backend: str, part: Partition) -> str:
    """Backend for a recipe-level event, demoting kernel-hostile layouts.

    The fused kernels tile the operand as (bm, bk) VMEM blocks; 'channel'
    and 'subchannel' partitions resolve to (1, k) rows, which defeats the
    (8, 128) VPU tiling, and 'tensor' resolves to one whole-operand block
    that can overflow the ~16 MB of VMEM per core -- those events always
    take the XLA lowering.
    """
    be = resolve_backend(backend)
    if be != "xla" and part.kind in ("tensor", "channel", "subchannel"):
        return "xla"
    return be


def _group_amax(x: jnp.ndarray, mesh_axes=()):
    """(g_amax, zero-guarded g_amax): one global XLA reduce, allreduced
    over ``mesh_axes`` when the operand is a shard_map shard -- the
    group amax (and the Alg. 1 mantissa derived from it) must be the
    amax of the *whole* tensor, not of this device's shard."""
    g_amax = pmax_over(
        jnp.max(jnp.abs(x.astype(jnp.float32))), mesh_axes
    )
    # Zero guard AND nonfinite guard: an Inf amax would otherwise pass
    # straight into the Alg. 1 mantissa (Inf > 0 is True) and poison
    # the scales of *every* block, clean ones included. Sanitizing to
    # 1.0 keeps clean blocks' per-block scales finite while the
    # poisoned blocks fall through to the BF16 arm; the raw g_amax is
    # still returned first so the stats guard lanes see the event.
    safe = jnp.where((g_amax > 0) & jnp.isfinite(g_amax), g_amax, 1.0)
    return g_amax, safe


def _group_mantissa(safe_g: jnp.ndarray, fmt: FormatSpec, algo: str):
    """The Alg. 1 shared mantissa m_g (1.0 for the ablation algos)."""
    if algo != "gam":
        return jnp.float32(1.0)
    m_g, _ = split_mantissa_exponent(
        jnp.minimum(fmt.amax / safe_g, jnp.finfo(jnp.float32).max)
    )
    return m_g


def quant_err(
    x: jnp.ndarray,
    part: Partition,
    fmt: FormatSpec = E4M3,
    algo: str = "gam",
    *,
    backend: str = "auto",
    mesh_axes=(),
) -> QuantErr:
    """Fused quantize + per-block error sums of a 2-D operand.

    Backend-dispatched core of the 'tensor' and 'e4m3' recipes. Handles
    block-non-divisible shapes by zero-padding (zeros quantize exactly
    and are excluded from the error sums/counts by construction).
    ``mesh_axes``: shard_map axes to allreduce the group amax over
    (x is then this device's shard; returned err_sums/counts stay
    shard-local, ``group_amax``/``group_mantissa`` are global).
    """
    be = _kernel_backend(backend, part)
    if be == "xla":
        return _ref.quant_err_ref(x, part, fmt, algo, mesh_axes=mesh_axes)
    M, K = x.shape
    bm, bk = part.resolve(x.shape)
    xp = _pad2d(x, bm, bk)
    g_amax, safe_g = _group_amax(x, mesh_axes)
    m_g = _group_mantissa(safe_g, fmt, algo)
    xq, _, err_sums, counts = gam_quant_blocks(
        xp, m_g,
        block=(bm, bk), q_amax=fmt.amax, fmt_dtype=fmt.dtype, algo=algo,
        interpret=(be == "interpret"),
    )
    return QuantErr(
        y=xq[:M, :K],
        err_sums=err_sums,
        counts=counts,
        group_amax=g_amax,
        group_mantissa=m_g,
    )


def quant_err_groups(
    x: jnp.ndarray,
    part: Partition,
    fmt: FormatSpec,
    algo: str,
    row_amax: jnp.ndarray,
    live_rows: jnp.ndarray,
    *,
    backend: str = "auto",
):
    """The error pass of :func:`quant_err` over an operand whose rows
    hold several tensors, each starting on a block row.

    ``x`` (M, K) has M a whole multiple of the block rows (K is padded
    with zero columns, as in :func:`quant_err`); ``row_amax`` (nm,) is
    the amax of the group (tensor) each block row belongs to and
    ``live_rows`` () how many block rows from the first hold a group.
    The kernel reads only those, and writes no rows. Returns (err_sums
    (nm, nk), counts (nm, nk), group mantissa of each block row (nm,)),
    the sums zero past the live rows.
    """
    be = resolve_backend(backend)
    bm, bk = part.resolve(x.shape)
    xp = _pad2d(x, bm, bk)  # zero columns: no error, no count
    if be == "xla":
        _, err, cnt, m_g = _ref.quant_err_groups_ref(
            xp, part, fmt, algo, row_amax
        )
        return err, cnt, m_g
    m_g = _row_mantissa(row_amax, fmt, algo)
    err, cnt = gam_quant_groups(
        xp, m_g, live_rows,
        block=(bm, bk), q_amax=fmt.amax, fmt_dtype=fmt.dtype,
        algo=algo, interpret=(be == "interpret"),
    )
    return err, cnt, m_g


def quantize_groups(
    x: jnp.ndarray,
    part: Partition,
    fmt: FormatSpec,
    algo: str,
    row_amax: jnp.ndarray,
    keep: jnp.ndarray,
    live_rows: jnp.ndarray,
    *,
    backend: str = "auto",
) -> jnp.ndarray:
    """The apply pass after :func:`quant_err_groups`: ``x`` with each
    block row fake-quantized (scaled by its group's amax) where ``keep``
    (nm,) is set and left as it is elsewhere. The kernel writes over
    ``x`` in place and only its live rows."""
    be = resolve_backend(backend)
    K = x.shape[1]
    bm, bk = part.resolve(x.shape)
    xp = _pad2d(x, bm, bk)
    if be == "xla":
        y = _ref.quant_err_groups_ref(xp, part, fmt, algo, row_amax)[0]
        rows = jnp.repeat(keep, bm, total_repeat_length=xp.shape[0])
        return jnp.where(rows[:, None], y, xp)[:, :K]
    y = gam_quant_groups(
        xp, _row_mantissa(row_amax, fmt, algo), live_rows, keep,
        block=(bm, bk), q_amax=fmt.amax, fmt_dtype=fmt.dtype,
        algo=algo, interpret=(be == "interpret"),
    )
    return y[:, :K]


def _row_mantissa(row_amax, fmt: FormatSpec, algo: str):
    safe = jnp.where((row_amax > 0) & jnp.isfinite(row_amax), row_amax, 1.0)
    return jnp.broadcast_to(_group_mantissa(safe, fmt, algo), safe.shape)


def _select_kernel_call(x, block, mode, algo, emit, be, mesh_axes):
    """Shared prologue + launch for both selection entry points: pad,
    one global amax reduce (allreduced when sharded), the per-format
    Alg. 1 mantissas, and the kernel call. One definition so the
    fake-quant and pack-emitting paths can never drift on scaling
    inputs. Returns (kernel outputs, group_amax, E4M3 mantissa)."""
    bm, bk = block
    xp = _pad2d(x, bm, bk)
    g_amax, safe_g = _group_amax(x, mesh_axes)
    mg4 = _group_mantissa(safe_g, E4M3, algo)
    mg5 = _group_mantissa(safe_g, E5M2, algo)
    mgnv = _group_mantissa(safe_g, NVFP4, algo)
    out = mor_select_blocks(
        xp, jnp.stack([mg4, mg5, mgnv]), safe_g,
        block=block, q_amax4=E4M3.amax, q_amax5=E5M2.amax,
        q_amax_nv=NVFP4.amax, dt4=E4M3.dtype, dt5=E5M2.dtype, mode=mode,
        algo=algo, range_ratio=E5M2_RANGE_RATIO,
        nv_range_ratio=NVFP4_RANGE_RATIO, emit=emit,
        interpret=(be == "interpret"),
    )
    return out, g_amax, mg4


def mor_select(
    x: jnp.ndarray,
    part: Partition,
    mode: str = "sub3",
    algo: str = "gam",
    *,
    backend: str = "auto",
    mesh_axes=(),
) -> MorSelect:
    """Fused sub-tensor MoR selection (§3.2, + sub4) of a 2-D operand.

    One pass per block: the fp8 candidates (and for ``mode='sub4'`` the
    two-level NVFP4 candidate), Eq. 3 error comparison, Eq. 4 range
    gates, and the per-block select -- versus the three-plus full
    operand passes of the naive lowering. ``mesh_axes``: shard_map
    axes to allreduce the group amax over (per-block sums/selects stay
    shard-local; the Eq. 3/4 gates are per-block, so with a global
    amax every shard makes the single-device choice bit-for-bit --
    NVFP4 micro scales derive from the block data and the allreduced
    group amax, so sharded sub4 packs stay bit-identical too).
    """
    be = _kernel_backend(backend, part)
    M, K = x.shape
    bm, bk = part.resolve(x.shape)
    if mode == "sub4" and bk % NVFP4_MICRO:
        # Micro-blocks need 16-divisible contraction blocks; the sub4
        # recipe's aligned partition guarantees this, direct callers
        # with exotic blocks take the (internally padding) XLA path.
        be = "xla"
    if be == "xla":
        return _ref.mor_select_ref(x, part, mode, algo, mesh_axes=mesh_axes)
    out, g_amax, mg4 = _select_kernel_call(
        x, (bm, bk), mode, algo, "select", be, mesh_axes
    )
    y, sel, e4_sums, e5_sums, counts = out[:5]
    return MorSelect(
        y=y[:M, :K],
        sel=sel,
        e4_sums=e4_sums,
        e5_sums=e5_sums,
        counts=counts,
        group_amax=g_amax,
        group_mantissa=mg4,
        nv_sums=out[5] if mode == "sub4" else None,
    )


def quantize_pack(
    x: jnp.ndarray,
    part: Partition,
    mode: str = "sub3",
    algo: str = "gam",
    *,
    backend: str = "auto",
    mesh_axes=(),
):
    """One-pass fused sub-tensor selection *and* real packing.

    The pack-emitting variant of :func:`mor_select`: the same single
    VMEM pass per block that makes the §3.2 decision also writes the
    winner's real payload -- fp8 bit patterns, BF16 passthrough values,
    per-block GAM scales, and for ``mode='sub4'`` the packed E2M1
    nibbles + E4M3 micro-scale bytes -- so ``quantize_for_gemm`` no
    longer re-derives block amaxes / Alg. 1 scales / payload bits in a
    second XLA pass over the operand. Byte-identical to
    ``ref.pack_mixed`` on the selection's tags (the two-pass lowering
    stays as the ``backend='xla'`` oracle, ``ref.quantize_pack_ref``).

    Returns ``(MixedOperand, MorSelect)``; the MorSelect carries the
    per-block error sums / counts / group scalars the recipe layer
    aggregates into the stats vector, with ``y=None`` (real
    quantization never materializes the fake-quant output).

    ``mesh_axes`` as in :func:`mor_select`: the group amax (and with
    it every Alg. 1 scale and micro scale) is allreduced first, so a
    shard packs exactly the bytes its blocks would get on one device.
    """
    be = _kernel_backend(backend, part)
    M, K = x.shape
    bm, bk = part.resolve(x.shape)
    if mode == "sub4" and (bk % NVFP4_MICRO or bm % 2):
        # Nibble packing pairs rows and micro-blocks need 16-divisible
        # contraction blocks; the sub4 recipe's aligned partition
        # guarantees both, direct callers with exotic blocks take the
        # XLA path (whose packer raises on truly incapable blocks).
        be = "xla"
    if be == "xla":
        return _ref.quantize_pack_ref(x, part, mode, algo,
                                      mesh_axes=mesh_axes)
    out, g_amax, mg4 = _select_kernel_call(
        x, (bm, bk), mode, algo, "pack", be, mesh_axes
    )
    if mode == "sub4":
        (pq, pbf, sel, scales, e4_sums, e5_sums, counts, nv_sums,
         nib, ms) = out
    else:
        pq, pbf, sel, scales, e4_sums, e5_sums, counts = out
        nv_sums, nib, ms = None, None, None
    mo = MixedOperand(
        payload_q=pq,
        payload_bf16=pbf,
        tags=sel,
        scales=scales,
        block=(bm, bk),
        shape=(M, K),
        payload_nib=nib,
        micro_scales=ms,
        has_nvfp4=(mode == "sub4"),
    )
    r = MorSelect(
        y=None,
        sel=sel,
        e4_sums=e4_sums,
        e5_sums=e5_sums,
        counts=counts,
        group_amax=g_amax,
        group_mantissa=mg4,
        nv_sums=nv_sums,
    )
    return mo, r


def gam_quant(
    x: jnp.ndarray,
    *,
    block=(128, 128),
    fmt: FormatSpec = E4M3,
    algo: str = "gam",
    backend: str = "auto",
):
    """Fused quantize of a 2-D operand. Returns (xq, exp, err_sums, counts).

    Pallas path: global amax via one XLA reduce -> group mantissa -> fused
    per-block kernel. XLA path: the pure-jnp oracle.
    """
    be = resolve_backend(backend)
    part = Partition("block", block)
    if be == "xla":
        return _ref.gam_quant_ref(x, part, fmt, algo)
    _, safe_g = _group_amax(x)
    m_g = _group_mantissa(safe_g, fmt, algo)
    return gam_quant_blocks(
        x, m_g,
        block=block, q_amax=fmt.amax, fmt_dtype=fmt.dtype, algo=algo,
        interpret=(be == "interpret"),
    )


def fp8_gemm(a_q, b_q, a_scale, b_scale, *, block=(128, 128, 128),
             out_dtype=jnp.bfloat16, backend: str = "auto"):
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.fp8_gemm_ref(a_q, b_q, a_scale, b_scale, block,
                                 out_dtype)
    return _fp8_gemm_kernel(
        a_q, b_q, a_scale, b_scale, block=block, out_dtype=out_dtype,
        interpret=(be == "interpret"),
    )


class GemmTile(NamedTuple):
    """Static tiling knobs for one mixed-GEMM launch.

    decode_cache: use the k-keyed VMEM cache for the A decode (None =
                  the kernel's fit-based auto rule).
    bn_mult:      B row blocks per kernel step (the wider-bn sweep; 1 =
                  one pack block per tile).
    """

    decode_cache: Optional[bool] = None
    bn_mult: int = 1


# Shape-keyed block-size autotune table consulted by :func:`mixed_gemm`:
# (n_m, n_n, n_k) block-grid key -> GemmTile. Seeded from the bench
# lanes (benchmarks/bench_kernels.py records the chosen tile per row);
# anything absent falls through to gemm_tile_for's heuristic. Extend
# with register_gemm_tile.
_GEMM_TILE_TABLE: dict = {}


def register_gemm_tile(n_m: int, n_n: int, n_k: int, tile: GemmTile):
    """Pin the tile for one block-grid shape (overrides the heuristic)."""
    _GEMM_TILE_TABLE[(n_m, n_n, n_k)] = tile


def decode_row_block(m_rows: int, bk: int = 128) -> int:
    """Activation row block for an m_rows-row decode GEMM: the 16-row
    sublane tile for skinny batches (slots << 128), never a padded 128
    (see ``ref.activation_row_block``)."""
    return _ref.activation_row_block(m_rows, bk)


def register_decode_tiles(params, m_rows: int) -> int:
    """Pin the skinny-M decode lane for every quantized weight.

    Serving decode GEMMs are (m_rows, K) @ (K, N) with m_rows = engine
    slots << 128: the activation packs at the 16-row sublane tile
    (``decode_row_block``), giving a 1 x n_k A grid whose per-(i, k)
    decode stripes are tiny -- the k-keyed VMEM cache always fits, so
    the lane is (decode_cache=True, bn_mult=1). Walks ``params`` for
    QTensor-like leaves (anything exposing ``as_mixed_operand``) and
    registers one table entry per distinct (n_m, n_n, n_k) block grid;
    returns the number of grids registered. Idempotent.
    """
    grids = set()
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda l: hasattr(l, "as_mixed_operand")
    )
    for leaf in leaves:
        if not hasattr(leaf, "as_mixed_operand"):
            continue
        mo = leaf.as_mixed_operand()
        n_n, n_k = mo.tags.shape[-2], mo.tags.shape[-1]
        bm = decode_row_block(m_rows, mo.block[1])
        key = (-(-m_rows // bm), n_n, n_k)
        register_gemm_tile(
            *key,
            GemmTile(
                decode_cache=decode_cache_bytes(n_k, bm, mo.block[1])
                <= DECODE_CACHE_BUDGET,
                bn_mult=1,
            ),
        )
        grids.add(key)
    return len(grids)


def gemm_tile_for(
    n_m: int, n_n: int, n_k: int, block, tile: Optional[GemmTile] = None
) -> GemmTile:
    """Resolve the tile for a (n_m, n_n, n_k) block grid.

    Explicit ``tile`` wins, then the registered table, then the
    heuristic: prefer the decode cache whenever its (n_k, bm, bk) f32
    stripe store fits the VMEM budget; otherwise sweep a wider N tile
    (largest bn_mult in {4, 2} dividing n_n with bn * bn_mult <= 512)
    so the A decode still amortizes without scratch.
    """
    if tile is not None:
        return tile
    hit = _GEMM_TILE_TABLE.get((n_m, n_n, n_k))
    if hit is not None:
        return hit
    bm, bn, bk = block
    if n_n <= 1:
        return GemmTile(decode_cache=False, bn_mult=1)
    if decode_cache_bytes(n_k, bm, bk) <= DECODE_CACHE_BUDGET:
        return GemmTile(decode_cache=True, bn_mult=1)
    bn_mult = next(
        (m for m in (4, 2) if n_n % m == 0 and bn * m <= 512), 1
    )
    return GemmTile(decode_cache=False, bn_mult=bn_mult)


def mixed_gemm(
    a: MixedOperand,
    b: MixedOperand,
    *,
    out_dtype=jnp.bfloat16,
    backend: str = "auto",
    tile: Optional[GemmTile] = None,
) -> jnp.ndarray:
    """Mixed-representation block GEMM: C = A @ B^T, unpadded (M, N).

    Both operands arrive in their quantization view (rows x contraction,
    see :class:`~repro.kernels.ref.MixedOperand`); every block is decoded
    per its tag (E4M3 / E5M2 / BF16 passthrough / NVFP4) in-register and
    the product is f32-accumulated -- one fused kernel launch on TPU
    versus the dequantize-then-bf16-matmul lowering it replaces. The
    per-(i, k) A decode is amortized across the N sweep (VMEM cache or
    wider-bn tile, :func:`gemm_tile_for`); ``tile`` overrides the
    autotune table end to end (``mixed_dot``/``qdot`` pass it through).
    """
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.mixed_gemm_ref(a, b, out_dtype)
    assert a.block[1] == b.block[1], (a.block, b.block)
    n_m, n_k = a.tags.shape
    n_n = b.tags.shape[0]
    cfg = gemm_tile_for(
        n_m, n_n, n_k, (a.block[0], b.block[0], a.block[1]), tile
    )
    bn_mult = cfg.bn_mult if n_n % max(cfg.bn_mult, 1) == 0 else 1
    out = mixed_gemm_blocks(
        a.payload_q, a.payload_bf16, a.payload_nib, a.micro_scales,
        a.tags, a.scales,
        b.payload_q, b.payload_bf16, b.payload_nib, b.micro_scales,
        b.tags, b.scales,
        block=(a.block[0], b.block[0], a.block[1]),
        out_dtype=out_dtype,
        interpret=(be == "interpret"),
        a_has_nvfp4=a.has_nvfp4,
        b_has_nvfp4=b.has_nvfp4,
        decode_cache=cfg.decode_cache,
        bn_mult=max(bn_mult, 1),
    )
    return out[: a.shape[0], : b.shape[0]]


def mixed_dot(
    x2: jnp.ndarray,
    mo: MixedOperand,
    *,
    out_dtype=jnp.bfloat16,
    backend: str = "auto",
    tile: Optional[GemmTile] = None,
) -> jnp.ndarray:
    """x2 @ W^T for an unquantized (M, K) activation against a mixed
    (N, K)-view operand: the shared serving wrapper behind ``qdot``,
    ``mor_dot``'s QTensor path and the quantized lm-head -- packs the
    activation as an all-BF16 compact pack with the row block sized to
    the activation (decode steps have a handful of rows)."""
    bk = mo.block[1]
    a = _ref.passthrough_mixed(
        x2, (_ref.activation_row_block(x2.shape[0], bk), bk)
    )
    return mixed_gemm(a, mo, out_dtype=out_dtype, backend=backend,
                      tile=tile)


def _local_mixed(payload_q, payload_bf16, nib, ms, tags, scales, block,
                 has_nvfp4):
    """Rebuild a shard-local MixedOperand from shard_map-sliced leaves.

    The local logical shape is the local *padded* shape: per-shard
    padding blocks decode to zeros (zero payloads under scale 1.0), so
    they contribute nothing to the product and the caller slices the
    assembled global output back to the logical (M, N) once. The static
    ``has_nvfp4`` hint survives the leaf round-trip via closure.
    """
    shape = (tags.shape[-2] * block[0], tags.shape[-1] * block[1])
    return MixedOperand(payload_q, payload_bf16, tags, scales, block,
                        shape, nib, ms, has_nvfp4)


def sharded_mixed_gemm(
    a: MixedOperand,
    b: MixedOperand,
    *,
    mesh,
    row_axis=None,
    col_axis=None,
    contract_axis=None,
    out_dtype=jnp.bfloat16,
    backend: str = "auto",
) -> jnp.ndarray:
    """Mesh-sharded mixed-representation GEMM: C = A @ B^T under shard_map.

    Runs the block GEMM *per shard*: each device launches the fused
    kernel on its local payload blocks with the matching local tag/scale
    SMEM operands (tags/scales shard on the same block grid as the
    payload BlockSpecs, so a shard's kernel sees exactly the metadata of
    its own blocks -- see kernels/README.md). Sharding must be
    block-aligned: each sharded axis size must divide the operand's
    block-grid extent.

      row_axis       shards A's rows      -> C rows sharded, no traffic.
      col_axis       shards B's rows      -> C cols sharded, no traffic.
      contract_axis  shards K of both     -> per-shard partial products
                     are f32-psum'd before the out_dtype cast.

    Compact payload buffers (see MixedOperand.compact) are replicated --
    a single don't-care block has no row axis to shard. Operands packed
    by ``quantize_for_gemm`` under a policy with matching ``mesh_axes``
    carry shard-local blocks whose tags/scales are bit-identical to the
    single-device pack (tests/test_sharded_mor.py).
    """
    from repro.sharding.rules import mixed_operand_pspec

    assert a.block[1] == b.block[1], (a.block, b.block)
    if a.padded_shape[1] != b.padded_shape[1]:
        raise ValueError(
            f"contraction extents differ: {a.padded_shape} vs "
            f"{b.padded_shape}"
        )

    def _ax(name):
        return mesh.shape[name] if name else 1

    for mo, rax, who in ((a, row_axis, "A"), (b, col_axis, "B")):
        if mo.tags.shape[-2] % _ax(rax):
            raise ValueError(
                f"{who}: row block grid {mo.tags.shape[-2]} not divisible "
                f"by mesh axis {rax!r} ({_ax(rax)})"
            )
        if mo.tags.shape[-1] % _ax(contract_axis):
            raise ValueError(
                f"{who}: contraction block grid {mo.tags.shape[-1]} not "
                f"divisible by mesh axis {contract_axis!r} "
                f"({_ax(contract_axis)})"
            )

    from jax.sharding import PartitionSpec as P

    a_specs = mixed_operand_pspec(a, row_axis, contract_axis)
    b_specs = mixed_operand_pspec(b, col_axis, contract_axis)
    inner_dtype = jnp.float32 if contract_axis else out_dtype
    block_a, block_b = a.block, b.block
    nv_a, nv_b = a.has_nvfp4, b.has_nvfp4

    def body(aq, abf, anib, ams, at, asc, bq, bbf, bnib, bms, bt, bsc):
        out = mixed_gemm(
            _local_mixed(aq, abf, anib, ams, at, asc, block_a, nv_a),
            _local_mixed(bq, bbf, bnib, bms, bt, bsc, block_b, nv_b),
            out_dtype=inner_dtype,
            backend=backend,
        )
        if contract_axis:
            out = jax.lax.psum(out, contract_axis)
        return out.astype(out_dtype)

    sm = shard_map_unchecked(
        body, mesh,
        in_specs=a_specs + b_specs,
        out_specs=P(row_axis, col_axis),
    )
    out = sm(
        a.payload_q, a.payload_bf16, a.payload_nib, a.micro_scales,
        a.tags, a.scales,
        b.payload_q, b.payload_bf16, b.payload_nib, b.micro_scales,
        b.tags, b.scales,
    )
    return out[: a.shape[0], : b.shape[0]]


def flash_attention(q, k, v, *, causal=True, q_offset=None,
                    block_q=512, block_k=512, backend: str = "auto"):
    """Backend-dispatched flash attention.

    Two accepted layouts:

    * 4-D GQA contract -- q ``(B, S, Hq, dh)`` against k/v
      ``(B, T, Hkv, dh)`` with ``Hq % Hkv == 0``: kv heads are repeated
      into the q-head count here (each q head ``h`` reads kv head
      ``h // (Hq // Hkv)``), operands fold to ``(B*Hq, S|T, dh)`` for
      the kernel, and the output unfolds back to ``(B, S, Hq, dh)``.
      ``q_offset`` may be a scalar or per-batch-row ``(B,)``.
    * 3-D pre-folded ``(BH, S|T, d)`` passthrough (head counts already
      matched by the caller); ``q_offset`` scalar or ``(BH,)``.

    ``q_offset`` is the key position of query row 0 (default: last
    query aligned with last key, i.e. ``T - S``) -- see
    ``flash_attention_fwd``.
    """
    if q.ndim == 4:
        B, S, Hq, dh = q.shape
        if k.ndim != 4 or v.ndim != 4 or k.shape != v.shape:
            raise ValueError(
                f"4-D q needs matching 4-D k/v, got k{k.shape} v{v.shape}"
            )
        T, Hkv = k.shape[1], k.shape[2]
        if k.shape != (B, T, Hkv, dh) or Hq % Hkv:
            raise ValueError(
                f"GQA contract wants k/v (B={B}, T, Hkv, dh={dh}) with "
                f"Hq={Hq} divisible by Hkv, got k{k.shape}"
            )
        G = Hq // Hkv

        def fold(x):  # (B, L, H, dh) -> (B*H, L, dh)
            H = x.shape[2]
            return jnp.moveaxis(x, 2, 1).reshape(B * H, x.shape[1], dh)

        qf = fold(q)
        kf = fold(jnp.repeat(k, G, axis=2) if G > 1 else k)
        vf = fold(jnp.repeat(v, G, axis=2) if G > 1 else v)
        off = q_offset
        if off is not None:
            off = jnp.asarray(off, jnp.int32).reshape(-1)
            if off.shape[0] == B and B != B * Hq:
                off = jnp.repeat(off, Hq)
        out = flash_attention(
            qf, kf, vf, causal=causal, q_offset=off,
            block_q=block_q, block_k=block_k, backend=backend,
        )
        return jnp.moveaxis(out.reshape(B, Hq, S, dh), 1, 2)
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.flash_attention_ref(q, k, v, causal, q_offset=q_offset)
    return flash_attention_fwd(
        q, k, v, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        interpret=(be == "interpret"),
    )
