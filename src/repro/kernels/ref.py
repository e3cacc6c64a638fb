"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are also the ``backend='xla'`` lowerings of the public entry points
in :mod:`repro.kernels.ops`, so they are written to be *bit-identical* to
the pre-kernel XLA paths of :mod:`repro.core.mor` (the recipe regression
tests assert this). This module must not import ``repro.core.mor`` --
``core.mor`` dispatches through ``kernels.ops`` which imports this
module, and a back-edge would close an import cycle.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.collectives import pmax_over
from repro.core.formats import (
    E2M1_AMAX,
    E4M3,
    E5M2,
    NVFP4,
    NVFP4_MICRO,
    FormatSpec,
    cast_to_format,
    decode_e2m1,
    encode_e2m1,
    round_to_e2m1,
)
from repro.core.gam import compute_scales, scales_from_bmax
from repro.core.metrics import E5M2_RANGE_RATIO, NVFP4_RANGE_RATIO
from repro.core.partition import Partition, _pad2d, from_blocks, to_blocks

__all__ = [
    "TAG_E4M3",
    "TAG_E5M2",
    "TAG_BF16",
    "TAG_NVFP4",
    "QuantErr",
    "MorSelect",
    "MixedOperand",
    "expand_micro_onehot",
    "nvfp4_block_capable",
    "pack_mixed",
    "passthrough_mixed",
    "activation_row_block",
    "decode_mixed_ref",
    "mixed_gemm_ref",
    "gam_quant_ref",
    "quant_err_ref",
    "mor_select_ref",
    "quantize_pack_ref",
    "fp8_gemm_ref",
    "flash_attention_ref",
]

# Per-block representation tags: the contract between the MoR selection
# (repro.kernels.mor_select emits exactly these ids), the packing layer
# below, and the mixed-representation GEMM kernel. TAG_NVFP4 blocks
# store packed E2M1 nibbles + per-16-element E4M3 micro scales (sub4
# recipe) instead of a byte-per-element fp8 payload.
TAG_E4M3 = 0
TAG_E5M2 = 1
TAG_BF16 = 2
TAG_NVFP4 = 3


def nvfp4_block_capable(block: Tuple[int, int]) -> bool:
    """Whether a block shape can hold NVFP4 payloads: nibble packing
    pairs rows (even rows) and micro scales group NVFP4_MICRO
    contraction elements (16-divisible columns). Non-capable blocks can
    never carry TAG_NVFP4 (the sub4 recipe aligns its partition to
    (2, 16); packing rejects violations)."""
    br, bk = block
    return br % 2 == 0 and bk % NVFP4_MICRO == 0


def expand_micro_onehot(d: jnp.ndarray, bk: int, g0) -> jnp.ndarray:
    """(rows, G) per-micro-group row stripe -> (rows, bk) for the block
    whose first group index is ``g0``, via an exact one-hot f32 matmul
    (each output lane sums its single group value plus zeros).

    Shared by the selection and GEMM kernels: Mosaic lowers
    dot_general where a lane-splitting reshape/repeat would not, and
    the stripes ride in whole because a (rows, bk/16) block would
    violate the 128-lane tile. The matmul is bit-exact (one non-zero
    summand per output lane), so both kernels reproduce the
    jnp.repeat-based references bit-for-bit.
    """
    G = d.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (G, bk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
    onehot = (g0 + c // NVFP4_MICRO == r).astype(jnp.float32)
    return jax.lax.dot_general(
        d, onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _nib_compact_shape(block: Tuple[int, int]) -> Tuple[int, int]:
    return (max(block[0] // 2, 1), block[1])


def _ms_compact_shape(block: Tuple[int, int]) -> Tuple[int, int]:
    return (block[0], max(block[1] // NVFP4_MICRO, 1))


class QuantErr(NamedTuple):
    """One fused quantize+error event (backend-independent result).

    y:              (M, K) fake-quantized operand in the input dtype.
    err_sums:       (nm, nk) f32 per-block relative-error sums (Eq. 1-3).
    counts:         (nm, nk) f32 per-block non-zero element counts.
    group_amax:     () f32 amax of the whole group (tensor).
    group_mantissa: () f32 GAM shared mantissa m_g (1.0 for ablations).
    """

    y: jnp.ndarray
    err_sums: jnp.ndarray
    counts: jnp.ndarray
    group_amax: jnp.ndarray
    group_mantissa: jnp.ndarray


class MorSelect(NamedTuple):
    """One fused sub-tensor selection event (paper §3.2 + the sub4
    NVFP4 extension).

    y:          (M, K) per-block selected output in the input dtype.
    sel:        (nm, nk) i32 selection id: 0=E4M3, 1=E5M2, 2=BF16,
                3=NVFP4 (sub4 only).
    e4_sums:    (nm, nk) f32 E4M3 per-block relative-error sums.
    e5_sums:    (nm, nk) f32 E5M2 per-block relative-error sums.
    counts:     (nm, nk) f32 per-block non-zero element counts.
    group_amax / group_mantissa: as in :class:`QuantErr` (E4M3's m_g).
    nv_sums:    (nm, nk) f32 NVFP4 per-block relative-error sums
                (None for sub2/sub3).
    """

    y: jnp.ndarray
    sel: jnp.ndarray
    e4_sums: jnp.ndarray
    e5_sums: jnp.ndarray
    counts: jnp.ndarray
    group_amax: jnp.ndarray
    group_mantissa: jnp.ndarray
    nv_sums: jnp.ndarray | None = None


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class MixedOperand:
    """One GEMM operand in the mixed-representation block layout.

    The operand is seen in its *quantization view*: (R, K) with the
    contraction axis last, zero-padded to a multiple of ``block``.
    Per-block storage is a tri-lane buffer (see kernels/README.md):

    payload_q:    (Rp, Kp) uint8 -- raw fp8 bits (E4M3 bit patterns for
                  TAG_E4M3 blocks, E5M2 for TAG_E5M2; zero elsewhere).
    payload_bf16: (Rp, Kp) original-precision buffer in the operand's
                  stored dtype (bf16 in training); holds the original
                  values for TAG_BF16 blocks, zero elsewhere.
    payload_nib:  (Rp/2, Kp) uint8 -- packed E2M1 nibbles for TAG_NVFP4
                  blocks (zero elsewhere). Row-halves packing *per
                  block*: within block (i, j), byte row r holds the
                  code of logical row r in its low nibble and of row
                  r + br/2 in its high nibble, so the kernel decode is
                  two vector nibble extracts + one sublane concat.
    micro_scales: (Rp, Kp/16) uint8 -- E4M3 bit patterns of the NVFP4
                  per-16-element micro scales (bits of 1.0f for
                  all-zero micro-groups; zero outside TAG_NVFP4 blocks).
    tags:         (nr, nk) int32 per-block representation tag.
    scales:       (nr, nk) f32 reconstructed GAM scales (1.0 for
                  TAG_BF16 and padding-only blocks; the two-level
                  NVFP4 *block* scale for TAG_NVFP4 blocks).
    block:        (br, bk) static block shape.
    shape:        (R, K) static logical (unpadded) shape.
    has_nvfp4:    static tri-state hint for the GEMM kernel's NVFP4
                  decode: False = no block can be TAG_NVFP4 (skip the
                  decode even when a compact sub-byte lane's shape
                  coincides with the full one -- single-block
                  operands), True = TAG_NVFP4 blocks may be present,
                  None = unknown (legacy packs; shape heuristic).

    Any payload lane may be *compact*: collapsed to one don't-care
    block when no (concrete) tag references it -- see :meth:`compact`.
    A fully-fp8 weight then really is ~1 byte/element, and a
    fully-NVFP4 weight ~0.56 bytes/element (0.5 payload + 1/16
    micro-scale).
    """

    payload_q: jnp.ndarray
    payload_bf16: jnp.ndarray
    tags: jnp.ndarray
    scales: jnp.ndarray
    block: Tuple[int, int]
    shape: Tuple[int, int]
    payload_nib: jnp.ndarray = None
    micro_scales: jnp.ndarray = None
    has_nvfp4: bool | None = None

    def __post_init__(self):
        # Sub-byte lanes are optional at construction (pre-NVFP4 call
        # sites); default to compact don't-care blocks so the pytree
        # structure is uniform and every consumer can assume them.
        if self.payload_nib is None:
            lead = jnp.shape(self.tags)[:-2]
            self.payload_nib = jnp.zeros(
                (*lead, *_nib_compact_shape(self.block)), jnp.uint8
            )
        if self.micro_scales is None:
            lead = jnp.shape(self.tags)[:-2]
            self.micro_scales = jnp.zeros(
                (*lead, *_ms_compact_shape(self.block)), jnp.uint8
            )

    def tree_flatten(self):
        return (
            (self.payload_q, self.payload_bf16, self.tags, self.scales,
             self.payload_nib, self.micro_scales),
            (self.block, self.shape, self.has_nvfp4),
        )

    def tree_flatten_with_keys(self):
        # Same children, same order -- but with named key paths, so the
        # payload-lane taint checker (repro.analysis.jaxpr_lint) can
        # seed taint by lane name anywhere a MixedOperand rides in an
        # argument tree.
        children, aux = self.tree_flatten()
        names = ("payload_q", "payload_bf16", "tags", "scales",
                 "payload_nib", "micro_scales")
        return (
            tuple(
                (jax.tree_util.GetAttrKey(n), c)
                for n, c in zip(names, children)
            ),
            aux,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        pq, pbf, tags, scales, nib, ms = children
        block, shape, has_nvfp4 = aux
        return cls(pq, pbf, tags, scales, block, shape, nib, ms,
                   has_nvfp4)

    @property
    def padded_shape(self) -> Tuple[int, int]:
        # Derived from the tag grid, not the payloads (either buffer may
        # be compact).
        return (
            self.tags.shape[-2] * self.block[0],
            self.tags.shape[-1] * self.block[1],
        )

    def compact(self) -> "MixedOperand":
        """Drop every payload lane no tag references down to a single
        don't-care block. Host-side only (needs concrete tags); leading
        stack axes (layer-stacked serving weights) are preserved so
        ``lax.scan`` slicing keeps working. The ``has_nvfp4`` hint is
        refined to the concrete truth while we are looking at the
        tags."""
        tags = np.asarray(self.tags)
        br, bk = self.block
        lead = self.payload_q.shape[:-2]
        out = dataclasses.replace(
            self, has_nvfp4=bool((tags == TAG_NVFP4).any())
        )
        is_fp8 = (tags == TAG_E4M3) | (tags == TAG_E5M2)
        if not is_fp8.any():
            out = dataclasses.replace(
                out, payload_q=jnp.zeros((*lead, br, bk), jnp.uint8)
            )
        if not (tags == TAG_BF16).any():
            out = dataclasses.replace(
                out,
                payload_bf16=jnp.zeros(
                    (*lead, br, bk), self.payload_bf16.dtype
                ),
            )
        if not (tags == TAG_NVFP4).any():
            out = dataclasses.replace(
                out,
                payload_nib=jnp.zeros(
                    (*lead, *_nib_compact_shape(self.block)), jnp.uint8
                ),
                micro_scales=jnp.zeros(
                    (*lead, *_ms_compact_shape(self.block)), jnp.uint8
                ),
            )
        return out

    def transpose(self) -> "MixedOperand":
        """The transposed quantization view (exact: per-block tags,
        scales and payloads are permutation-invariant under block
        transpose). NVFP4 blocks are *not* transpose-invariant -- their
        nibble pairing and 1x16 micro-blocks follow the contraction
        direction -- so packs holding TAG_NVFP4 blocks must re-quantize
        the transposed view instead (core.linear gates this on the
        recipe; concrete tags are also checked here)."""
        assert self.tags.ndim == 2, (
            "transpose() is for single-matrix operands; slice a stacked "
            "operand per layer first (lax.scan or _layer_mo)"
        )
        # NVFP4 precondition, enforced *statically* so it also fires
        # under jit (where tags are tracers and content checks would
        # silently pass): dense sub-byte lanes mean the pack was built
        # with_nvfp4 and may carry TAG_NVFP4 blocks.
        nr, nk = self.tags.shape
        dense_nib = (nr > 1 or nk > 1) and tuple(
            self.payload_nib.shape
        ) == (self.padded_shape[0] // 2, self.padded_shape[1])
        no_nv_msg = (
            "cannot transpose a pack with NVFP4 payload lanes: micro "
            "scales are contraction-directed (re-quantize the "
            "transposed view)"
        )
        assert not dense_nib, no_nv_msg
        if not isinstance(self.tags, jax.core.Tracer):
            assert not (np.asarray(self.tags) == TAG_NVFP4).any(), \
                no_nv_msg
        blockT = (self.block[1], self.block[0])
        return MixedOperand(
            payload_q=self.payload_q.T,
            payload_bf16=self.payload_bf16.T,
            tags=self.tags.T,
            scales=self.scales.T,
            block=blockT,
            shape=(self.shape[1], self.shape[0]),
            # Sub-byte lanes hold no data here (no NVFP4 tags): fresh
            # compact blocks in the transposed geometry.
            payload_nib=jnp.zeros(_nib_compact_shape(blockT), jnp.uint8),
            micro_scales=jnp.zeros(_ms_compact_shape(blockT), jnp.uint8),
            has_nvfp4=False,
        )

    def dequant(self) -> jnp.ndarray:
        """Stored (Fig. 4: original-dtype) values, unpadded (R, K)."""
        R, K = self.shape
        return decode_mixed_ref(self)[:R, :K]


def _nvfp4_lanes(xf, s_nv, tags, block):
    """(nib blocks (nr, nk, br/2, bk), micro-scale blocks (nr, nk, br,
    bk/16)) of the NVFP4 candidate for every block. xf: (nr, nk, br,
    bk) f32; s_nv: (nr, nk) block scales targeting NVFP4.amax. Blocks
    not tagged NVFP4 get zeroed lanes."""
    nr, nk = xf.shape[:2]
    br, bk = block
    ng = bk // NVFP4_MICRO
    xs = xf * s_nv[:, :, None, None]
    g = xs.reshape(nr, nk, br, ng, NVFP4_MICRO)
    d = jnp.max(jnp.abs(g), axis=-1) / E2M1_AMAX  # (nr, nk, br, ng)
    d_q = cast_to_format(d, E4M3)
    safe_d = jnp.where(d_q > 0, d_q, 1.0)
    q = round_to_e2m1(g / safe_d[..., None]).reshape(nr, nk, br, bk)
    codes = encode_e2m1(q)
    nib = (codes[:, :, : br // 2, :]
           | (codes[:, :, br // 2 :, :] << 4)).astype(jnp.uint8)
    ms = jax.lax.bitcast_convert_type(
        safe_d.astype(jnp.float8_e4m3fn), jnp.uint8
    )
    t = tags[:, :, None, None]
    nib = jnp.where(t == TAG_NVFP4, nib, jnp.uint8(0))
    ms = jnp.where(t == TAG_NVFP4, ms, jnp.uint8(0))
    return nib, ms


def pack_mixed(
    x2d: jnp.ndarray,
    tags: jnp.ndarray,
    block: Tuple[int, int],
    algo: str = "gam",
    group_amax: jnp.ndarray | None = None,
    with_nvfp4: bool = False,
) -> MixedOperand:
    """Real-quantize a 2-D operand into the mixed block layout.

    ``tags`` is the (nr, nk) per-block representation decision (e.g.
    ``MorSelect.sel`` or a broadcast tensor-level accept). The fp8 bits
    and GAM scales are computed exactly as the fake-quantization path
    does (same ``scales_from_bmax``, same saturating cast), so
    ``decode_mixed_ref(pack_mixed(x, tags)) == mor fake-quant output``
    bit-for-bit for the selected blocks.

    ``group_amax``: the (already allreduced, when sharded) group amax;
    must be supplied for a shard of a larger operand or the shard-local
    Alg. 1 mantissa would diverge from the decisions in ``tags``.

    ``with_nvfp4``: build the packed-nibble + micro-scale lanes for
    TAG_NVFP4 blocks (sub4 recipe). Static so three-way-and-below
    recipes pay nothing and keep byte-identical packs; requires an
    NVFP4-capable block (even rows, 16-divisible columns).
    """
    br, bk = block
    # Pad up front so the block view keeps the caller's exact block
    # (Partition.resolve would shrink an align-rounded block back to
    # the raw operand extent).
    xp = _pad2d(x2d, br, bk)
    part = Partition("block", (br, bk))
    xb = to_blocks(xp, part)  # (nr, nk, br, bk) original dtype
    nr, nk = xb.shape[:2]
    assert tags.shape == (nr, nk), (tags.shape, (nr, nk))

    bmax = jnp.max(jnp.abs(xb), axis=(2, 3)).astype(jnp.float32)
    s4 = scales_from_bmax(bmax, E4M3, algo, group_amax=group_amax).scale
    s5 = scales_from_bmax(bmax, E5M2, algo, group_amax=group_amax).scale
    xf = xb.astype(jnp.float32)

    def bits(scale, fmt):
        xs = jnp.clip(
            xf * scale[:, :, None, None], -fmt.amax, fmt.amax
        ).astype(fmt.dtype)
        return jax.lax.bitcast_convert_type(xs, jnp.uint8)

    t = tags[:, :, None, None]
    payload_q = jnp.where(
        t == TAG_E4M3, bits(s4, E4M3),
        jnp.where(t == TAG_E5M2, bits(s5, E5M2), jnp.uint8(0)),
    )
    payload_bf16 = jnp.where(t == TAG_BF16, xb, jnp.zeros_like(xb))
    scales = jnp.where(
        tags == TAG_E4M3, s4, jnp.where(tags == TAG_E5M2, s5, 1.0)
    ).astype(jnp.float32)

    padded = (nr * br, nk * bk)
    if with_nvfp4:
        if not nvfp4_block_capable(block):
            raise ValueError(
                f"NVFP4 packing needs an even-row, {NVFP4_MICRO}-"
                f"divisible-column block, got {block} (the sub4 recipe "
                "aligns its partition to (2, 16) automatically)"
            )
        s_nv = scales_from_bmax(
            bmax, NVFP4, algo, group_amax=group_amax
        ).scale
        nib, ms = _nvfp4_lanes(xf, s_nv, tags, block)
        scales = jnp.where(tags == TAG_NVFP4, s_nv, scales).astype(
            jnp.float32
        )
        payload_nib = from_blocks(nib, (padded[0] // 2, padded[1]))
        micro_scales = from_blocks(
            ms, (padded[0], padded[1] // NVFP4_MICRO)
        )
    else:
        payload_nib = jnp.zeros(_nib_compact_shape(block), jnp.uint8)
        micro_scales = jnp.zeros(_ms_compact_shape(block), jnp.uint8)
    return MixedOperand(
        payload_q=from_blocks(payload_q, padded),
        payload_bf16=from_blocks(payload_bf16, padded),
        tags=tags.astype(jnp.int32),
        scales=scales,
        block=(br, bk),
        shape=tuple(x2d.shape),
        payload_nib=payload_nib,
        micro_scales=micro_scales,
        has_nvfp4=with_nvfp4,
    )


def passthrough_mixed(
    x2d: jnp.ndarray, block: Tuple[int, int]
) -> MixedOperand:
    """All-BF16 mixed layout of an unquantized operand (e.g. the
    activation side of a serving GEMM against real-quantized weights).
    The fp8 and sub-byte buffers are compact (one don't-care block) by
    construction."""
    br, bk = block
    xp = _pad2d(x2d, br, bk)
    nr, nk = xp.shape[0] // br, xp.shape[1] // bk
    return MixedOperand(
        payload_q=jnp.zeros((br, bk), jnp.uint8),
        payload_bf16=xp,
        tags=jnp.full((nr, nk), TAG_BF16, jnp.int32),
        scales=jnp.ones((nr, nk), jnp.float32),
        block=(br, bk),
        shape=tuple(x2d.shape),
        has_nvfp4=False,
    )


def activation_row_block(m: int, bk: int) -> int:
    """Row-block size for a passthrough activation pack: full K blocks,
    rows padded only to the 16-sublane TPU tile (decode activations have
    a handful of rows -- padding them to a 128-row block would inflate
    the hot serving GEMM ~8-32x)."""
    return min(bk, -(-m // 16) * 16)


def _full_buffer(buf, padded_shape, fill_dtype):
    """A compact (single-block) payload decodes as zeros; its tags never
    reference it, so the values are don't-care."""
    if tuple(buf.shape) == tuple(padded_shape):
        return buf
    return jnp.zeros(padded_shape, fill_dtype)


def decode_mixed_ref(mo: MixedOperand) -> jnp.ndarray:
    """Padded (Rp, Kp) stored values in the operand's original dtype --
    the exact values the mixed GEMM kernel reconstructs in-register."""
    br, bk = mo.block
    Rp, Kp = mo.padded_shape
    part = Partition("block", (br, bk))
    qb = to_blocks(
        _full_buffer(mo.payload_q, mo.padded_shape, jnp.uint8), part
    )
    q4 = jax.lax.bitcast_convert_type(qb, jnp.float8_e4m3fn).astype(
        jnp.float32
    )
    q5 = jax.lax.bitcast_convert_type(qb, jnp.float8_e5m2).astype(
        jnp.float32
    )
    t = mo.tags[:, :, None, None]
    s = mo.scales[:, :, None, None]
    f8 = (jnp.where(t == TAG_E5M2, q5, q4) / s).astype(
        mo.payload_bf16.dtype
    )
    bfb = to_blocks(
        _full_buffer(
            mo.payload_bf16, mo.padded_shape, mo.payload_bf16.dtype
        ),
        part,
    )
    yb = jnp.where(t == TAG_BF16, bfb, f8)
    if nvfp4_block_capable(mo.block):
        # NVFP4 lane: unpack row-halved nibbles + expand micro scales.
        # Non-capable blocks can never carry TAG_NVFP4, so the branch
        # is a static shape decision.
        nibb = to_blocks(
            _full_buffer(mo.payload_nib, (Rp // 2, Kp), jnp.uint8),
            Partition("block", (br // 2, bk)),
        ).astype(jnp.int32)
        lo = decode_e2m1(nibb & 15)
        hi = decode_e2m1(nibb >> 4)
        vals = jnp.concatenate([lo, hi], axis=2)  # (nr, nk, br, bk)
        msb = to_blocks(
            _full_buffer(
                mo.micro_scales, (Rp, Kp // NVFP4_MICRO), jnp.uint8
            ),
            Partition("block", (br, bk // NVFP4_MICRO)),
        )
        d = jax.lax.bitcast_convert_type(
            msb, jnp.float8_e4m3fn
        ).astype(jnp.float32)
        d_exp = jnp.repeat(d, NVFP4_MICRO, axis=3)
        nv = ((vals * d_exp) / s).astype(mo.payload_bf16.dtype)
        yb = jnp.where(t == TAG_NVFP4, nv, yb)
    return from_blocks(yb, mo.padded_shape)


def mixed_gemm_ref(
    a: MixedOperand,
    b: MixedOperand,
    out_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Reference mixed-representation GEMM: C = A @ B^T, unpadded (M, N).

    Decodes both operands to their stored values, then accumulates in
    f32 one K-block at a time in the kernel's grid order, so interpret
    mode and this XLA lowering are bit-identical.
    """
    assert a.block[1] == b.block[1], (a.block, b.block)
    Ka, Kb = a.padded_shape[1], b.padded_shape[1]
    assert Ka == Kb, (a.padded_shape, b.padded_shape)
    bk = a.block[1]
    A = decode_mixed_ref(a).astype(jnp.float32)
    B = decode_mixed_ref(b).astype(jnp.float32)
    acc = jnp.zeros((A.shape[0], B.shape[0]), jnp.float32)
    for k in range(Ka // bk):
        sl = slice(k * bk, (k + 1) * bk)
        acc = acc + jax.lax.dot_general(
            A[:, sl], B[:, sl], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    M, N = a.shape[0], b.shape[0]
    return acc[:M, :N].astype(out_dtype)


def _global_amax(x: jnp.ndarray, mesh_axes) -> jnp.ndarray | None:
    """Allreduced group amax of a sharded operand; None when unsharded
    (scales_from_bmax then derives it from the local block amaxes --
    bit-identical, both are exact maxima of the same elements)."""
    if not mesh_axes:
        return None
    return pmax_over(
        jnp.max(jnp.abs(x.astype(jnp.float32))), mesh_axes
    )


def _blocked_quant_err(
    xb: jnp.ndarray, fmt: FormatSpec, algo: str,
    group_amax: jnp.ndarray | None = None,
):
    """Single-pass quantize + per-block error sums on a blocked view.

    xb: (nm, nk, bm, bk) in its *original* dtype (bf16 in training -- the
    paper's Fig. 4 pipeline is BF16-in/BF16-out, so large intermediates
    never materialize in f32; per-block scale math runs in f32 on the tiny
    (nm, nk) arrays). Returns (xqb in xb.dtype, scales, err_sums f32,
    counts f32). This is the XLA analogue of the fused Pallas kernels.
    ``group_amax`` carries the allreduced global amax for sharded events.
    """
    bmax = jnp.max(jnp.abs(xb), axis=(2, 3)).astype(jnp.float32)
    scales = scales_from_bmax(bmax, fmt, algo, group_amax=group_amax)
    s = scales.scale[:, :, None, None]
    xqb_f32 = cast_to_format(xb.astype(jnp.float32) * s, fmt) / s
    xqb = xqb_f32.astype(xb.dtype)  # Fig. 4: output stays BF16
    xf = xb.astype(jnp.float32)
    nz = xf != 0.0
    err = jnp.where(
        nz,
        jnp.abs((xf - xqb.astype(jnp.float32)) / jnp.where(nz, xf, 1.0)),
        0.0,
    )
    return (
        xqb,
        scales,
        jnp.sum(err, (2, 3)),
        jnp.sum(nz, (2, 3)).astype(jnp.float32),
    )


def quant_err_ref(
    x: jnp.ndarray, part: Partition, fmt: FormatSpec, algo: str = "gam",
    mesh_axes=(),
) -> QuantErr:
    """Reference for the ops.quant_err entry point (one-format events)."""
    xb = to_blocks(x, part)
    xqb, scales, err_sums, counts = _blocked_quant_err(
        xb, fmt, algo, group_amax=_global_amax(x, mesh_axes)
    )
    return QuantErr(
        y=from_blocks(xqb, x.shape),
        err_sums=err_sums,
        counts=counts,
        group_amax=scales.group_amax,
        group_mantissa=scales.group_mantissa,
    )


def quant_err_groups_ref(
    x: jnp.ndarray, part: Partition, fmt: FormatSpec, algo: str,
    row_amax: jnp.ndarray,
):
    """Reference for the grouped one-format event (``ops.quant_err_groups``):
    each block row scaled with its own group's amax ``row_amax`` (nm,).
    Returns (y, err_sums (nm, nk), counts (nm, nk), mantissa (nm,))."""
    xb = to_blocks(x, part)
    xqb, scales, err_sums, counts = _blocked_quant_err(
        xb, fmt, algo, group_amax=row_amax[:, None]
    )
    m_g = jnp.broadcast_to(scales.group_mantissa, row_amax.shape[:1] + (1,))
    return from_blocks(xqb, x.shape), err_sums, counts, m_g[:, 0]


def mor_select_ref(
    x: jnp.ndarray, part: Partition, mode: str = "sub3", algo: str = "gam",
    mesh_axes=(),
) -> MorSelect:
    """Reference for mor_select_blocks: fused §3.2 per-block selection
    (sub2/sub3), extended with the four-way sub4 NVFP4 cascade."""
    assert mode in ("sub2", "sub3", "sub4"), mode
    xb = to_blocks(x, part)
    g = _global_amax(x, mesh_axes)
    q4b, scales4, e4_sums, counts = _blocked_quant_err(
        xb, E4M3, algo, group_amax=g
    )
    q5b, _, e5_sums, _ = _blocked_quant_err(xb, E5M2, algo, group_amax=g)

    m1 = e4_sums < e5_sums  # Eq. 3
    if mode == "sub2":
        use5 = jnp.zeros_like(m1)
        use_nv, nv_sums, qnb = None, None, None
    else:
        # Eq. 4 dynamic-range gate on the nonzero magnitudes.
        xabs = jnp.abs(xb)
        anynz = counts > 0
        bmax = jnp.max(xabs, axis=(2, 3)).astype(jnp.float32)
        big = jnp.asarray(jnp.finfo(xb.dtype).max, xb.dtype)
        bmin = jnp.min(jnp.where(xb != 0, xabs, big), axis=(2, 3)).astype(
            jnp.float32
        )
        ratio = jnp.where(anynz, bmax / jnp.where(anynz, bmin, 1.0), 1.0)
        use5 = jnp.logical_and(jnp.logical_not(m1), ratio < E5M2_RANGE_RATIO)
        use_nv, nv_sums, qnb = None, None, None
        if mode == "sub4":
            # Four-way cascade: NVFP4 first (Eq. 3 against the E4M3
            # benchmark + the Eq. 4-style NVFP4 range gate), then the
            # plain sub3 cascade for the blocks that fall through. The
            # NVFP4 gate is on *micro-group amaxes* (the quantity the
            # E4M3 micro scales must represent); intra-group range is
            # already priced into nv_sums by Eq. 3.
            qnb, _, nv_sums, _ = _blocked_quant_err(
                xb, NVFP4, algo, group_amax=g
            )
            nm_, nk_, bm_, bk_ = xb.shape
            pad_g = (-bk_) % NVFP4_MICRO
            xbg = xb.astype(jnp.float32)
            if pad_g:
                xbg = jnp.concatenate(
                    [xbg, jnp.zeros((nm_, nk_, bm_, pad_g), jnp.float32)],
                    axis=-1,
                )
            ga = jnp.max(
                jnp.abs(xbg).reshape(nm_, nk_, bm_, -1, NVFP4_MICRO),
                axis=-1,
            )  # (nm, nk, bm, ng) micro-group amaxes
            gnz = ga > 0
            big = jnp.float32(jnp.finfo(jnp.float32).max)
            ga_min = jnp.min(
                jnp.where(gnz, ga, big), axis=(2, 3)
            )
            g_ratio = jnp.where(
                anynz, bmax / jnp.where(anynz, ga_min, 1.0), 1.0
            )
            use_nv = jnp.logical_and(
                nv_sums < e4_sums, g_ratio < NVFP4_RANGE_RATIO
            )

    m1b = m1[:, :, None, None]
    yb = jnp.where(m1b, q4b, jnp.where(use5[:, :, None, None], q5b, xb))
    sel = jnp.where(
        m1, jnp.int32(0), jnp.where(use5, jnp.int32(1), jnp.int32(2))
    )
    if use_nv is not None:
        yb = jnp.where(use_nv[:, :, None, None], qnb, yb)
        sel = jnp.where(use_nv, jnp.int32(TAG_NVFP4), sel)
    return MorSelect(
        y=from_blocks(yb, x.shape),
        sel=sel,
        e4_sums=e4_sums,
        e5_sums=e5_sums,
        counts=counts,
        group_amax=scales4.group_amax,
        group_mantissa=scales4.group_mantissa,
        nv_sums=nv_sums,
    )


def quantize_pack_ref(
    x: jnp.ndarray, part: Partition, mode: str = "sub3",
    algo: str = "gam", mesh_axes=(),
) -> Tuple[MixedOperand, MorSelect]:
    """Reference for ops.quantize_pack: the two-pass lowering (fused
    selection, then the XLA packer over the selection's tags). This is
    the bit-exactness oracle for the pack-emitting kernel: payload
    bytes, nibbles, micro-scale bytes, tags and GAM scales must all
    match. The returned MorSelect carries ``y=None`` -- real
    quantization never materializes the fake-quant output."""
    r = mor_select_ref(x, part, mode, algo, mesh_axes=mesh_axes)
    block = part.resolve(x.shape)
    mo = pack_mixed(
        x, r.sel, block, algo,
        group_amax=r.group_amax,
        with_nvfp4=(mode == "sub4"),
    )
    return mo, r._replace(y=None)


def gam_quant_ref(
    x: jnp.ndarray,
    part: Partition,
    fmt: FormatSpec,
    algo: str = "gam",
):
    """Reference for gam_quant_blocks: (xq, block_exp, err_sums, counts)."""
    scales = compute_scales(x, part, fmt, algo=algo)
    xb = to_blocks(x.astype(jnp.float32), part)
    s = scales.scale[:, :, None, None]
    xqb = cast_to_format(xb * s, fmt) / s
    xq = from_blocks(xqb, x.shape).astype(x.dtype)
    xqb = to_blocks(xq.astype(jnp.float32), part)
    nz = xb != 0
    err = jnp.where(nz, jnp.abs((xb - xqb) / jnp.where(nz, xb, 1.0)), 0.0)
    return (
        xq,
        scales.block_exp,
        jnp.sum(err, (2, 3)),
        jnp.sum(nz, (2, 3)).astype(jnp.float32),
    )


def fp8_gemm_ref(
    a_q: jnp.ndarray,
    b_q: jnp.ndarray,
    a_scale: jnp.ndarray,
    b_scale: jnp.ndarray,
    block: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Dequantize per block then matmul in f32."""
    bm, bn, bk = block
    M, K = a_q.shape
    N = b_q.shape[1]
    a = a_q.astype(jnp.float32).reshape(M // bm, bm, K // bk, bk)
    a = a / a_scale[:, None, :, None]
    b = b_q.astype(jnp.float32).reshape(K // bk, bk, N // bn, bn)
    b = b / b_scale[:, None, :, None]
    return (a.reshape(M, K) @ b.reshape(K, N)).astype(out_dtype)


def flash_attention_ref(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True,
    q_offset=None,
) -> jnp.ndarray:
    """Naive softmax attention. q: (BH, S, d), k/v: (BH, T, d).

    ``q_offset``: key position of query row 0, scalar or (BH,) per-row;
    default aligns the last query with the last key (offset ``T - S``,
    the historical ``tril(k=T-S)`` mask). Ignored when not causal.
    """
    BH, S, d = q.shape
    T = k.shape[1]
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (d**-0.5)
    if causal:
        off = jnp.broadcast_to(
            jnp.asarray(
                T - S if q_offset is None else q_offset, jnp.int32
            ).reshape(-1),
            (BH,),
        )
        q_pos = off[:, None] + jnp.arange(S)  # (BH, S)
        mask = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bqk,bkd->bqd", p, v.astype(jnp.float32)
    ).astype(q.dtype)
