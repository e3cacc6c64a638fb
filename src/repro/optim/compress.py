"""Training-state compression through the real MoR selection machinery.

Three pieces:

1. :func:`compress_decompress_grads` / :func:`compress_grads` -- the
   gradient round-trip the jit train step applies before the optimizer.
   Legacy modes ('fp8', 'fp8_ef') keep the PR-2 per-tensor GAM-scaled
   E4M3 round-trip; the 'mor' / 'mor_ef' modes route every gradient
   leaf through :func:`repro.core.mor.mor_quantize` -- per-block
   selection between the recipe's representations (sub2/sub3/sub4),
   exactly the decision path the forward/backward GEMM operands use.
   The ``_ef`` variants keep a persistent error-feedback residual per
   leaf (Mellempudi et al.): the residual is added to the raw gradient
   *before* selection, so the per-block decisions see the corrected
   values, and the new residual is ``corrected - quantized`` -- the
   accumulated quantization error stays bounded by one quantization
   step of the chosen block format instead of drifting across steps
   (tests/test_compress_props.py pins that bound).

2. :func:`make_pod_compressed_psum` -- the explicit cross-pod collective
   for shard_map trainers. With a :class:`~repro.core.policy.MoRPolicy`
   it ships *real* MoR payloads across the pod axis: each pod packs its
   local partial gradient with :func:`quantize_for_gemm` (uint8 fp8
   payload + packed NVFP4 nibbles + micro scales + per-block tags + GAM
   scales), all-gathers the six lanes, decodes every pod's pack and
   sums in f32. Within-pod sharding axes go in ``inner_axes``: the pack
   then uses the PR-3 allreduced group amax, so the payload bytes, tags
   and scales each shard ships are bit-identical to a single-device
   pack of the whole pod gradient (tests/test_compress_psum.py).
   Without a policy the legacy flat per-tensor E4M3 path is kept.

3. :func:`ef_init` -- zero residual state, shaped like the grads.

Bytes on the wire / in HBM per element: a fully-fp8 selection ships
1 B/elt payload (+8 B per 128x128 block of tag+scale), fully-NVFP4
0.5625 B/elt -- vs 2 B/elt for a bf16 all-reduce and 1 B/elt for flat
E4M3 with *one* scale per tensor. The witness test in
tests/test_compress_psum.py shows where the per-block machinery pays:
one outlier block no longer destroys the scale of every other block.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.collectives import all_gather_over
from repro.core.formats import E4M3, cast_to_format
from repro.core.mor import (
    EVENT_GRAD,
    STAT_EVENT_KIND,
    mor_quantize,
    quantize_for_gemm,
)
from repro.core.policy import MoRPolicy
from repro.kernels.ref import MixedOperand

__all__ = [
    "GRAD_COMPRESS_MODES",
    "DEFAULT_GRAD_POLICY",
    "compress_decompress_grads",
    "compress_grads",
    "ef_init",
    "leaf2d",
    "make_pod_compressed_psum",
]

GRAD_COMPRESS_MODES = ("fp8", "fp8_ef", "mor", "mor_ef")

# Per-block three-way selection is the default gradient recipe: E5M2's
# wider exponent range matters most for gradients (the paper's Eq. 4
# dynamic-range gate exists for exactly this tensor class).
DEFAULT_GRAD_POLICY = MoRPolicy(recipe="sub3")


def leaf2d(x: jnp.ndarray) -> jnp.ndarray:
    """The 2-D quantization view of one pytree leaf: trailing axis kept
    (it is the contraction axis of the GEMM that produced the grad),
    leading axes flattened; vectors become one row, scalars (1, 1)."""
    if x.ndim == 0:
        return x.reshape(1, 1)
    if x.ndim == 1:
        return x.reshape(1, -1)
    return x.reshape(-1, x.shape[-1])


def _q_roundtrip(g: jnp.ndarray) -> jnp.ndarray:
    """Per-tensor GAM-scaled E4M3 round-trip in the gradient dtype
    (legacy 'fp8' mode -- one scale per tensor, no selection)."""
    gf = g.astype(jnp.float32)
    amax = jnp.max(jnp.abs(gf))
    scale = jnp.where(amax > 0, E4M3.amax / amax, 1.0)
    return (cast_to_format(gf * scale, E4M3) / scale).astype(g.dtype)


def _mor_roundtrip(
    g: jnp.ndarray, policy: MoRPolicy
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fake-quantize one gradient leaf through the shared MoR decision
    path. Returns (round-tripped leaf in g's dtype, stats row stamped
    EVENT_GRAD)."""
    gf = g.astype(jnp.float32)
    y2d, stats = mor_quantize(leaf2d(gf), policy)
    return (
        y2d.reshape(g.shape).astype(g.dtype),
        stats.at[STAT_EVENT_KIND].set(EVENT_GRAD),
    )


def ef_init(grads) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_grads(
    grads,
    mode: str = "mor",
    ef_state: Optional[Any] = None,
    policy: Optional[MoRPolicy] = None,
) -> Tuple[Any, Optional[Any], Optional[Any]]:
    """Gradient compression round-trip with per-event stats.

    Returns ``(new_grads, new_ef_state, stats)``:

    * ``new_grads`` -- grads after the round-trip, original dtypes.
    * ``new_ef_state`` -- the updated residual tree for ``*_ef`` modes;
      for the plain modes, ``ef_state`` passed through unchanged.
    * ``stats`` -- for 'mor'/'mor_ef', a tree like ``grads`` whose
      leaves are STATS_WIDTH rows with ``event_kind = EVENT_GRAD``;
      ``None`` for the legacy per-tensor modes (they bypass the stats
      machinery by construction).

    'mor' / 'mor_ef' quantize each leaf's 2-D view (:func:`leaf2d`)
    under ``policy`` (default :data:`DEFAULT_GRAD_POLICY`); the EF
    variant adds the persistent residual *before* selection so the
    per-block decisions price the corrected values.
    """
    if mode not in GRAD_COMPRESS_MODES:
        raise ValueError(
            f"mode {mode!r} not in {GRAD_COMPRESS_MODES}"
        )
    pol = policy if policy is not None else DEFAULT_GRAD_POLICY

    if mode == "fp8":
        return jax.tree.map(_q_roundtrip, grads), ef_state, None

    if mode == "mor":
        pairs = jax.tree.map(lambda g: _mor_roundtrip(g, pol), grads)
        is_pair = lambda x: isinstance(x, tuple)
        new_g = jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair)
        stats = jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair)
        return new_g, ef_state, stats

    # Error-feedback variants.
    if ef_state is None:
        raise ValueError(f"mode {mode!r} needs ef_state (see ef_init)")

    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        if mode == "fp8_ef":
            q = _q_roundtrip(corrected)
            stats = None
        else:  # mor_ef
            q, stats = _mor_roundtrip(corrected, pol)
        return q.astype(g.dtype), corrected - q.astype(jnp.float32), stats

    triples = jax.tree.map(one, grads, ef_state)
    is_triple = lambda x: isinstance(x, tuple)
    new_g = jax.tree.map(lambda t: t[0], triples, is_leaf=is_triple)
    new_e = jax.tree.map(lambda t: t[1], triples, is_leaf=is_triple)
    if mode == "fp8_ef":
        return new_g, new_e, None
    stats = jax.tree.map(lambda t: t[2], triples, is_leaf=is_triple)
    return new_g, new_e, stats


def compress_decompress_grads(
    grads,
    mode: str = "fp8",
    ef_state: Optional[Any] = None,
    policy: Optional[MoRPolicy] = None,
) -> Tuple[Any, Optional[Any]]:
    """Signature-stable wrapper: **always** returns ``(grads,
    ef_state)`` for every mode (the pre-PR-8 version returned a bare
    tree for mode='fp8' and a tuple for 'fp8_ef', and the train step
    mis-assigned the tuple; tests/test_train_compress.py pins this).
    Non-EF modes return ``ef_state`` unchanged (``None`` if not given).
    """
    new_g, new_e, _ = compress_grads(grads, mode, ef_state, policy)
    return new_g, new_e


def _gather_decode_sum(
    mo: MixedOperand, axis_name: Optional[str], out_dtype
) -> jnp.ndarray:
    """all-gather the six payload lanes of ``mo`` over the pod axis,
    decode each pod's pack and sum in f32. The per-pod loop is a
    static Python loop (the gathered leading dim is the static axis
    size); decode is the shared XLA reference, so the summed value is
    exactly sum(dequant(pack(g_pod)))."""
    lanes = (
        mo.payload_q, mo.payload_bf16, mo.payload_nib,
        mo.micro_scales, mo.tags, mo.scales,
    )
    g = [all_gather_over(l, axis_name) for l in lanes]
    n_pods = g[0].shape[0]
    total = None
    for i in range(n_pods):
        moi = MixedOperand(
            payload_q=g[0][i], payload_bf16=g[1][i], tags=g[4][i],
            scales=g[5][i], block=mo.block, shape=mo.shape,
            payload_nib=g[2][i], micro_scales=g[3][i],
            has_nvfp4=mo.has_nvfp4,
        )
        d = moi.dequant().astype(jnp.float32)
        total = d if total is None else total + d
    return total.astype(out_dtype)


def make_pod_compressed_psum(
    axis_name: str = "pod",
    policy: Optional[MoRPolicy] = None,
    inner_axes: Tuple[str, ...] = (),
):
    """Compressed cross-pod sum for shard_map trainers.

    Without ``policy``: the legacy flat path -- one per-tensor E4M3
    payload + one f32 scale per pod, all-gathered and dequant-summed.

    With ``policy``: each pod packs its local partial gradient through
    the real selection machinery (:func:`quantize_for_gemm` on the
    :func:`leaf2d` view, in bf16 -- the within-pod reduction dtype) and
    the collective ships the six mixed-layout lanes instead. When the
    pod's gradient is itself sharded within the pod, name those mesh
    axes in ``inner_axes``: every pack statistic (group amax, Eq. 3/4
    gates) is then allreduced within the pod, so the shards of one pod
    emit bit-identical tags/scales and exactly the payload bytes a
    single-device pack of the full pod gradient would
    (tests/test_compress_psum.py). ``axis_name`` must *not* be in
    ``inner_axes`` -- pods hold different partial sums, not shards of
    one tensor.

    ``axis_name=None`` degenerates to a local pack/decode round-trip
    (single-pod mesh, or unit-testing the numerics outside shard_map).
    """
    if policy is not None and axis_name in policy.mesh_axes:
        raise ValueError(
            f"policy.mesh_axes {policy.mesh_axes} must not include the "
            f"pod axis {axis_name!r}"
        )
    if policy is not None and axis_name in inner_axes:
        raise ValueError(
            f"inner_axes {inner_axes} must not include the pod axis "
            f"{axis_name!r}: pods hold independent partial sums"
        )

    def psum_fp8(g: jnp.ndarray) -> jnp.ndarray:
        gf = g.astype(jnp.float32)
        amax = jnp.max(jnp.abs(gf))
        scale = jnp.where(amax > 0, E4M3.amax / amax, 1.0)
        q = jnp.clip(gf * scale, -E4M3.amax, E4M3.amax).astype(
            jnp.float8_e4m3fn
        )
        qs = all_gather_over(q, axis_name)  # (n_pods, ...) fp8 payload
        ss = all_gather_over(scale, axis_name)  # (n_pods,) f32
        deq = qs.astype(jnp.float32) / ss.reshape(
            (-1,) + (1,) * (qs.ndim - 1)
        )
        return jnp.sum(deq, axis=0).astype(g.dtype)

    if policy is None:
        return psum_fp8

    pol = policy.replace(mesh_axes=tuple(inner_axes))

    def psum_mor(g: jnp.ndarray) -> jnp.ndarray:
        # bf16 is the stored dtype of the pack's original-precision
        # lane -- the same dtype a within-pod GSPMD reduction delivers.
        x2d = leaf2d(g).astype(jnp.bfloat16)
        mo, _ = quantize_for_gemm(x2d, pol)
        out2d = _gather_decode_sum(mo, axis_name, jnp.float32)
        return out2d.reshape(g.shape).astype(g.dtype)

    return psum_mor
