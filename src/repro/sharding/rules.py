"""Sharding rules: parameter, optimizer-state, batch, cache and
quantized-weight PartitionSpecs for every architecture.

Megatron-style TP over 'model':
  wqkv / fc1 / expert-w1  -> column-parallel (shard output features)
  wo   / fc2 / expert-w2  -> row-parallel    (shard input features)
  embeddings / lm_head    -> vocab-sharded
  MoE experts             -> expert-parallel (shard E)
  norms / small ssm vecs  -> replicated
DP over ('pod','data') shards the batch. ZeRO-1: optimizer moments and
f32 master weights are additionally sharded over 'data' on the largest
dimension the param spec leaves free.

Quantized leaves (docs/sharding.md): a ``MixedOperand`` shards *as one
unit* -- uint8 payload, original-precision dual buffer, per-block tag
and GAM-scale grids all partition along the same block grid
(``mixed_operand_pspec``), so a shard owns complete blocks with their
metadata and the mixed GEMM kernel runs shard-locally. ``QTensor``
serving weights reuse the dense rule of the weight they replace,
transposed into the (N, K) quantization view
(``qtensor_pspec_from_dense``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.collectives import shard_map_unchecked
from repro.core.formats import NVFP4_MICRO
from repro.kernels.ref import MixedOperand

__all__ = [
    "param_specs", "opt_state_spec_from_param", "batch_spec", "cache_specs_tree",
    "named_shardings", "zero1_spec",
    "mixed_operand_pspec", "qtensor_pspec_from_dense",
    "quantized_param_specs", "packed_moment_pspec", "opt_state_specs",
    "shard_map_unchecked",
]

# name-fragment -> (spec builder). Matched against the flattened path.
# Specs are for the *unstacked* per-layer shapes; stacked layer params get
# a leading None inserted.


def _leaf_spec(path: str, leaf) -> P:
    ndim = leaf.ndim
    # Embeddings / heads: vocab-sharded.
    if path.endswith("embed") or path.endswith("lm_head"):
        # embed (V, d) -> shard V; lm_head (d, V) -> shard V.
        return P("model", None) if path.endswith("embed") else P(None, "model")
    # Norm scales / biases / small vectors: replicated.
    if ndim <= 1:
        return P(*([None] * ndim))
    # MoE experts (E, d, f): expert-parallel on E.
    if "moe" in path and ("w1" in path or "w2" in path):
        return P("model", None, None)
    if "router" in path:
        return P(None, None)
    # Column-parallel (shard output dim).
    col = ("wqkv", "wi", "w_in", "w_up", "w_qkv", "w_x", "xwq", "xwkv",
           "w_ff1")
    # Row-parallel (shard input dim).
    row = ("wo", "w_out", "w_down", "xwo", "w_ff2")
    last = path.split("/")[-1]
    if last in col:
        return P(*([None] * (ndim - 1)), "model")
    if last in row:
        return P("model", *([None] * (ndim - 1)))
    if last == "r":  # sLSTM recurrence (H, dh, 4dh): head-sharded if even.
        return P(None, None, None)
    if last == "conv_w":
        return P(None, "model")
    if last in ("w_bc", "w_dt_down"):
        return P("model", None)
    if last == "w_dt_up":
        return P(None, "model")
    if last in ("A_log", "D", "dt_bias"):
        return P("model", None) if ndim == 2 else P("model")
    return P(*([None] * ndim))


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
    return "/".join(parts)


def param_specs(cfg: ArchConfig, params_shape) -> Any:
    """PartitionSpec pytree matching a params (shape) pytree.

    Stacked block params (leading n_units axis) get a leading None.
    """

    def spec_for(path, leaf):
        p = _path_str(path)
        stacked = "blocks" in p
        base = _leaf_spec(p, _Unstacked(leaf) if stacked else leaf)
        if stacked:
            return P(None, *base)
        return base

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


class _Unstacked:
    """Shape view dropping the stacked layer axis."""

    def __init__(self, leaf):
        self.ndim = leaf.ndim - 1
        self.shape = leaf.shape[1:]


def zero1_spec(spec: P, shape: Tuple[int, ...], data_axes=("data",)) -> P:
    """Extend a param spec with 'data' sharding on the largest free dim
    divisible by the data-axis size (ZeRO-1 optimizer partitioning)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best, best_size = None, 0
    for i, (s, n) in enumerate(zip(entries, shape)):
        if s is None and n % 16 == 0 and n > best_size:
            best, best_size = i, n
    if best is not None:
        entries[best] = data_axes if len(data_axes) > 1 else data_axes[0]
    return P(*entries)


def opt_state_spec_from_param(cfg: ArchConfig, params_shape, multi_pod=False):
    """Specs for (master, m, v) f32 optimizer triples: param spec + ZeRO-1."""
    pspecs = param_specs(cfg, params_shape)
    data_axes = ("data",)

    def extend(spec, leaf):
        return zero1_spec(spec, leaf.shape, data_axes)

    return jax.tree.map(extend, pspecs, params_shape)


def batch_spec(multi_pod: bool = False) -> P:
    return P(("pod", "data") if multi_pod else "data")


_TP = 16  # model-axis size of the production meshes


def _cache_leaf_spec(path: str, shape, batch) -> P:
    """Cache entries: (n_units, B, ...) -- batch over data axes; the kv
    seq dim over 'model' when divisible (context-parallel decode,
    docs/sharding.md), else replicated over model."""
    ndim = len(shape)

    def tp_if(axis):
        return "model" if shape[axis] % _TP == 0 else None

    if path.endswith("/k") or path.endswith("/v") or path.endswith("xk") \
            or path.endswith("xv"):
        # (L, B, S, hkv, hd): shard S over model (works for any kv count).
        return P(None, batch, tp_if(2), None, None)
    if path.endswith("k_scale") or path.endswith("v_scale"):
        return P(None, batch, tp_if(2), None)
    if path.endswith("C"):
        return P(None, batch, None, tp_if(3), None)
    if path.endswith("conv"):
        return P(None, batch, None, tp_if(3))
    if path.endswith("/h") and ndim == 4:  # mamba h (L,B,di,N)
        return P(None, batch, tp_if(2), None)
    return P(None, batch, *([None] * (ndim - 2)))


def cache_specs_tree(cfg: ArchConfig, cache_shape, multi_pod: bool = False):
    batch = ("pod", "data") if multi_pod else "data"

    def spec_for(path, leaf):
        return _cache_leaf_spec("/" + _path_str(path), leaf.shape, batch)

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def named_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# --------------------------------------------------------- quantized --


def mixed_operand_pspec(
    mo: MixedOperand,
    rows: Optional[str] = None,
    cols: Optional[str] = None,
) -> Tuple[P, P, P, P, P, P]:
    """(payload_q, payload_bf16, payload_nib, micro_scales, tags,
    scales) PartitionSpecs for one mixed-layout operand, sharding its
    quantization-view rows over ``rows`` and its contraction blocks
    over ``cols``.

    All six leaves partition along the same block grid -- the packed
    4-bit NVFP4 lane holds whole (br/2, bk) nibble blocks per payload
    block and the (br, bk/16) micro-scale grid holds whole micro-scale
    rows per block, so a shard owns complete blocks together with
    *all* their metadata -- the invariant the per-shard mixed GEMM
    kernel relies on (the SMEM tag/scale operands of a shard describe
    exactly its payload blocks). A *compact* payload buffer (one
    don't-care block, see ``MixedOperand.compact``) is replicated: it
    has no row extent to shard and is dead weight either way. Leading
    stack axes (layer-stacked serving weights) stay unsharded.
    """
    lead = mo.tags.ndim - 2
    Rp, Kp = mo.padded_shape

    def sp(*axes) -> P:
        return P(*([None] * lead), *axes)

    def payload_spec(buf, full_shape) -> P:
        if tuple(buf.shape[-2:]) != tuple(full_shape):  # compact buffer
            return sp(None, None)
        return sp(rows, cols)

    return (
        payload_spec(mo.payload_q, (Rp, Kp)),
        payload_spec(mo.payload_bf16, (Rp, Kp)),
        payload_spec(mo.payload_nib, (Rp // 2, Kp)),
        payload_spec(mo.micro_scales, (Rp, Kp // NVFP4_MICRO)),
        sp(rows, cols),
        sp(rows, cols),
    )


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def qtensor_pspec_from_dense(qt, dense_spec: P, mesh: Optional[Mesh] = None):
    """A QTensor-shaped PartitionSpec pytree from the dense rule of the
    (K, N) weight it replaced.

    The QTensor stores the weight in its transposed (N, K) quantization
    view, so a dense ``P(a_K, a_N)`` becomes rows=``a_N``,
    cols=``a_K`` on the mixed-operand leaves; stats are replicated.
    Stacked weights (dense ``P(None, a_K, a_N)``) keep the leading
    layer axis unsharded.

    With ``mesh``, an axis that does not divide the *block grid* is
    demoted to replicated: quantized leaves shard in whole 128x128
    blocks or not at all (a split block would separate payload rows
    from their tag/scale cell).
    """
    from repro.serve.quantized import QTensor  # avoid import cycle

    lead = qt.mo.tags.ndim - 2
    entries = list(dense_spec) + [None] * (lead + 2 - len(dense_spec))
    a_k, a_n = entries[-2], entries[-1]
    if mesh is not None:
        nr, nk = qt.mo.tags.shape[-2], qt.mo.tags.shape[-1]
        if nr % _axis_size(mesh, a_n):
            a_n = None
        if nk % _axis_size(mesh, a_k):
            a_k = None
    pq, pbf, nib, ms, tags, scales = mixed_operand_pspec(
        qt.mo, rows=a_n, cols=a_k
    )
    # The spec pytree must share the value pytree's static aux data
    # (including the has_nvfp4 hint) or tree_map over (params, specs)
    # rejects the pair as structure-mismatched.
    mo_spec = MixedOperand(
        payload_q=pq, payload_bf16=pbf, tags=tags, scales=scales,
        block=qt.mo.block, shape=qt.mo.shape,
        payload_nib=nib, micro_scales=ms, has_nvfp4=qt.mo.has_nvfp4,
    )
    stats_spec = P(*([None] * qt.stats.ndim))
    return QTensor(mo=mo_spec, stats=stats_spec, shape=qt.shape)


def quantized_param_specs(
    cfg: ArchConfig, params, mesh: Optional[Mesh] = None
) -> Any:
    """PartitionSpec pytree for a params tree whose GEMM weights were
    replaced by QTensors (``serve.quantized.quantize_params``).

    Dense leaves keep their :func:`param_specs` rule; each QTensor leaf
    derives its spec from the dense rule of the weight it replaced, so
    e.g. a column-parallel ``wo`` stays row-parallel in its (N, K)
    quantization view and the serving GEMMs stay tensor-parallel
    *without dequantizing*. ``mesh`` enables block-grid divisibility
    demotion (see :func:`qtensor_pspec_from_dense`).
    """
    from repro.serve.quantized import QTensor  # avoid import cycle

    def spec_for(path, leaf):
        p = _path_str(path)
        stacked = "blocks" in p
        if isinstance(leaf, QTensor):
            # Dense rule on the original (K, N) shape, stack axis
            # re-inserted for layer-stacked weights, then transposed
            # into the quantization view.
            base = _leaf_spec(p, _ShapeView(leaf.shape))
            dense = P(None, *base) if leaf.is_stacked else base
            return qtensor_pspec_from_dense(leaf, dense, mesh)
        base = _leaf_spec(p, _Unstacked(leaf) if stacked else leaf)
        return P(None, *base) if stacked else base

    return jax.tree_util.tree_map_with_path(
        spec_for, params, is_leaf=lambda x: isinstance(x, QTensor)
    )


class _ShapeView:
    """Duck-typed (ndim, shape) stand-in for _leaf_spec rule matching."""

    def __init__(self, shape):
        self.shape = tuple(shape)
        self.ndim = len(self.shape)


# ------------------------------------------------ compressed opt state --


def packed_moment_pspec(pm, rows=None, mesh: Optional[Mesh] = None):
    """A PackedMoment-shaped PartitionSpec for one packed Adam moment.

    ZeRO-style: the quantization-view *rows* shard over ``rows``
    (normally the 'data' axis) when the block grid divides the axis
    size -- whole 128-row block rows move together with their tag/scale
    cells, the same invariant as :func:`mixed_operand_pspec`. An axis
    that does not divide the block grid is demoted to replicated
    (quantized leaves shard in whole blocks or not at all). The stats
    row is replicated.
    """
    from repro.optim.moments import PackedMoment  # avoid import cycle

    a_r = rows
    if mesh is not None and a_r is not None:
        if pm.mo.tags.shape[-2] % _axis_size(mesh, a_r):
            a_r = None
    pq, pbf, nib, ms, tags, scales = mixed_operand_pspec(
        pm.mo, rows=a_r, cols=None
    )
    mo_spec = MixedOperand(
        payload_q=pq, payload_bf16=pbf, tags=tags, scales=scales,
        block=pm.mo.block, shape=pm.mo.shape,
        payload_nib=nib, micro_scales=ms, has_nvfp4=pm.mo.has_nvfp4,
    )
    return PackedMoment(
        mo=mo_spec, stats=P(None), shape=pm.shape
    )


def opt_state_specs(
    cfg: ArchConfig,
    opt_state,
    data_axes=("data",),
    mesh: Optional[Mesh] = None,
):
    """An OptState-shaped PartitionSpec tree for the (possibly
    MoR-compressed) optimizer state.

    Master weights and dense moment leaves get the param spec extended
    with ZeRO-1 data sharding (:func:`zero1_spec`); PackedMoment leaves
    get :func:`packed_moment_pspec` (rows over the data axis, block-
    grid divisibility demotion under ``mesh``); the error-feedback
    residual -- gradient-shaped -- reuses the master layout, matching
    the ZeRO-2 gradient constraint in the train step; ``step`` is
    replicated.
    """
    from repro.optim.adamw import OptState
    from repro.optim.moments import PackedMoment  # avoid import cycle

    rows = data_axes if len(data_axes) > 1 else data_axes[0]
    pspecs = param_specs(cfg, opt_state.master)

    def ext(spec, leaf):
        return zero1_spec(spec, leaf.shape, data_axes)

    master_specs = jax.tree.map(ext, pspecs, opt_state.master)

    def moment_specs(tree):
        return jax.tree.map(
            lambda leaf, spec: (
                packed_moment_pspec(leaf, rows=rows, mesh=mesh)
                if isinstance(leaf, PackedMoment)
                else zero1_spec(spec, leaf.shape, data_axes)
            ),
            tree, pspecs,
            is_leaf=lambda x: isinstance(x, PackedMoment),
        )

    return OptState(
        master=master_specs,
        m=moment_specs(opt_state.m),
        v=moment_specs(opt_state.v),
        step=P(),
        ef=(None if opt_state.ef is None
            else jax.tree.map(ext, pspecs, opt_state.ef)),
    )
