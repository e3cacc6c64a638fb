"""MoR products of many small tensors held in the rows of one buffer.

An MoE layer's experts each see a ragged, data-dependent number of rows.
The layer lays them out in one buffer of static shape (R, K): the rows
of group ``g`` (one expert) follow those of ``g - 1``, each group padded
with zero rows to a whole number of scale blocks, so that no block
straddles two experts; the rows past the last group are a tail that no
group owns. ``Groups`` says where the groups lie.

:func:`grouped_ffn` runs the experts' FFN over such a buffer, with one
pair of weights per group. Each of its two products is
:func:`repro.core.linear.mor_dot` for the buffer and one weight per
group, ``w`` (G, K, N): each group's rows are one MoR tensor and each
``w[g]`` another, with their own per-tensor decision, GAM group
mantissa and stats row, as if every expert ran its own ``mor_dot``.
The products are ``jax.lax.ragged_dot`` over the groups' sizes, the
quantization kernel stops at the last group, and the elementwise work
runs over the live rows alone (:func:`over_live_rows`), so the cost
follows the routed rows and not the buffer's bound. Padding rows are
zeros: they add nothing to an amax, an error sum or a count, and a
group's non-zero fraction is taken over its routed rows alone.

Supported operand policies: ``'off'``, and the one-format recipes
``'tensor'`` and ``'e4m3'`` on square ``'block'`` partitions whose rows
are the buffer's alignment (transposing such a group's blocks gives the
blocks of its transpose, so the wgrad product takes both operands'
quantized forms: dy's from dgrad, as ``mor_dot`` does, and x's from
the forward product, kept as the residual, where ``mor_dot`` quantizes
x again to the same values and stats). Other policies raise.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .formats import E4M3
from .linear import N_BWD_EVENTS, _scope
from .mor import STATS_WIDTH, _guard_lanes, _stats, mor_quantize, partition_of
from .policy import MoRDotPolicy, MoRPolicy

from repro.kernels import ops as kops

__all__ = ["Groups", "make_groups", "row_align", "grouped_quantize",
           "over_live_rows", "grouped_ffn"]

# Block rows whose amax one step of the group-amax loop reads.
_AMAX_CHUNK = 8
# Buffer rows a step of ``over_live_rows`` reads at most.
LIVE_CHUNK = 1024


class Groups(NamedTuple):
    """Where the groups lie in a buffer of rows.

    sizes: (G,) i32 rows of each group in the buffer, a whole number of
           block rows; group g starts where group g - 1 ends.
    rows:  (G,) i32 routed (non-padding) rows of each group.
    """

    sizes: jnp.ndarray
    rows: jnp.ndarray


def make_groups(rows: jnp.ndarray, align: int) -> Groups:
    """Groups of ``rows`` routed rows each, padded to ``align``."""
    rows = rows.astype(jnp.int32)
    return Groups(sizes=-(-rows // align) * align, rows=rows)


def row_align(policy: MoRDotPolicy) -> int:
    """Rows a group is padded to: the block rows of the policy's
    activation and gradient events (128 when quantization is off)."""
    rows = {p.block_shape[0] for p in (policy.act, policy.grad)
            if p.enabled}
    if len(rows) > 1:
        raise ValueError(f"grouped MoR needs one block row size, got {rows}")
    return rows.pop() if rows else 128


def _check(p: MoRPolicy, align: int) -> None:
    if not p.enabled:
        return
    if p.recipe not in ("tensor", "e4m3") or p.partition != "block" or \
            p.block_shape[0] != p.block_shape[1] or \
            p.block_shape[0] != align or p.mesh_axes:
        raise NotImplementedError(
            "grouped MoR supports the 'tensor' and 'e4m3' recipes on "
            f"square {align}-row 'block' partitions, got {p}"
        )


def _block_row_groups(groups: Groups, nm: int, bm: int) -> jnp.ndarray:
    """(nm,) group of each block row; G for the tail."""
    ends = jnp.cumsum(groups.sizes) // bm
    return jnp.searchsorted(ends, jnp.arange(nm, dtype=ends.dtype),
                            side="right").astype(jnp.int32)


def _live_block_row_amax(x: jnp.ndarray, live: jnp.ndarray, bm: int):
    """(nm,) amax of each block row up to ``live`` block rows (zero past
    them), read a few block rows a step so that the tail is not read."""
    nm = x.shape[0] // bm
    c = min(_AMAX_CHUNK, nm)

    def body(i, out):
        start = jnp.minimum(i * c, nm - c)
        rows = jax.lax.dynamic_slice_in_dim(x, start * bm, c * bm, axis=0)
        amax = jnp.max(jnp.abs(rows.reshape(c, -1)), axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, amax.astype(jnp.float32), start, axis=0)

    out = jax.lax.fori_loop(0, -(-live // c), body,
                            jnp.zeros((nm,), jnp.float32))
    return jnp.where(jnp.arange(nm) < live, out, 0.0)


def grouped_quantize(x: jnp.ndarray, groups: Groups, policy: MoRPolicy
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fake-quantize each group of ``x`` (R, K) as its own tensor.

    Returns (y (R, K) in x.dtype, stats (G, STATS_WIDTH)); the tail of
    ``y`` is ``x``'s. The kernels read the live rows twice, for the
    groups' errors and then to quantize the groups that accept, which
    they write over ``x`` in place: no second buffer of the bound."""
    G = groups.sizes.shape[0]
    R, K = x.shape
    bm = policy.block_shape[0]
    if R % bm:
        raise ValueError(f"buffer rows {R} are no multiple of {bm}")
    nm = R // bm
    brg = _block_row_groups(groups, nm, bm)
    live = jnp.sum(groups.sizes) // bm
    seg = functools.partial(jax.ops.segment_sum, segment_ids=brg,
                            num_segments=G + 1)
    elts = jnp.maximum(groups.rows.astype(jnp.float32) * K, 1.0)

    if not policy.enabled:
        nz = seg(jnp.sum((x != 0).reshape(nm, -1), axis=1,
                         dtype=jnp.float32))[:G]
        amax = jax.ops.segment_max(
            jnp.max(jnp.abs(x.astype(jnp.float32)).reshape(nm, -1), 1),
            brg, num_segments=G + 1)[:G]
        amax = jnp.maximum(amax, 0.0)
        stats = jax.vmap(lambda a, n: _stats(
            -1.0, 0.0, a, 0.0, 0.0, 1.0, n, 1.0,
            guard_flags=_guard_lanes(a)[0]))(amax, nz / elts)
        return x, stats

    part = partition_of(policy)
    row_amax = _live_block_row_amax(x, live, bm)
    g_amax = jnp.maximum(jax.ops.segment_max(
        row_amax, brg, num_segments=G + 1), 0.0).at[G].set(0.0)
    err, cnt, m_row = kops.quant_err_groups(
        x, part, E4M3, policy.algo, g_amax[brg], live,
        backend=policy.backend,
    )
    err_g = seg(jnp.sum(err, axis=1))
    cnt_g = seg(jnp.sum(cnt, axis=1))
    bad_g = seg(jnp.sum((~jnp.isfinite(err)).astype(jnp.float32), axis=1))
    m_g = jax.ops.segment_max(m_row, brg, num_segments=G + 1)
    m_g = jnp.where(jnp.isfinite(m_g), m_g, 1.0)  # an empty group
    rel = err_g / jnp.maximum(cnt_g, 1.0)
    if policy.recipe == "tensor":
        # A nonfinite error rejects: the group stays BF16.
        ok = rel < policy.threshold
    else:  # e4m3: always quantized
        ok = jnp.ones((G + 1,), bool)
    ok = ok.at[G].set(False)  # the tail stays as it is
    y = kops.quantize_groups(x, part, E4M3, policy.algo, g_amax[brg],
                             ok[brg], live, backend=policy.backend)

    def row(okf, e, a, n, mg, bad):
        flags, _ = _guard_lanes(a)
        flags = flags + jnp.where(bad > 0, 2.0, 0.0)
        return _stats(okf, e, a, okf, 0.0, 1.0 - okf, n, mg,
                      guard_flags=flags, fallback_count=bad)

    stats = jax.vmap(row)(ok[:G].astype(jnp.float32), rel[:G], g_amax[:G],
                          cnt_g[:G] / elts, m_g[:G], bad_g[:G])
    return y.astype(x.dtype), stats


def _ragged(x, w, sizes, out_dtype):
    """(R, K) rows by group times (G, K, N) -> (R, N) in ``out_dtype``,
    accumulated in f32; zero past the last group. The output is not
    written in f32 first: at the buffer's bound that would be the
    layer's largest temporary."""
    return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=out_dtype)


def _ragged_wgrad(x, dy, sizes):
    """sum over each group's rows of x^T dy: (R, K), (R, N) -> (G, K, N)."""
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[],
    )
    return jax.lax.ragged_dot_general(x, dy, sizes, dn,
                                      preferred_element_type=jnp.float32)


def _weights(w, policy: MoRPolicy, transpose: bool):
    """Each group's weight quantized as its own tensor, in the (N, K)
    view of the forward product or the (K, N) view of dgrad."""
    def one(wg):
        return mor_quantize(wg.T if transpose else wg, policy)

    return jax.vmap(one)(w)


def _product_fwd(x, w, groups, policy: MoRDotPolicy):
    """y[rows of g] = MoR(x[rows of g]) @ MoR(w[g]) for every group g:
    (R, K) rows by group, (G, K, N) -> (y (R, N) in x.dtype, zero past
    the last group; fwd_stats (G, N_FWD_EVENTS, STATS_WIDTH)), and the
    residuals of :func:`_product_bwd`."""
    align = row_align(policy)
    for p in (policy.act, policy.weight, policy.grad):
        _check(p, align)
    with _scope("mor_quant", "fwd_x"):
        xq, x_stats = grouped_quantize(x, groups, policy.act)
    with _scope("mor_quant", "fwd_w"):
        wq_t, w_stats = _weights(w, policy.weight, transpose=True)
    with _scope("gemm", "fwd"):
        y = _ragged(xq, jnp.swapaxes(wq_t, 1, 2), groups.sizes, x.dtype)
    with jax.named_scope("mor_quant"):
        fwd_stats = jnp.stack([x_stats, w_stats], axis=1)
    # Square blocks that never straddle a group: Q(x_g^T) = Q(x_g)^T, so
    # the wgrad_x event is fwd_x's, values and stats: keep the quantized
    # rows for wgrad rather than x, and quantize nothing twice.
    quantized = policy.enabled and policy.quantize_bwd
    return (y, fwd_stats), (xq if quantized else x, x_stats, w, groups)


def _product_again(res, policy: MoRDotPolicy):
    """The forward product of :func:`_product_fwd` again, from its
    residuals: the kept rows are the quantized ones where it quantized."""
    x, _, w, groups = res
    with _scope("mor_quant", "fwd_w"):
        wq_t, _ = _weights(w, policy.weight, transpose=True)
    with _scope("gemm", "fwd"):
        return _ragged(x, jnp.swapaxes(wq_t, 1, 2), groups.sizes, x.dtype)


def _product_bwd(res, dy, policy: MoRDotPolicy):
    """(dx, dw, the backward events' stats (G, N_BWD_EVENTS,
    STATS_WIDTH)) of :func:`_product_fwd`. The weight's gradient is
    finished before dx is handed on, so that the quantized dy and the
    kept rows are freed before the next backward product allocates."""
    x, xT_stats, w, groups = res
    if not (policy.enabled and policy.quantize_bwd):
        with _scope("gemm", "dgrad"):
            dx = _ragged(dy, jnp.swapaxes(w, 1, 2), groups.sizes, x.dtype)
        with _scope("gemm", "wgrad"):
            dw = _ragged_wgrad(x, dy, groups.sizes)
        stats = jnp.zeros((w.shape[0], N_BWD_EVENTS, STATS_WIDTH),
                          jnp.float32)
    else:
        with _scope("mor_quant", "dgrad_dy"):
            dyq, dy_stats = grouped_quantize(dy, groups, policy.grad)
        with _scope("mor_quant", "dgrad_w"):
            w_kn, w_stats = _weights(w, policy.weight, transpose=False)
        with _scope("gemm", "dgrad"):
            dx = _ragged(dyq, jnp.swapaxes(w_kn, 1, 2), groups.sizes,
                         x.dtype)
        # Q(dy_g^T) = Q(dy_g)^T likewise; x holds the forward's Q(x).
        with _scope("gemm", "wgrad"):
            dw = _ragged_wgrad(x, dyq, groups.sizes)
        with jax.named_scope("mor_quant"):
            stats = jnp.stack([dy_stats, w_stats, xT_stats, dy_stats],
                              axis=1)
    dx, dw = jax.lax.optimization_barrier((dx, dw))
    return dx.astype(x.dtype), dw.astype(w.dtype), stats


def _chunk(R: int) -> int:
    """Rows a step of the loops over the live rows: the largest divisor
    of R up to ``LIVE_CHUNK``, so that no step reaches past the buffer."""
    c = min(LIVE_CHUNK, R)
    while R % c:
        c -= 1
    return c


def over_live_rows(fn, live, bufs, *rows):
    """Runs ``fn`` over the first ``live`` rows of a buffer, a chunk at a
    time, and returns ``bufs`` with those chunks replaced.

    ``bufs`` and ``rows`` are (R, ...) arrays. ``fn`` takes a chunk of
    each buffer (its current content, so that an output may overwrite
    its own input in place) and of each of ``rows``, and returns the
    buffers' new chunks. The loop stops at the chunk that holds row
    ``live - 1``: the work follows the routed rows and not the bound, and
    rows past that chunk keep the buffers' content."""
    R = bufs[0].shape[0]
    c = _chunk(R)

    def body(i, bufs):
        def part(a):
            return jax.lax.dynamic_slice_in_dim(a, i * c, c, 0)

        got = fn(*map(part, bufs), *map(part, rows))
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            b, g.astype(b.dtype), i * c, 0) for b, g in zip(bufs, got))

    return jax.lax.fori_loop(0, -(-live // c), body, tuple(bufs))


def _gated(act):
    """(h rows, their gates) -> ``act`` of h times each row's gate,
    computed in f32 and returned in h's dtype."""
    def f(h, gate):
        a = act(h.astype(jnp.float32))
        return (a * gate[:, None]).astype(h.dtype)

    return f


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def grouped_ffn(x, w1, w2, gate, tok1, tok2, groups: Groups,
                policy: MoRDotPolicy, act):
    """The experts' FFN over a buffer of rows by group:
    y = MoR(a) @ MoR(w2[g]) with a = act(MoR(x) @ MoR(w1[g])) * gate.

    x: (R, d) rows by group (``Groups``), w1: (G, d, H), w2: (G, F, d),
    act: (rows, H) -> (rows, F) elementwise in each row and mapping zero
    rows to zero (a GLU halves H), gate: (R,) f32 the weight of each row
    (0 for padding), tok1,
    tok2: (G, N_BWD_EVENTS, STATS_WIDTH) zeros whose cotangents carry
    the two products' backward stats. Each product is every group's own
    MoR tensors (``_product_fwd``); the activation runs over the live
    rows (``over_live_rows``). Returns (y (R, d) in x.dtype, zero past
    the last group; the two products' fwd_stats).

    The backward pass keeps the two products' quantized inputs, and
    multiplies the first product again rather than keep its (R, 2F)
    output from the forward pass; the activation's gradient overwrites
    that output in place.
    """
    out, _ = _ffn_fwd(x, w1, w2, gate, tok1, tok2, groups, policy, act)
    return out


def _ffn_fwd(x, w1, w2, gate, tok1, tok2, groups, policy, act):
    del tok1, tok2
    live = jnp.sum(groups.sizes)
    (h, st1), res1 = _product_fwd(x, w1, groups, policy)
    f = _gated(act)
    a, = over_live_rows(lambda _, hc, gc: (f(hc, gc),), live,
                        (jnp.zeros((h.shape[0], w2.shape[1]), h.dtype),),
                        h, gate)
    (y, st2), res2 = _product_fwd(a, w2, groups, policy)
    return (y, st1, st2), (res1, res2, gate)


def _ffn_bwd(policy, act, res, cts):
    res1, res2, gate = res
    groups = res1[3]
    live = jnp.sum(groups.sizes)
    da, dw2, tok2 = _product_bwd(res2, cts[0], policy)
    h = _product_again(res1, policy)
    f = _gated(act)

    def grads(hc, _, gc, dac):
        return jax.vjp(f, hc, gc)[1](dac)

    dh, dgate = over_live_rows(grads, live,
                               (h, jnp.zeros(gate.shape, gate.dtype)),
                               gate, da)
    dx, dw1, tok1 = _product_bwd(res1, dh, policy)
    return dx, dw1, dw2, dgate, tok1, tok2, None


grouped_ffn.defvjp(_ffn_fwd, _ffn_bwd)
