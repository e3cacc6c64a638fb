"""mor_dot: the MoR-quantized GEMM primitive (paper §4 integration point).

Faithful to the paper's Megatron hook placement: for a linear layer
``y = x @ w`` we fake-quantize, per policy,

  forward:   Q(x) @ Q(w)                          (act + weight events)
  backward:  dx = Q(dy) @ Q(w)^T                  (grad + weight events)
             dw = Q(x^T) @ Q(dy^T)                (act^T + grad^T events)

Each quantization event sees its operand as a 2-D view whose *last* axis is
that GEMM's contraction axis, so per-channel/sub-channel partitioning is
aligned with the dot-product dimension in all three GEMMs (paper §3.1,
"based on the dot product direction").

Stats plumbing: forward stats are a normal output; backward stats leave the
VJP as the cotangent of a zero-valued ``token`` argument -- a purely
functional channel that stacks naturally under ``lax.scan`` over layers.

mor_dot returns f32-accumulated results cast back to the input dtype
(bf16 in training), matching mixed-precision GEMM semantics.

GEMM lowerings (``MoRDotPolicy.fuse_gemm``):

  * fake-quant (default): each event dequantizes back to BF16 and the
    three GEMMs are plain bf16 ``jnp.dot`` -- the per-block E4M3/E5M2
    decisions never reach the matmul.
  * fused: each event packs real uint8 fp8 payloads + per-block
    tags/scales (``core.mor.quantize_for_gemm``) and all three GEMMs run
    through the mixed-representation block kernel
    (``repro.kernels.mixed_gemm``) -- per-block representations are
    decoded in-register inside the matmul. Same decisions, same stats
    rows (one shared decision path), outputs within f32-accumulation
    ordering tolerance.

Named scopes: each quantization event runs under ``mor_quant/<role>``
(``fwd_x``, ``fwd_w``, ``dgrad_dy``, ``dgrad_w``, ``wgrad_x``,
``wgrad_dy``) and each product under ``gemm/<which>`` (``fwd``,
``dgrad``, ``wgrad``), siblings under the caller's scope, so a device
trace can tell quantization time from matmul time per sublayer.

Serving: a weight that is already real-quantized (``serve.quantized
.QTensor``; anything exposing ``as_mixed_operand()``) is consumed
directly by the mixed kernel against a BF16-passthrough activation
pack -- no dequantize-materialize step, no grad support.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .mor import STATS_WIDTH, mor_quantize, quantize_for_gemm
from .policy import MoRDotPolicy

# Loaded after .mor so the core -> kernels import chain is already
# resolved (see the import note in core/mor.py).
from repro.kernels import ops as kops

__all__ = [
    "N_FWD_EVENTS",
    "N_BWD_EVENTS",
    "new_token",
    "mor_dot",
]

N_FWD_EVENTS = 2  # x, w
N_BWD_EVENTS = 4  # dy(dgrad), w(dgrad), x^T(wgrad), dy^T(wgrad)


def new_token() -> jnp.ndarray:
    """Zero token whose cotangent carries the N_BWD_EVENTS stats rows."""
    return jnp.zeros((N_BWD_EVENTS, STATS_WIDTH), dtype=jnp.float32)


@contextlib.contextmanager
def _scope(layer: str, part: str):
    with jax.named_scope(layer), jax.named_scope(part):
        yield


def _flat2d(x: jnp.ndarray) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _is_mixed_weight(w) -> bool:
    """Real-quantized serving weight (QTensor or compatible)."""
    return hasattr(w, "as_mixed_operand")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def mor_dot(x, w, token, policy: MoRDotPolicy):
    """y = MoR(x) @ MoR(w).  x: (..., K), w: (K, N), token: new_token().

    Returns (y: (..., N) in x.dtype, fwd_stats: (N_FWD_EVENTS, STATS_WIDTH)).

    >>> import jax.numpy as jnp
    >>> from repro.core.linear import mor_dot, new_token
    >>> from repro.core.policy import SUBTENSOR3_MOR
    >>> x = jnp.ones((4, 128), jnp.bfloat16)
    >>> w = jnp.ones((128, 32), jnp.bfloat16)
    >>> y, fwd_stats = mor_dot(x, w, new_token(), SUBTENSOR3_MOR)
    >>> y.shape, fwd_stats.shape       # one stats row per fwd event
    ((4, 32), (2, 14))
    >>> float(y[0, 0])                 # ones @ ones, exact under fp8
    128.0

    The fused GEMM lowering is a policy flag, not a different API:

    >>> yf, _ = mor_dot(x, w, new_token(), SUBTENSOR3_MOR.replace(
    ...     fuse_gemm=True))
    >>> bool(jnp.allclose(yf.astype(jnp.float32), y.astype(jnp.float32)))
    True

    Mesh-sharded use (docs/sharding.md): inside a ``shard_map`` body,
    run mor_dot on the local batch shard with every operand policy
    carrying ``mesh_axes`` (``core.policy.with_mesh_axes``). The
    quantization decisions then match the single-device run
    bit-for-bit; the wgrad output is a per-shard partial that the
    caller psums over the batch axes, exactly like an unquantized dot.
    """
    out, _ = _fwd(x, w, token, policy)
    return out


def _plain_dot(x, w):
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def _check_fusable(policy: MoRDotPolicy):
    """The mixed GEMM tiles all three dots with one block grid: every
    enabled operand policy must be 'block'-partitioned with one shared
    block shape (so the contraction blocks of both operands of each
    GEMM, and of the transposed wgrad views, line up)."""
    ps = [("act", policy.act), ("weight", policy.weight)]
    if policy.quantize_bwd:
        ps.append(("grad", policy.grad))
    shapes = set()
    for name, p in ps:
        # Disabled events still pack (as BF16 passthrough) on this
        # policy's block grid, so its block_shape must agree too.
        shapes.add(tuple(p.block_shape))
        if p.enabled and p.partition != "block":
            raise ValueError(
                f"fuse_gemm=True needs partition='block' for the {name} "
                f"policy (got {p.partition!r})"
            )
    if len(shapes) > 1:
        raise ValueError(
            f"fuse_gemm=True needs one shared block_shape, got {shapes}"
        )


def _serve_fwd(x, w, policy: MoRDotPolicy):
    """Forward against a real-quantized (mixed-layout) serving weight."""
    mo = w.as_mixed_operand()  # (N, K) quantization view
    x2, lead = _flat2d(x)
    with _scope("gemm", "fwd"):
        y = kops.mixed_dot(
            x2, mo, out_dtype=x.dtype, backend=policy.weight.backend
        ).reshape(*lead, w.shape[1])
    fwd_stats = jnp.zeros((N_FWD_EVENTS, STATS_WIDTH), jnp.float32)
    return (y, fwd_stats), (x, w)


def _fwd(x, w, token, policy: MoRDotPolicy):
    del token
    if _is_mixed_weight(w):
        return _serve_fwd(x, w, policy)
    if not policy.enabled:
        with _scope("gemm", "fwd"):
            y = _plain_dot(x, w)
        fwd_stats = jnp.zeros((N_FWD_EVENTS, STATS_WIDTH), jnp.float32)
        return (y, fwd_stats), (x, w)

    x2, lead = _flat2d(x)
    if policy.fuse_gemm:
        _check_fusable(policy)
        # Activation event (M, K) and weight event (N, K): both packed
        # for real, contraction last; the kernel consumes the payloads.
        with _scope("mor_quant", "fwd_x"):
            a_mo, x_stats = quantize_for_gemm(x2, policy.act)
        with _scope("mor_quant", "fwd_w"):
            b_mo, w_stats = quantize_for_gemm(w.T, policy.weight)
        with _scope("gemm", "fwd"):
            y = kops.mixed_gemm(
                a_mo, b_mo, out_dtype=x.dtype, backend=policy.act.backend
            )
    else:
        # Activation event: (M, K), contraction last.
        with _scope("mor_quant", "fwd_x"):
            xq, x_stats = mor_quantize(x2, policy.act)
        # Weight event for the fwd GEMM: w is (K, N), contraction first ->
        # quantize the (N, K) transposed view so channels align with the
        # dot dim.
        with _scope("mor_quant", "fwd_w"):
            wq_t, w_stats = mor_quantize(w.T, policy.weight)
        with _scope("gemm", "fwd"):
            y = jnp.dot(
                xq, wq_t.T, preferred_element_type=jnp.float32
            ).astype(x.dtype)
    y = y.reshape(*lead, w.shape[1])
    with jax.named_scope("mor_quant"):
        fwd_stats = jnp.stack([x_stats, w_stats])
    return (y, fwd_stats), (x, w)


def _transpose_invariant(p) -> bool:
    """Quantizing the transposed view == transposing the quantized view.

    Holds exactly for per-tensor scaling and square per-block scaling
    (block amaxes/scales are permutation-invariant under block transpose);
    per-channel / sub-channel scaling is direction-dependent (paper §3.1),
    so those must re-quantize the transposes. NVFP4 (sub4) is likewise
    direction-dependent: its 1x16 micro-blocks and row-paired nibble
    packing follow the contraction axis, so sub4 events always
    re-quantize (and re-pack) the transposed views.
    """
    if p.recipe == "sub4":
        return False
    if p.partition == "tensor":
        return True
    if p.partition == "block" and p.block_shape[0] == p.block_shape[1]:
        return True
    return False


def _bwd_fused(policy: MoRDotPolicy, x2, dy2, lead, x, w):
    """dgrad + wgrad through the mixed-representation kernel, mirroring
    the fake-quant branch structure event for event (same stats rows)."""
    be = policy.grad.backend
    # dgrad GEMM: dx[m,k] = sum_n dy[m,n] * w[k,n] -- both views
    # contraction-last already.
    with _scope("mor_quant", "dgrad_dy"):
        dy_mo, dy_stats = quantize_for_gemm(dy2, policy.grad)  # (M, N)
    with _scope("mor_quant", "dgrad_w"):
        w_mo, w_stats = quantize_for_gemm(w, policy.weight)    # (K, N)
    with _scope("gemm", "dgrad"):
        dx = kops.mixed_gemm(
            dy_mo, w_mo, out_dtype=x.dtype, backend=be
        ).reshape(*lead, x.shape[-1])

    # wgrad GEMM: dw[k,n] = sum_m x[m,k] * dy[m,n].
    if _transpose_invariant(policy.act) and _transpose_invariant(policy.grad):
        # Q(x^T) == Q(x)^T bit-exactly: pack the (M, K) view and
        # transpose the pack (tags/scales/payloads permute with the
        # blocks), reusing the dy pack outright.
        with _scope("mor_quant", "wgrad_x"):
            x_mo, xT_stats = quantize_for_gemm(x2, policy.act)
        with _scope("gemm", "wgrad"):
            dw = kops.mixed_gemm(
                x_mo.transpose(), dy_mo.transpose(),
                out_dtype=w.dtype, backend=be,
            )
        dyT_stats = dy_stats
    else:
        with _scope("mor_quant", "wgrad_x"):
            xT_mo, xT_stats = quantize_for_gemm(x2.T, policy.act)  # (K, M)
        with _scope("mor_quant", "wgrad_dy"):
            dyT_mo, dyT_stats = quantize_for_gemm(dy2.T, policy.grad)
        with _scope("gemm", "wgrad"):
            dw = kops.mixed_gemm(
                xT_mo, dyT_mo, out_dtype=w.dtype, backend=be
            )
    with jax.named_scope("mor_quant"):
        token_grad = jnp.stack([dy_stats, w_stats, xT_stats, dyT_stats])
    return dx, dw, token_grad


def _bwd(policy: MoRDotPolicy, res, cts):
    x, w = res
    if _is_mixed_weight(w):
        raise NotImplementedError(
            "mor_dot cannot differentiate through a real-quantized "
            "(QTensor) serving weight"
        )
    dy, _dstats = cts
    dy2, _ = _flat2d(dy)
    x2, lead = _flat2d(x)

    if not (policy.enabled and policy.quantize_bwd):
        with _scope("gemm", "dgrad"):
            dx = jnp.dot(
                dy2, w.T, preferred_element_type=jnp.float32
            ).astype(x.dtype).reshape(x.shape)
        with _scope("gemm", "wgrad"):
            dw = jnp.dot(
                x2.T, dy2, preferred_element_type=jnp.float32
            ).astype(w.dtype)
        return dx, dw, jnp.zeros((N_BWD_EVENTS, STATS_WIDTH), jnp.float32)

    if policy.fuse_gemm:
        _check_fusable(policy)
        return _bwd_fused(policy, x2, dy2, lead, x, w)

    # dgrad GEMM: dx[m,k] = sum_n dy[m,n] * w[k,n].
    with _scope("mor_quant", "dgrad_dy"):
        dyq, dy_stats = mor_quantize(dy2, policy.grad)      # (M, N) contr. n
    with _scope("mor_quant", "dgrad_w"):
        w_kn, w_stats = mor_quantize(w, policy.weight)      # (K, N) contr. n
    with _scope("gemm", "dgrad"):
        dx = jnp.dot(
            dyq, w_kn.T, preferred_element_type=jnp.float32
        ).astype(x.dtype).reshape(*lead, x.shape[-1])

    # wgrad GEMM: dw[k,n] = sum_m x[m,k] * dy[m,n].
    # For transpose-invariant partitions, Q(x^T) == Q(x)^T bit-exactly, so
    # re-quantizing along M re-uses the same quantized values (avoids two
    # extra full-tensor quantization passes; Perf iteration 2).
    if _transpose_invariant(policy.act) and _transpose_invariant(policy.grad):
        with _scope("mor_quant", "wgrad_x"):
            xTq, xT_stats = mor_quantize(x2, policy.act)
        dyTq, dyT_stats = dyq, dy_stats  # Q(dy^T) == Q(dy)^T: reuse
        with _scope("gemm", "wgrad"):
            dw = jax.lax.dot_general(
                xTq, dyTq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(w.dtype)
    else:
        with _scope("mor_quant", "wgrad_x"):
            xTq, xT_stats = mor_quantize(x2.T, policy.act)  # (K, M) contr. m
        with _scope("mor_quant", "wgrad_dy"):
            dyTq, dyT_stats = mor_quantize(dy2.T, policy.grad)  # (N, M)
        with _scope("gemm", "wgrad"):
            dw = jnp.dot(
                xTq, dyTq.T, preferred_element_type=jnp.float32
            ).astype(w.dtype)

    with jax.named_scope("mor_quant"):
        token_grad = jnp.stack([dy_stats, w_stats, xT_stats, dyT_stats])
    return dx, dw, token_grad


mor_dot.defvjp(_fwd, _bwd)
