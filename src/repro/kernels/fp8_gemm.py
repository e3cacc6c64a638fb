"""Per-block-scaled FP8 GEMM kernel (Pallas, TPU target).

C = (A_q * a_scale) @ (B_q * b_scale) with E4M3 payloads and one f32
scale per 128x128 block of each operand (the GAM-reconstructed scales:
shared group mantissa x per-block E8M0 exponent). Accumulation is f32 in
a VMEM scratch tile; scales are applied once per K-block, DeepSeek-style.

This is the real-quantization serving path: weights (and optionally
activations) stored as QTensors (repro.serve.quantized) flow through this
kernel; on hardware the 2x bandwidth saving is realized even though the
v5e MXU computes in bf16 (payloads upcast in-register after the VMEM load).

Grid: (M/bm, N/bn, K/bk), K innermost ('arbitrary'), f32 accum scratch.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fp8_gemm"]


def _kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    sa = sa_ref[0, 0]  # scale of this (i, k) block of A
    sb = sb_ref[0, 0]  # scale of this (k, j) block of B
    # Dequantize once per block pair: (A/sa) @ (B/sb) == AB / (sa*sb).
    part = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] += part / (sa * sb)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "out_dtype", "interpret")
)
def fp8_gemm(
    a_q: jnp.ndarray,
    b_q: jnp.ndarray,
    a_scale: jnp.ndarray,
    b_scale: jnp.ndarray,
    *,
    block: Tuple[int, int, int] = (128, 128, 128),
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jnp.ndarray:
    """a_q: (M, K) fp8 (scaled values); b_q: (K, N) fp8;
    a_scale: (M/bm, K/bk) f32; b_scale: (K/bk, N/bn) f32.

    Returns (M, N) in out_dtype: the dequantized product.
    """
    M, K = a_q.shape
    K2, N = b_q.shape
    assert K == K2
    bm, bn, bk = block
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk

    kernel = functools.partial(_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="fp8_gemm",
    )(a_q, b_q, a_scale, b_scale)
