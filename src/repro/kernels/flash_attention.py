"""Flash attention forward kernel (Pallas, TPU target).

Online-softmax over KV blocks with (m, l, acc) persisted in VMEM scratch
across the innermost grid dimension; causal masking by true key/query
position. The (S, T) score matrix never leaves VMEM -- this kernel is the
hardware realization of the chunked XLA attention in
repro.models.attention (whose remat-ed scan is the portable fallback used
by the dry-run).

Layout: q (BH, S, d), k/v (BH, T, d) -- callers fold batch x heads.
``ops.flash_attention`` accepts the unfolded GQA layout ((B, S, Hq, dh)
queries against (B, T, Hkv, dh) caches) and repeats kv heads into the
q-head count before folding; this module only ever sees matched head
counts. Grid: (BH, S/bq, T/bk), KV innermost.

Queries need not start at key position 0: ``q_offset`` (scalar or one
entry per folded BH row) gives the key position of query row 0, so a
short query chunk attends correctly against a longer cache (S < T).
The default places the *last* query at the *last* key (offset T - S),
matching ``ref.flash_attention_ref`` and the decode convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

_NEG = -1e30


def _divisor_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (pick_chunk-style): the
    kernel grid needs bq | S and bk | T, so ragged extents shrink the
    block instead of erroring."""
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def _kernel(q_ref, k_ref, v_ref, off_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, bq: int, bk: int, n_k: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    qi = pl.program_id(1)
    off = off_ref[b]  # key position of this row's query 0 (SMEM scalar)

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        if causal:
            q_pos = off + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0
            )
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1
            )
            s = jnp.where(k_pos <= q_pos, s, _NEG)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # Skip key blocks strictly above this query block's last true
        # position (off + qi*bq + bq - 1).
        @pl.when(kj * bk <= off + qi * bq + bq - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(kj == n_k - 1)
    def _():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset=None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (BH, S, d); k, v: (BH, T, d). Returns (BH, S, d) in q.dtype.

    ``q_offset``: key position of query row 0 -- a scalar shared by all
    rows or a (BH,) vector (one per folded batch*head row, the serving
    engine's mixed-length chunks). Default ``None`` aligns the last
    query with the last key (offset ``T - S``; identity when S == T).
    Ignored for ``causal=False``.
    """
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3:
        raise ValueError(
            f"flash_attention_fwd wants folded (BH, S|T, d) operands, "
            f"got q{q.shape} k{k.shape} v{v.shape}"
        )
    BH, S, d = q.shape
    T = k.shape[1]
    if k.shape != (BH, T, d) or v.shape != (BH, T, d):
        raise ValueError(
            f"k/v must be (BH={BH}, T, d={d}) and match: "
            f"got k{k.shape} v{v.shape}"
        )
    if block_q < 1 or block_k < 1:
        raise ValueError(
            f"block sizes must be positive, got block_q={block_q} "
            f"block_k={block_k}"
        )
    # Blocks must tile the sequence extents; ragged S/T shrink to the
    # largest dividing block instead of failing (bq=1 worst case).
    bq = _divisor_block(S, block_q)
    bk = _divisor_block(T, block_k)
    if S % bq or T % bk:  # pragma: no cover - _divisor_block guarantees
        raise ValueError(
            f"block grid does not tile the operand: S={S} bq={bq} "
            f"T={T} bk={bk}"
        )
    n_k = T // bk
    scale = d**-0.5

    off = jnp.asarray(
        T - S if q_offset is None else q_offset, jnp.int32
    ).reshape(-1)
    if off.shape[0] not in (1, BH):
        raise ValueError(
            f"q_offset must be a scalar or one entry per BH={BH} row, "
            f"got shape {off.shape}"
        )
    off = jnp.broadcast_to(off, (BH,))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_k=n_k
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_offset (BH,)
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v, off)
