"""AdamW with f32 master weights + moments (Megatron-style mixed precision).

Model params live in BF16; the optimizer state holds an f32 master copy
plus Adam moments, all ZeRO-1-shardable (see repro.sharding.rules). The
update runs on the master weights and re-casts to BF16 params.

With a :class:`~repro.optim.moments.MomentPolicy` the Adam moments are
stored as packed MoR payloads (:class:`~repro.optim.moments.PackedMoment`
leaves): decoded to f32 at the top of the update, re-encoded through the
real per-block selection machinery at the bottom -- see
repro.optim.moments for the bytes-per-param budget and docs/training.md
for the layout. ``OptState.ef`` carries the gradient-compression
error-feedback residual when the train step runs an ``*_ef`` mode
(repro.optim.compress); it defaults to None and is absent from the
pytree then.

No optax in this environment -- this is a standalone implementation with
global-norm clipping and a cosine LR schedule.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mor import EVENT_MOMENT_M, EVENT_MOMENT_V
from repro.optim.moments import (
    MomentPolicy,
    PackedMoment,
    decode_any,
    maybe_encode_moment,
    mean_logical_bpe,
    moment_stats_rows,
)

__all__ = ["AdamWConfig", "OptState", "init_opt_state", "adamw_update",
           "cosine_lr", "global_norm"]


def _is_pm(x) -> bool:
    return isinstance(x, PackedMoment)


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    final_lr: float = 3e-5
    warmup_steps: int = 100
    total_steps: int = 10000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


class OptState(NamedTuple):
    master: Any  # f32 master weights (pytree like params)
    m: Any  # f32 moments, or PackedMoment leaves under a MomentPolicy
    v: Any
    step: jnp.ndarray  # () int32
    # Gradient-compression error-feedback residual (f32, params-shaped)
    # for the '*_ef' compress modes; None (an empty subtree) otherwise.
    ef: Any = None


def init_opt_state(
    params,
    moments: Optional[MomentPolicy] = None,
    ef: bool = False,
) -> OptState:
    """Fresh optimizer state. ``moments`` packs the Adam moment leaves
    (repro.optim.moments); ``ef=True`` allocates the error-feedback
    residual tree the '*_ef' gradient-compression modes thread through
    steps."""
    # copy=True: an f32 param (norm scales) must not share its buffer
    # with its master copy, or a step donating both params and state
    # would donate one buffer twice.
    f32 = lambda p: p.astype(jnp.float32, copy=True)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)

    def moment(p, kind):
        return maybe_encode_moment(zeros(p), moments, kind)

    return OptState(
        master=jax.tree.map(f32, params),
        m=jax.tree.map(lambda p: moment(p, EVENT_MOMENT_M), params),
        v=jax.tree.map(lambda p: moment(p, EVENT_MOMENT_V), params),
        step=jnp.zeros((), jnp.int32),
        ef=jax.tree.map(zeros, params) if ef else None,
    )


def cosine_lr(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    step = step.astype(jnp.float32)
    warm = cfg.peak_lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip(
        (step - cfg.warmup_steps)
        / max(cfg.total_steps - cfg.warmup_steps, 1),
        0.0,
        1.0,
    )
    cos = cfg.final_lr + 0.5 * (cfg.peak_lr - cfg.final_lr) * (
        1 + jnp.cos(jnp.pi * prog)
    )
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def global_norm(tree) -> jnp.ndarray:
    leaves = [
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)
    ]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


@jax.named_scope("optim")
def adamw_update(
    cfg: AdamWConfig,
    grads,
    opt_state: OptState,
    *,
    decay_mask=None,
    moments: Optional[MomentPolicy] = None,
    guard: Optional["GuardPolicy"] = None,
) -> Tuple[Any, OptState, dict]:
    """Returns (new bf16 params, new opt state, metrics).

    With ``moments``, PackedMoment leaves in ``opt_state.m``/``.v`` are
    decoded to f32 for the update and the new moments are re-encoded
    through the same policy (the dense/packed split per leaf is static,
    so the state pytree structure is step-invariant). Metrics then also
    carry the optimizer-event stats rows (``moment_stats_m/v``, used by
    train_step's summarizer) and the parameter-weighted logical
    bytes/param of each packed moment tree (``moment_bpe_m/v``).
    ``opt_state.ef`` rides through untouched -- the gradient
    compression that owns it runs *before* this update.

    With a ``guard`` (:class:`repro.robust.GuardPolicy`) whose
    ``skip_nonfinite_updates`` is set, a nonfinite global grad norm --
    any NaN/Inf gradient element makes the already-computed ``gnorm``
    nonfinite, so detection is free -- drops the whole update: master
    weights, both Adam moments (packed payload lanes bit-exact, since
    ``select`` picks values and the poisoned branch never propagates)
    and the step counter all keep their previous values. Metrics then
    carry ``guard_skip`` (1.0 on a dropped step) for train_step's EF
    preservation and the chaos suite's counters."""
    step = opt_state.step + 1
    lr = cosine_lr(cfg, step)

    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(
        lambda g: g.astype(jnp.float32) * scale, grads
    )

    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    m_dec = jax.tree.map(decode_any, opt_state.m, is_leaf=_is_pm)
    v_dec = jax.tree.map(decode_any, opt_state.v, is_leaf=_is_pm)
    new_m = jax.tree.map(
        lambda m, g: b1 * m + (1 - b1) * g, m_dec, grads
    )
    new_v = jax.tree.map(
        lambda v, g: b2 * v + (1 - b2) * g * g, v_dec, grads
    )

    if decay_mask is None:
        decay_mask = jax.tree.map(
            lambda p: 1.0 if p.ndim >= 2 else 0.0, opt_state.master
        )

    def upd(master, m, v, wd):
        mh = m / c1
        vh = v / c2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * wd * master
        return master - lr * delta

    new_master = jax.tree.map(
        upd, opt_state.master, new_m, new_v, decay_mask
    )
    new_params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), new_master
    )
    metrics = {"lr": lr, "grad_norm": gnorm}
    if moments is not None and moments.enabled:
        new_m = jax.tree.map(
            lambda x: maybe_encode_moment(x, moments, EVENT_MOMENT_M),
            new_m,
        )
        new_v = jax.tree.map(
            lambda x: maybe_encode_moment(x, moments, EVENT_MOMENT_V),
            new_v,
        )
        for name, tree in (("m", new_m), ("v", new_v)):
            rows = moment_stats_rows(tree)
            if rows is not None:
                metrics[f"moment_stats_{name}"] = rows
            metrics[f"moment_bpe_{name}"] = mean_logical_bpe(tree)
    if guard is not None and guard.skip_nonfinite_updates:
        from repro.robust.guard import tree_select

        ok = jnp.isfinite(gnorm)
        new_master = tree_select(ok, new_master, opt_state.master)
        new_m = tree_select(ok, new_m, opt_state.m)
        new_v = tree_select(ok, new_v, opt_state.v)
        step = jnp.where(ok, step, opt_state.step)
        # Params re-derive from the *selected* master so a skipped step
        # republishes the exact previous weights.
        new_params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16), new_master
        )
        metrics["guard_skip"] = 1.0 - ok.astype(jnp.float32)
    new_state = OptState(
        new_master, new_m, new_v, step, opt_state.ef
    )
    return new_params, new_state, metrics
