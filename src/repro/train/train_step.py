"""The jitted training step: loss -> grads -> (optional microbatching,
gradient compression) -> AdamW update, with MoR stats as outputs.

This is the function the multi-pod dry-run lowers and the trainer runs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import STATS_WIDTH, MoRDotPolicy, MoRPolicy, with_mesh_axes
from repro.core.mor import STAT_FALLBACK_COUNT, STAT_GUARD_FLAGS
from repro.models import make_loss_fn, make_tokens
from repro.models.common import constrain
from repro.optim.adamw import (
    AdamWConfig,
    OptState,
    adamw_update,
    global_norm,
)
from repro.optim.compress import DEFAULT_GRAD_POLICY
from repro.optim.moments import MomentPolicy
from repro.robust.guard import GuardPolicy, tree_select
from repro.sharding import rules as _rules

__all__ = ["TrainConfig", "make_train_step", "summarize_mor_stats"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    # Microbatching: split the global batch into n accumulation steps.
    grad_accum: int = 1
    remat: bool = True
    # Gradient compression (repro.optim.compress): legacy per-tensor
    # E4M3 ('fp8'/'fp8_ef') or per-block MoR selection ('mor'/'mor_ef')
    # under ``grad_policy``. The '*_ef' modes keep an error-feedback
    # residual in OptState.ef -- create the state with
    # ``init_opt_state(params, ef=True)``.
    compress_grads: str = "none"  # 'none'|'fp8'|'fp8_ef'|'mor'|'mor_ef'
    grad_policy: MoRPolicy = DEFAULT_GRAD_POLICY
    # Adam moments stored as packed MoR payloads (repro.optim.moments);
    # None keeps the dense-f32 layout. Must match the MomentPolicy the
    # opt state was initialized with.
    moments: MomentPolicy | None = None
    aux_coef: float = 0.01
    # ZeRO-2: constrain gradients to the data-sharded optimizer layout so
    # GSPMD reduce-scatters them instead of all-reducing (halves DP
    # gradient traffic; optimizer math runs on the scattered shards).
    zero2_grads: bool = True
    # shard_map embedding: when the returned step runs *inside* a
    # shard_map body (manual SPMD, e.g. the cross-pod compressed-psum
    # trainer), name the batch-sharded mesh axes here so every MoR
    # quantization event allreduces its global statistics and the
    # precision decisions match the single-device run bit-for-bit
    # (docs/sharding.md). Leave () for the jit/GSPMD trainer: there the
    # compiler already makes jnp reductions over sharded operands
    # global, so no explicit collectives are needed.
    mor_mesh_axes: Tuple[str, ...] = ()
    # Numerics guard rails (docs/robustness.md): with a GuardPolicy,
    # adamw_update drops updates whose global grad norm is nonfinite
    # (master/moments/step preserved bit-exactly) and this step keeps
    # the EF residuals of the skipped update -- a dropped step must not
    # absorb its own quantization error into EF (no double count).
    # None keeps the unguarded behavior.
    guard: GuardPolicy | None = None


@jax.named_scope("mor_quant")
@jax.named_scope("stats")
def summarize_mor_stats(
    fwd_stats, bwd_stats, opt_stats=None
) -> Dict[str, jnp.ndarray]:
    """Reduce the per-layer/per-event stats pytrees to scalar metrics
    (scoped ``mor_quant/stats`` in the compiled step).

    Disabled-policy events (recipe 'off', decision column == -1) are
    excluded: a passthrough event reports ``frac_bf16 = 1.0`` by
    construction, and averaging those rows in dragged ``fwd_frac_bf16``
    toward 1 even when every *enabled* event quantized. With no enabled
    events at all, every metric is 0.

    ``opt_stats`` carries the optimizer-event rows (stats layout v4,
    event_kind > 0): gradient-compression and packed-moment encode
    events, summarized into the ``opt_*`` family the same way --
    ``opt_frac_bf16``/``opt_rel_err`` plus ``opt_payload_bpe`` (mean
    stats lane [11], the logical bytes/param of the compressed state).

    Guard counters (docs/robustness.md) aggregate over *every* row,
    disabled events included (a passthrough event can still carry a
    poisoned operand worth reporting): ``guard_flag_events`` counts
    rows with any guard flag set, ``guard_fallback_blocks`` sums the
    nonfinite-block fallback counts.
    """

    def rows(tree):
        leaves = [
            l.reshape(-1, l.shape[-1])
            for l in jax.tree.leaves(tree)
            if hasattr(l, "ndim") and l.ndim >= 1
            and l.shape[-1] == STATS_WIDTH
        ]
        if not leaves:
            return None
        return jnp.concatenate(leaves)

    def frac(cat, idx):
        if cat is None:
            return jnp.float32(0.0)
        enabled = cat[:, 0] >= 0.0  # decision == -1: disabled sentinel
        n = jnp.maximum(jnp.sum(enabled.astype(jnp.float32)), 1.0)
        return jnp.sum(jnp.where(enabled, cat[:, idx], 0.0)) / n

    out = {}
    guard_events = jnp.float32(0.0)
    fallback_blocks = jnp.float32(0.0)

    def guard_tally(cat):
        nonlocal guard_events, fallback_blocks
        if cat is None:
            return
        guard_events += jnp.sum(
            (cat[:, STAT_GUARD_FLAGS] > 0.0).astype(jnp.float32)
        )
        fallback_blocks += jnp.sum(cat[:, STAT_FALLBACK_COUNT])

    if fwd_stats is not None:
        cat = rows(fwd_stats)
        out["fwd_frac_bf16"] = frac(cat, 5)
        out["fwd_rel_err"] = frac(cat, 1)
        guard_tally(cat)
    if bwd_stats is not None:
        cat = rows(bwd_stats)
        out["bwd_frac_bf16"] = frac(cat, 5)
        out["bwd_rel_err"] = frac(cat, 1)
        guard_tally(cat)
    if opt_stats is not None:
        cat = rows(opt_stats)
        out["opt_frac_bf16"] = frac(cat, 5)
        out["opt_rel_err"] = frac(cat, 1)
        out["opt_payload_bpe"] = frac(cat, 11)
        guard_tally(cat)
    out["guard_flag_events"] = guard_events
    out["guard_fallback_blocks"] = fallback_blocks
    return out


def make_train_step(
    cfg: ArchConfig,
    policy: MoRDotPolicy,
    tcfg: TrainConfig,
    grad_fault=None,
):
    """Returns train_step(params, opt_state, batch) ->
    (params, opt_state, metrics).

    ``grad_fault``: optional ``hook(grads, batch) -> grads`` applied to
    the accumulated gradients *before* compression -- the chaos
    harness's injection point (repro.robust.faults.make_grad_fault
    builds hooks gated on a ``batch['inject']`` flag, so one compiled
    step serves clean and injected steps). Production steps leave it
    None; the hook must be the identity for clean batches or the
    differential chaos assertions are meaningless."""
    if tcfg.mor_mesh_axes:
        policy = with_mesh_axes(policy, tcfg.mor_mesh_axes)
    loss_fn = make_loss_fn(
        cfg, policy, remat=tcfg.remat, aux_coef=tcfg.aux_coef
    )
    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)

    if tcfg.compress_grads != "none":
        from repro.optim.compress import compress_grads as _compress

        # Gradient compression quantizes *global* gradients; under a
        # shard_map trainer its statistics must allreduce like every
        # other event's.
        grad_policy = (
            tcfg.grad_policy.replace(mesh_axes=tuple(tcfg.mor_mesh_axes))
            if tcfg.mor_mesh_axes else tcfg.grad_policy
        )

    def single_micro(params, tokens, batch):
        (total, aux), (g_params, g_tokens) = grad_fn(params, tokens, batch)
        return total, aux, g_params, g_tokens

    def train_step(params, opt_state: OptState, batch):
        tokens = make_tokens(cfg)
        zspecs = (
            _rules.opt_state_spec_from_param(cfg, params)
            if tcfg.zero2_grads else None
        )

        def to_zero2(g_tree):
            # ZeRO-2: data-sharded gradient layout -> GSPMD emits
            # reduce-scatter instead of all-reduce (half the DP traffic)
            # and the f32 accumulation buffer is 1/DP the size. Applied
            # *inside* the microbatch loop so accumulation happens on
            # scattered shards (Megatron main-grads style).
            if zspecs is None:
                return g_tree
            return jax.tree.map(
                lambda g, sp: constrain(g, *sp), g_tree, zspecs
            )

        if tcfg.grad_accum > 1:
            n = tcfg.grad_accum

            def micro(carry, mb):
                g_acc, l_acc = carry
                total, aux, g_params, g_tokens = single_micro(
                    params, tokens, mb
                )
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / n,
                    g_acc, to_zero2(g_params),
                )
                return (g_acc, l_acc + total / n), (aux, g_tokens)

            mb_batch = jax.tree.map(
                lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch
            )
            g0 = to_zero2(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ))
            (g_params, total), (auxs, g_tokens) = jax.lax.scan(
                micro, (g0, jnp.float32(0.0)), mb_batch
            )
            # Stats/aux leaves are *per-microbatch means*: average over
            # the scan axis. Summing the bwd token cotangents inflated
            # bwd_frac_bf16 / bwd_rel_err by grad_accum x, and taking
            # aux[-1] silently reported only the last microbatch's fwd
            # stats and loss -- reported metrics must be invariant to
            # the grad_accum split (tests/test_stats_contract.py).
            aux = jax.tree.map(lambda x: jnp.mean(x, 0), auxs)
            g_tokens = jax.tree.map(lambda x: jnp.mean(x, 0), g_tokens)
        else:
            total, aux, g_params, g_tokens = single_micro(
                params, tokens, batch
            )
            g_params = to_zero2(g_params)

        if grad_fault is not None:
            g_params = grad_fault(g_params, batch)

        grad_stats = None
        new_ef = opt_state.ef
        if tcfg.compress_grads != "none":
            with jax.named_scope("optim"), jax.named_scope("compress"):
                g_params, new_ef, grad_stats = _compress(
                    g_params, mode=tcfg.compress_grads,
                    ef_state=opt_state.ef, policy=grad_policy,
                )

        new_params, new_opt, opt_metrics = adamw_update(
            tcfg.optimizer, g_params, opt_state, moments=tcfg.moments,
            guard=tcfg.guard,
        )
        with jax.named_scope("optim"):
            # Params keep their dtypes (f32 norm scales stay f32): a step
            # whose outputs differ from its inputs retraces and compiles
            # again on its second call, and cannot update them in place.
            new_params = jax.tree.map(
                lambda n, p: n.astype(p.dtype), new_params, params
            )
            if "guard_skip" in opt_metrics and new_ef is not None:
                # Skip-step EF preservation: compress_grads already folded
                # this step's residual into `corrected` and re-split it; if
                # the update is dropped, keeping the new residual would
                # make the *next* step absorb this step's quantization
                # error twice. Select the old residuals back (bit-exact).
                ok = opt_metrics["guard_skip"] < 0.5
                new_ef = tree_select(ok, new_ef, opt_state.ef)
        new_opt = new_opt._replace(ef=new_ef)
        # Optimizer-event rows (stats v4): gradient-compression events
        # plus the packed-moment encode events adamw_update reports.
        opt_rows = {
            "grad": grad_stats,
            "m": opt_metrics.pop("moment_stats_m", None),
            "v": opt_metrics.pop("moment_stats_v", None),
        }
        opt_rows = {k: s for k, s in opt_rows.items() if s is not None}
        metrics = {
            "loss": aux["loss"],
            "total_loss": total,
            "aux_loss": aux["aux_loss"],
            **opt_metrics,
            **summarize_mor_stats(
                aux.get("mor_fwd"), g_tokens, opt_rows or None
            ),
        }
        if new_ef is not None:
            with jax.named_scope("optim"):
                metrics["ef_norm"] = global_norm(new_ef)
        return new_params, new_opt, metrics

    return train_step
