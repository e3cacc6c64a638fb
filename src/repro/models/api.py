"""Public model API: loss, train/prefill/decode step builders.

These are the functions the trainer, server, benchmarks and the multi-pod
dry-run all lower. MoR statistics flow out of the train step as
``aux['mor']`` = {'fwd': stats pytree, 'bwd': token-cotangent pytree}.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import MoRDotPolicy

from . import transformer as T
from .common import constrain

__all__ = [
    "cross_entropy", "make_loss_fn", "make_prefill_fn", "make_decode_fn",
    "init_params", "make_tokens", "cache_specs", "init_cache",
]

init_params = T.init_params
make_tokens = T.make_tokens
cache_specs = T.cache_specs
init_cache = T.init_cache


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy; logits (B,S,V) f32, labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _collect_aux_losses(stats) -> jnp.ndarray:
    """Sum MoE load-balance aux losses found anywhere in the stats tree."""
    total = jnp.float32(0.0)
    flat, _ = jax.tree_util.tree_flatten_with_path(stats)
    for path, leaf in flat:
        if any("aux_loss" in str(k) for k in path):
            total = total + jnp.sum(leaf)
    return total


def make_loss_fn(cfg: ArchConfig, policy: MoRDotPolicy, *,
                 remat: bool = True, aux_coef: float = 0.01):
    """loss_fn(params, tokens, batch) -> (loss, aux).

    ``tokens`` are the zero bwd-stat tokens from make_tokens; take grads
    w.r.t. them to recover backward quantization stats.
    """

    def loss_fn(params, tokens, batch):
        logits, _, stats = T.forward(
            cfg, policy, params, tokens, batch, mode="train", remat=remat
        )
        with jax.named_scope("loss"):
            labels = batch["labels"]
            if cfg.family == "vlm":
                # Labels cover text positions only; drop image-prefix logits.
                logits = logits[:, cfg.img_tokens :]
            loss = cross_entropy(logits, labels)
            aux_loss = _collect_aux_losses(stats)
            total = loss + aux_coef * aux_loss
        return total, {"loss": loss, "aux_loss": aux_loss, "mor_fwd": stats}

    return loss_fn


def make_prefill_fn(cfg: ArchConfig, policy: MoRDotPolicy):
    def prefill_fn(params, tokens, batch):
        logits, cache, stats = T.forward(
            cfg, policy, params, tokens, batch, mode="prefill", remat=False
        )
        return logits[:, -1:], cache, stats

    return prefill_fn


def make_decode_fn(cfg: ArchConfig, policy: MoRDotPolicy):
    """decode_fn(params, tokens, cache, token, cur_index) -> (logits,
    new_cache, stats).

    ``token`` is (B, S) int32 -- S == 1 for a plain decode step, S > 1
    for a prefill chunk written into the cache. ``cur_index`` is the
    position of the last incoming token: a scalar () shared by the
    batch, or a (B,) vector so each row of a mixed-length batch reads
    and writes at its own true position (docs/serving.md)."""

    def decode_fn(params, tokens, cache, token, cur_index):
        logits, new_cache, stats = T.forward(
            cfg, policy, params, tokens, {"token": token},
            mode="decode", cache=cache, cur_index=cur_index, remat=False,
        )
        return logits, new_cache, stats

    return decode_fn
