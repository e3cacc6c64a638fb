"""Per-architecture smoke tests: reduced config, one forward + one train
step (loss + grads) on CPU, asserting output shapes and no NaNs; plus a
prefill->decode consistency check per family.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_archs, reduced
from repro.core import TENSOR_MOR, BF16_BASELINE
from repro.models import (
    cache_specs,
    init_cache,
    init_params,
    make_decode_fn,
    make_loss_fn,
    make_prefill_fn,
    make_tokens,
)
from repro.models.transformer import padded_vocab

ARCHS = [
    "moonlight-16b-a3b", "granite-moe-1b-a400m", "gemma-2b",
    "deepseek-coder-33b", "llama3-8b", "minitron-4b", "whisper-tiny",
    "xlstm-350m", "paligemma-3b", "hymba-1.5b",
]

B, S = 2, 32


def _batch(cfg, key, mode="train"):
    kt, kl = jax.random.split(key)
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab, jnp.int32),
    }
    if mode == "train":
        batch["labels"] = jax.random.randint(
            kl, (B, S), 0, cfg.vocab, jnp.int32
        )
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            kl, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16
        )
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            kl, (B, cfg.img_tokens, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    tokens = make_tokens(cfg)
    batch = _batch(cfg, key)

    loss_fn = make_loss_fn(cfg, TENSOR_MOR)
    (loss, aux), grads = jax.jit(
        jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
    )(params, tokens, batch)

    assert np.isfinite(float(loss)), f"{arch}: loss is not finite"
    # Sanity: loss near ln(vocab) at init.
    assert 0.5 * np.log(cfg.vocab) < float(loss) < 2.5 * np.log(cfg.vocab)
    g_params, g_tokens = grads
    for path, leaf in jax.tree_util.tree_flatten_with_path(g_params)[0]:
        assert np.all(np.isfinite(np.asarray(leaf, np.float32))), (
            f"{arch}: non-finite grad at {path}"
        )
    # Backward MoR stats came out through the token cotangents.
    tok_mags = jax.tree.map(
        lambda x: float(jnp.sum(jnp.abs(x))), g_tokens
    )
    total = sum(jax.tree.leaves(tok_mags))
    assert total > 0.0, f"{arch}: no backward MoR stats collected"


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_smoke(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    tokens = make_tokens(cfg)
    batch = _batch(cfg, key, mode="prefill")

    prefill = jax.jit(make_prefill_fn(cfg, TENSOR_MOR))
    logits, cache, _ = prefill(params, tokens, batch)
    Vp = padded_vocab(cfg)
    assert logits.shape == (B, 1, Vp)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    # One decode step continuing from prefill. Pad the prefill cache into
    # a longer decode cache where the layout is positional (kv caches).
    total = S + (cfg.img_tokens if cfg.family == "vlm" else 0)
    decode = jax.jit(make_decode_fn(cfg, TENSOR_MOR))
    full = init_cache(cfg, B, total + 8)

    def merge(dst, src):
        if dst.ndim >= 3 and src.ndim == dst.ndim and dst.shape[2] != src.shape[2]:
            # seq-dim padded kv cache: (L, B, S, ...)
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), 0, axis=2
            )
        return src.astype(dst.dtype)

    cache = jax.tree.map(merge, full, cache)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    logits2, cache2, _ = decode(
        params, tokens, cache, tok, jnp.asarray(total, jnp.int32)
    )
    assert logits2.shape == (B, 1, Vp)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


def test_bf16_baseline_runs():
    cfg = reduced(get_config("llama3-8b"))
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    tokens = make_tokens(cfg)
    batch = _batch(cfg, key)
    loss_fn = make_loss_fn(cfg, BF16_BASELINE)
    (loss, _), grads = jax.jit(
        jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
    )(params, tokens, batch)
    assert np.isfinite(float(loss))


def test_mor_close_to_bf16_loss():
    """Fake-quant MoR loss should be close to the BF16 loss at init."""
    cfg = reduced(get_config("llama3-8b"))
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    tokens = make_tokens(cfg)
    batch = _batch(cfg, key)
    l_bf, _ = jax.jit(make_loss_fn(cfg, BF16_BASELINE))(params, tokens, batch)
    l_mor, _ = jax.jit(make_loss_fn(cfg, TENSOR_MOR))(params, tokens, batch)
    assert abs(float(l_bf) - float(l_mor)) / float(l_bf) < 0.05
