"""Model weights from ``--seed``, made on the device by the benchmark.

Both the system under test and the plain reference take their weights
from here, so the reference never reads anything the program made. Every
matrix is drawn as ``normal * std`` in float32 and stored in bfloat16,
the type it is served and trained in; norm scales are float32 zeros
(the repository's RMS norm multiplies by ``1 + scale``). Each leaf, and
each layer of a layer-stacked leaf, has a key of its own, so the
reference can make one layer at a time.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


def base_key(seed: int):
    """A key from any non-negative seed, also one beyond 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def padded_vocab(cfg) -> int:
    return -(-cfg.vocab // 128) * 128


def leaf_table(cfg) -> Dict[str, Tuple[Tuple[int, ...], Any, float]]:
    """``path -> (shape, dtype, std)`` of a dense decoder's params; std 0
    marks a zero-initialised norm scale. Paths follow the tree of
    ``repro.models.init_params``."""
    d, L, f = cfg.d_model, cfg.n_layers, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    vp = padded_vocab(cfg)
    out_std = STD / max(1.0, (2 * L) ** 0.5)
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((vp, d), bf, STD),
        "final_norm/scale": ((d,), f32, 0.0),
        "lm_head": ((d, vp), bf, STD),
        "blocks/dense/wqkv": ((L, d, (hq + 2 * hkv) * hd), bf, STD),
        "blocks/dense/wo": ((L, hq * hd, d), bf, out_std),
        "blocks/dense/mlp/wi": ((L, d, f), bf, STD),
        "blocks/dense/mlp/wo": ((L, f, d), bf, out_std),
        "blocks/dense/ln1/scale": ((L, d), f32, 0.0),
        "blocks/dense/ln2/scale": ((L, d), f32, 0.0),
    }


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) % 2**31)


def _draw(key, shape, dtype, std):
    if std == 0.0:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_leaf(key, cfg, path: str, layer=None):
    """One leaf (or one layer of a stacked leaf) of the seeded params."""
    shape, dtype, std = leaf_table(cfg)[path]
    k = _leaf_key(key, path)
    if path.startswith("blocks/"):
        if layer is None:
            return jax.vmap(
                lambda l: _draw(jax.random.fold_in(k, l), shape[1:], dtype,
                                std)
            )(jnp.arange(shape[0]))
        return _draw(jax.random.fold_in(k, layer), shape[1:], dtype, std)
    out = _draw(k, shape, dtype, std)
    if path == "embed" and shape[0] > cfg.vocab:
        out = out.at[cfg.vocab:].set(0)
    return out


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def flatten(tree, prefix: str = "") -> Dict[str, Any]:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def make_params(cfg, seed: int, out_shardings=None):
    """All params in one jitted call on the device."""
    key = base_key(seed)

    @functools.partial(jax.jit, out_shardings=out_shardings)
    def gen(key):
        return nest({p: make_leaf(key, cfg, p) for p in leaf_table(cfg)})

    return gen(key)


def check_tree(cfg, params) -> None:
    """The seeded tree must be what the program's own init builds."""
    from repro.models import init_params

    want = jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), flatten(params))
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), flatten(want))
    if got != exp:
        raise RuntimeError(
            f"seeded params do not match the program's tree: {got} vs {exp}"
        )
