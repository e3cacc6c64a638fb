"""Model weights from ``--seed``, made on the device by the benchmark.

Both the system under test and the plain reference take their weights
from here, so the reference never reads anything the program made. Every
matrix is drawn as ``normal * std`` in float32 and stored in bfloat16,
the type it is served and trained in; norm scales are float32 zeros
(the repository's RMS norm multiplies by ``1 + scale``). The family
file's ``leaf_table(cfg)`` lists the leaves: ``path -> (shape, dtype,
std)``, a leaf under ``blocks/`` stacked over the units of the stack on
its first axis. Each leaf, and each unit of a stacked leaf, has a key of
its own (from its path), so the reference can make one unit at a time.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict

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


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) % 2**31)


def _draw(key, shape, dtype, std):
    if std == 0.0:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_leaf(fam, key, cfg, path: str, layer=None):
    """One leaf (or one layer of a stacked leaf) of the seeded params of
    the family ``fam``."""
    shape, dtype, std = fam.leaf_table(cfg)[path]
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


def stack_depth(fam, cfg) -> int:
    """How many units the family's ``blocks/`` leaves stack on their
    first axis; every such leaf has to stack as many."""
    depths = {shape[0] for path, (shape, _, _) in fam.leaf_table(cfg).items()
              if path.startswith("blocks/")}
    if len(depths) != 1:
        raise ValueError(f"blocks/ leaves stack {sorted(depths)} units")
    return depths.pop()


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


def make_params(fam, cfg, seed: int, out_shardings=None):
    """All params in one jitted call on the device."""
    key = base_key(seed)

    @functools.partial(jax.jit, out_shardings=out_shardings)
    def gen(key):
        return nest({p: make_leaf(fam, key, cfg, p)
                     for p in fam.leaf_table(cfg)})

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
