"""Weights made from the seed, by a rule the benchmark owns.

Every value is a pure function of ``(seed, leaf name, layer)``, so the
program's stacked parameter tree and the plain reference's per-layer
weights hold the same numbers without either taking them from the other.
The rule follows the published initialisations in spirit (dense matrices
N(0, 1/fan_in), embeddings N(0, 0.02^2)) and gives every norm and mixing
vector a random value, so that a fault in any of them shows.

Norm scales are stored as offsets from 1, as the program stores them.
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm", "ln_x")
MIX = ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w", "mu_k2", "mu_r2")


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _draw(key, name: str, shape: Tuple[int, ...]) -> jax.Array:
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        return 0.1 * normal()
    if name in MIX:
        return jax.random.uniform(key, shape, jnp.float32)
    if name == "tok":
        return 0.02 * normal()
    if name == "w0":
        return jax.random.uniform(key, shape, jnp.float32, -6.0, -1.0)
    if name == "u":
        return 0.5 * normal()
    if name.startswith("dd_b_") or name == "wd_b":
        return 0.1 * normal()
    if len(shape) == 2:
        return normal() / jnp.sqrt(jnp.float32(shape[0]))
    raise KeyError(f"no weight rule for leaf {name!r} of shape {shape}")


def leaf(seed_key: jax.Array, path: str, layer: int,
         shape: Tuple[int, ...]) -> jax.Array:
    """The float32 value of leaf ``path`` (e.g. ``g0/s0/wq``) at ``layer``."""
    key = jax.random.fold_in(seed_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    return _draw(key, path.rsplit("/", 1)[-1], shape)


def _path_str(path) -> str:
    return "/".join(p.key if hasattr(p, "key") else str(p) for p in path)


def program_tree(specs: Any, seed: int, dtype) -> Any:
    """The program's parameter tree for ``specs`` (a ShapeDtypeStruct tree
    whose ``g*`` leaves stack layers on axis 0), made in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    paths = [_path_str(p) for p, _ in flat]
    shapes = [tuple(s.shape) for _, s in flat]

    def make(key):
        out = []
        for path, shape in zip(paths, shapes):
            if path.startswith("g"):
                layers = jnp.arange(shape[0])
                v = jax.vmap(lambda l, p=path, s=shape[1:]: leaf(key, p, l, s)
                             )(layers)
            else:
                v = leaf(key, path, 0, shape)
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(base_key(seed))


def leaf_fn(seed: int, dtype=None) -> Callable[[str, int, Tuple[int, ...]],
                                              jax.Array]:
    """``f(path, layer, shape)`` for the reference: one jitted draw per leaf.

    With ``dtype`` the value is rounded to that type and widened back to
    float32: the weights a model serves in bfloat16 are those rounded values.
    """
    key = base_key(seed)
    cache: Dict[Tuple[str, Tuple[int, ...]], Callable] = {}

    def get(path: str, layer: int, shape: Tuple[int, ...]) -> jax.Array:
        fn = cache.get((path, shape))
        if fn is None:
            def draw(k, l):
                v = leaf(k, path, l, shape)
                return v.astype(dtype).astype(jnp.float32) if dtype else v
            fn = cache[(path, shape)] = jax.jit(draw)
        return fn(key, layer)

    return get
