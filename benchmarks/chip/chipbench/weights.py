"""Weights made from the seed, by a rule the benchmark owns.

Every value is a pure function of ``(seed, leaf name, layer)``, so the
program's stacked parameter tree and the plain reference's per-layer
weights hold the same numbers without either taking them from the other.
The rule follows the published initialisations in spirit (dense matrices
N(0, 1/fan_in), embeddings N(0, 0.02^2)) and gives every norm and mixing
vector a random value, so that a fault in any of them shows.

Norm scales are stored as offsets from 1, as the program stores them.

A leaf is drawn by the rule its model type names for its name, or else,
where it has two axes or more, N(0, 1/fan_in) with fan_in its second-to-last
axis (a stacked expert's input width). The embedding's and the final norm's
rules are here; each model type names the rest in ``archs/<model_type>.py``
(``WEIGHTS``), which no other model type reads.
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench import harness


def norm(key, shape) -> jax.Array:
    """A norm scale's offset from 1, or another small vector: N(0, 0.1^2)."""
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def unit(key, shape) -> jax.Array:
    """A mixing coefficient: uniform in [0, 1)."""
    return jax.random.uniform(key, shape, jnp.float32)


COMMON = {"tok": lambda key, shape: 0.02 * jax.random.normal(
    key, shape, jnp.float32), "final_norm": norm}


def rules(model_type: str) -> Dict[str, Callable]:
    """Leaf name -> rule: ``COMMON`` and the model type's own."""
    return {**COMMON, **getattr(harness.arch(model_type), "WEIGHTS", {})}


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _draw(key, model_type: str, name: str, shape: Tuple[int, ...]
          ) -> jax.Array:
    rule = rules(model_type).get(name)
    if rule is not None:
        return rule(key, shape)
    if len(shape) >= 2:
        return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
            jnp.float32(shape[-2]))
    raise KeyError(f"no weight rule for leaf {name!r} of shape {shape}: "
                   f"name one in archs/{model_type}.py")


def leaf(seed_key: jax.Array, model_type: str, path: str, layer: int,
         shape: Tuple[int, ...]) -> jax.Array:
    """The float32 value of leaf ``path`` (e.g. ``g0/s0/wq``) at ``layer``
    of a model of ``model_type``."""
    key = jax.random.fold_in(seed_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    return _draw(key, model_type, path.rsplit("/", 1)[-1], shape)


def _path_str(path) -> str:
    return "/".join(p.key if hasattr(p, "key") else str(p) for p in path)


def program_tree(specs: Any, seed: int, model_type: str, dtype,
                 shardings: Any = None) -> Any:
    """The program's parameter tree for ``specs`` (a ShapeDtypeStruct tree
    whose ``g*`` leaves stack layers on axis 0), made in one jitted call;
    each leaf laid out as ``shardings`` has it, where given."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    paths = [_path_str(p) for p, _ in flat]
    shapes = [tuple(s.shape) for _, s in flat]

    def make(key):
        out = []
        for path, shape in zip(paths, shapes):
            if path.startswith("g"):
                layers = jnp.arange(shape[0])
                v = jax.vmap(lambda l, p=path, s=shape[1:]: leaf(
                    key, model_type, p, l, s))(layers)
            else:
                v = leaf(key, model_type, path, 0, shape)
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    laid = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(make, **laid)(base_key(seed))


def leaf_fn(seed: int, model_type: str, dtype=None, sharding=None
            ) -> Callable[[str, int, Tuple[int, ...]], jax.Array]:
    """``f(path, layer, shape)`` for the reference: one jitted draw per leaf,
    laid out as ``sharding`` has it, where given.

    With ``dtype`` the value is rounded to that type and widened back to
    float32: the weights a model serves in bfloat16 are those rounded values.
    """
    key = base_key(seed)
    cache: Dict[Tuple[str, Tuple[int, ...]], Callable] = {}

    def get(path: str, layer: int, shape: Tuple[int, ...]) -> jax.Array:
        fn = cache.get((path, shape))
        if fn is None:
            def draw(k, l):
                v = leaf(k, model_type, path, l, shape)
                return v.astype(dtype).astype(jnp.float32) if dtype else v
            laid = {} if sharding is None else {"out_shardings": sharding}
            fn = cache[(path, shape)] = jax.jit(draw, **laid)
        return fn(key, layer)

    return get
