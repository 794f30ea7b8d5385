"""Seeded weights: the layout-free part.

The benchmark, not the program, decides what the weights are.  Every
weight is a function of the run's seed, a semantic name and its indices
(layer, expert), so the served model and the plain reference read the
same numbers without either taking anything from the other.  Which
leaves a model has, their shapes and scales, and where each sits in the
program's parameter tree belong to the configuration's layout
(``layouts/<name>.py``), which builds on the draws here:

* ``normal`` draws one weight in float32, scales it and rounds it to the
  served type;
* ``fill_tree`` maps a layout's fill over the program's parameter tree
  (its leaf paths and shapes are the interface read here), refusing a
  leaf the layout does not know;
* ``expert_of_row`` is where the program's expert-parallel layout keeps
  each expert.
"""
from __future__ import annotations

import zlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1
EMBED_STD = 0.02
ROUTER_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF),
                              int(words[1]) & 0x7FFFFFFF)


def _key(key, name: str, *idx):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    for i in idx:
        k = jax.random.fold_in(k, i)
    return k


def normal(key, name: str, idx, shape, std: float, dtype=jnp.bfloat16):
    """One weight: N(0, std^2) drawn in float32, rounded to ``dtype``."""
    x = jax.random.normal(_key(key, name, *idx), shape, jnp.float32) * std
    return x.astype(dtype)


def expert_of_row(rows: int, ranks: int) -> np.ndarray:
    """Expert held in each row of the program's stacked expert leaves.

    The program stores expert rows rank-major, and places expert ``e`` on
    rank ``e % ranks`` in local slot ``e // ranks``: row ``g * per_rank +
    j`` holds expert ``j * ranks + g``."""
    per_rank = rows // ranks
    g, j = np.divmod(np.arange(rows), per_rank)
    return (j * ranks + g).astype(np.int32)


def tree_path(path) -> str:
    """A leaf's path in the program's tree, as ``stack/blocks/sub0/...``."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def fill_tree(shapes, fill: Callable[[str, jax.ShapeDtypeStruct],
                                     Optional[jax.Array]]):
    """``shapes`` (from ``jax.eval_shape`` of the program's ``init``) with
    each leaf replaced by ``fill(path, shape)``.  Leaves for which ``fill``
    returns None raise, all named by their paths, before a weight of
    another shape or type than the program's does, so a change of the
    program's layout shows.  Run it under ``jax.jit`` with the program's
    output shardings."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tree_path(path) for path, _ in leaves]
    out = [fill(p, sds) for p, (_, sds) in zip(paths, leaves)]
    unknown = [f"program leaf {p!r} {sds.shape}"
               for p, (_, sds), x in zip(paths, leaves, out) if x is None]
    if unknown:
        raise KeyError("the layout has no weight for " + "; ".join(unknown))
    for p, (_, sds), x in zip(paths, leaves, out):
        if x.shape != sds.shape or x.dtype != sds.dtype:
            raise ValueError(f"benchmark weight for {p!r} is {x.shape} "
                             f"{x.dtype}; the program wants {sds.shape} "
                             f"{sds.dtype}")
    return jax.tree_util.tree_unflatten(treedef, out)
