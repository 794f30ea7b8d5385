"""Slotted KV pool: ``model.init_cache`` reinterpreted as a slab of
per-request slots.

The pool is one static-shape cache pytree of batch ``n_slots``; each row is
a slot that a request occupies from admission until EOS/max-len, after which
it is recycled for a queued request. Prefill runs against a batch-1 scratch
cache (same per-layer shapes) and the finished prefix is scattered into the
slot with ``write_slot`` — a traced-index ``dynamic_update_slice``, so slot
recycling never triggers recompilation.

Cache layouts differ per leaf (scan-stacked blocks put batch at axis 1,
unscanned lead layers at axis 0), so the batch axis AND the KV-length axis
of every leaf are discovered structurally: ``init_cache`` is
shape-evaluated at two batch sizes (resp. two ``s_max`` values) and the
differing axis is the one sought — neither is assumed adjacent to the
other.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


def place_cache(build: Callable[[], Any], mesh) -> Any:
    """Build a cache pytree straight onto ``mesh`` so a pool never lands
    whole on the first device.  K/V leaves (``[..., Hkv, hd]``) are split
    over kv heads on the ``model`` axis where the head count divides it,
    the layout the head-sharded attention projections write; every other
    leaf is replicated explicitly.  (The dry run's ``cache_shardings``
    may split the KV-length axis instead, which a block-table pool
    cannot use: its blocks are addressed through the table.)  A missing
    mesh keeps JAX's default placement.

    Specs are spelled the way the jitted steps hand the pool back (no
    trailing ``None``, no axis of size 1): jit keys its cache on the
    spelling, so ``P(.., "model", None)`` in and ``P(.., "model")`` out
    would compile every step twice."""
    if mesh is None:
        return build()
    from jax.sharding import NamedSharding, PartitionSpec
    ep = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    def sharding(path, leaf):
        if ep > 1 and jax.tree_util.keystr(path).endswith((".k", ".v")) \
                and leaf.shape[-2] % ep == 0:
            return NamedSharding(
                mesh, PartitionSpec(*[None] * (leaf.ndim - 2), "model"))
        return NamedSharding(mesh, PartitionSpec())
    shardings = jax.tree_util.tree_map_with_path(sharding,
                                                 jax.eval_shape(build))
    return jax.jit(build, out_shardings=shardings)()


def _differing_axes(la, lb) -> list:
    """Axis indices where two shape-evaluated leaves disagree."""
    return [i for i, (x, y) in enumerate(zip(la.shape, lb.shape)) if x != y]


def discover_batch_axes(init_cache: Callable[[int, int], Any],
                        s_max: int) -> Any:
    """Pytree of per-leaf batch-axis indices for ``init_cache`` outputs."""
    a = jax.eval_shape(lambda: init_cache(2, s_max))
    b = jax.eval_shape(lambda: init_cache(3, s_max))

    def axis(la, lb):
        diffs = _differing_axes(la, lb)
        if len(diffs) != 1:
            raise ValueError(
                f"cannot identify batch axis for cache leaf {la.shape} "
                f"vs {lb.shape}")
        return diffs[0]

    return jax.tree.map(axis, a, b)


def discover_seq_axes(init_cache: Callable[[int, int], Any],
                      s_max: int) -> Any:
    """Pytree of per-leaf KV-length-axis indices for ``init_cache`` outputs,
    found structurally like the batch axis (never assumed adjacent to it):
    shape-evaluate at two ``s_max`` values and take the differing axis.

    Sliding-window layers clamp their cache to ``min(s_max, window)``, so a
    leaf that is s_max-invariant at (s_max, s_max + 1) is probed again at
    (1, 2), below any window. A leaf whose shape depends on ``s_max`` at
    neither probe (e.g. an SSM state) has no KV-length axis and is marked
    ``-1`` (a real -1 sentinel, not ``None``, which jax pytrees treat as an
    empty subtree).
    """
    probes = [(s_max, s_max + 1), (1, 2)]
    trees = [jax.eval_shape(lambda s=s: init_cache(1, s))
             for pair in probes for s in pair]

    def axis(la_hi, lb_hi, la_lo, lb_lo):
        for la, lb in ((la_hi, lb_hi), (la_lo, lb_lo)):
            diffs = _differing_axes(la, lb)
            if len(diffs) == 1:
                return diffs[0]
            if len(diffs) > 1:
                raise ValueError(
                    f"cannot identify KV-length axis for cache leaf "
                    f"{la.shape} vs {lb.shape}")
        return -1

    return jax.tree.map(axis, *trees)


def min_kv_capacity(init_cache: Callable[[int, int], Any], s_max: int,
                    seq_axes: Any, default: int = 0) -> int:
    """Smallest per-layer KV length in the pool (sliding-window layers clamp
    their cache to the window, so prefill writes must fit the minimum).
    Leaves without a KV-length axis (marked ``-1``) impose no capacity; a
    cache with *no* seq-axed leaf at all (pure SSM state — fixed-size per
    slot) returns ``default`` when given, else raises."""
    shapes = jax.eval_shape(lambda: init_cache(1, s_max))
    caps = []
    jax.tree.map(
        lambda leaf, ax: caps.append(leaf.shape[ax]) if ax >= 0 else None,
        shapes, seq_axes)
    if not caps:
        if default:
            return default
        raise ValueError("no cache leaf depends on s_max; cannot size the "
                         "KV pool")
    return min(caps)


def write_slot(pool: Any, scratch: Any, slot: jnp.ndarray,
               batch_axes: Any) -> Any:
    """Scatter the batch-1 ``scratch`` cache into row ``slot`` of ``pool``.

    ``slot`` is a traced int32 scalar — one compilation serves every slot.
    """
    def upd(p, sc, ax):
        pm = jnp.moveaxis(p, ax, 0)
        sm = jnp.moveaxis(sc, ax, 0).astype(pm.dtype)
        pm = jax.lax.dynamic_update_slice(
            pm, sm, (slot,) + (0,) * (pm.ndim - 1))
        return jnp.moveaxis(pm, 0, ax)

    return jax.tree.map(upd, pool, scratch, batch_axes)
