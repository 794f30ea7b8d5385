"""Sequence-state ownership: the protocol every serving state pool obeys.

The engine used to talk to "a KV pool".  That framing breaks the moment a
model's per-sequence state is not a growing token-indexed cache: an SSM
layer (``models/mamba2.py``) carries a *fixed-size* recurrent state per
sequence — one [H, P, N] SSD state plus [W-1, ...] causal-conv tails —
and a hybrid (zamba2-style) stack carries both kinds at once.  What the
engine actually needs is a **state-ownership API**:

* ``SequenceStateStore`` — the protocol (admit planning, allocation,
  write, free/preempt, export).  ``ServeEngine``/``frontend``/``stepcore``
  address per-sequence state only through this surface.
* ``kvstore.KVOwner`` — the token-indexed implementation (slab rows or
  paged blocks + allocator + prefix index + handoff).
* ``SlotStateStore`` (here) — the slotted, preemptible state pool for
  SSM and hybrid models: ``model.init_cache(max_slots, max_seq_len)``
  reinterpreted as one slab whose rows hold *whatever state the model
  declares* — fixed-size conv + SSD recurrent state for SSM leaves,
  window-clamped ring-buffer K/V for hybrid attention leaves — composed
  in one pytree, written by the same traced-slot ``write_slot`` scatter.

Recurrent state makes two things first-class that the KV slab never
needed:

* **Prefill-continuation carry** — chunked prefill folds every consumed
  token into the batch-1 scratch *state* (there is no ``cache_len`` mask
  to hide stale positions behind), so the scratch must be reset to the
  pristine zero state each time a *new* request starts prefilling.
  ``begin_prefill()`` is that hook; for ``KVOwner`` it is a no-op (stale
  scratch positions are dead by masking).  Pad tokens inside the final
  chunk are masked out of the state update itself (``dt = 0`` at pad
  positions is an exact SSD identity; the conv tails are sliced at the
  last valid input) — see ``mamba_block(valid_len=...)``.
* **Token-exact preemption resume** — a preempted request's slot state is
  simply dropped; resume re-prefills prompt + committed output through
  the same chunked path and rewrites the slot, which reproduces the
  recurrent state exactly (state is a pure fold over the token stream).

Admission is slot-gated (the state is worst-case-sized per slot, so a
free slot is the only resource); ``share_plan`` degenerates to "start at
0, no shared blocks".  Cross-engine handoff of recurrent state is not
wired (split prefill/decode roles stay paged-transformer-only).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Protocol, Tuple

import jax
import numpy as np

from repro.serve.slots import (discover_batch_axes, discover_seq_axes,
                               min_kv_capacity, place_cache, write_slot)

AdmitPlan = Tuple[int, List[int], int, bool]


class SequenceStateStore(Protocol):
    """What ``ServeEngine`` asks of the component that owns per-sequence
    model state.  Implementations: ``kvstore.KVOwner`` (token-indexed K/V,
    slab or paged) and ``SlotStateStore`` (slotted SSM/hybrid state).

    Mutable attributes the engine's step loop reassigns:

    * ``pool`` — the full-batch state pytree every decode step threads;
    * ``scratch`` — the batch-1 prefill state.

    Static attributes fixed at construction: ``paged``, ``sharing``,
    ``s_pad`` (scratch KV length), ``kv_capacity`` (longest admissible
    padded prompt), ``blocks_per_slot``/``block_table``/``alloc`` (paged
    bookkeeping; 0/None for slotted stores), ``write_fn`` (the jitted
    scratch→pool commit), ``gather_fn``/``copy_fn`` (prefix sharing only).
    """
    paged: bool
    sharing: bool
    pool: Any
    scratch: Any
    s_pad: int
    kv_capacity: int
    blocks_per_slot: int
    block_table: Optional[np.ndarray]
    alloc: Any
    write_fn: Any
    gather_fn: Any
    copy_fn: Any

    def begin_prefill(self) -> None:
        """A new request is about to start prefilling into the scratch.
        Stores with recurrent scratch state reset it to the pristine zero
        state here; token-indexed stores need nothing (stale positions
        are dead by ``cache_len`` masking)."""
        ...

    def share_plan(self, tokens, resumed: bool) -> AdmitPlan:
        """Admission plan ``(start, shared_blocks, n_fresh, cow_last)``
        for a (re)prefill over ``tokens``."""
        ...

    def can_admit(self, plan: AdmitPlan) -> bool:
        """Whether the store can allocate ``plan`` right now."""
        ...

    def release(self, rid: int, slot: int) -> None:
        """Free every store-side resource request ``rid`` in ``slot``
        holds (finish and preempt both land here).  Slot recycling itself
        belongs to the engine's front."""
        ...

    def bt_row(self, rid: int) -> np.ndarray:
        """The request's block-table row (paged stores only)."""
        ...

    def probe_prefix(self, tokens) -> int:
        """Longest cached-prefix match in tokens (0 without sharing)."""
        ...

    def export_kv(self, pad_len: int) -> List[np.ndarray]:
        """Slice the scratch state for a prefill→decode handoff."""
        ...

    def import_kv(self, kv_leaves: List[np.ndarray], pad_len: int,
                  bt_row: np.ndarray) -> None:
        """Scatter a handoff record's state into this pool."""
        ...

    def stats(self) -> Dict[str, Any]:
        """The ``state_pool`` report section: pool kind, per-slot bytes,
        and store-specific counters (see serve/README.md)."""
        ...

    def jit_counts(self) -> Dict[str, int]:
        """Jit cache sizes of every store-owned entry (compile audit)."""
        ...


def _tree_nbytes(tree) -> int:
    return int(sum(np.dtype(leaf.dtype).itemsize * int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(tree)))


class SlotStateStore:
    """Slotted, preemptible state pool for SSM/hybrid models.

    ``pool`` is ``model.init_cache(max_slots, max_seq_len)`` — for a pure
    SSM stack that is per-slot *fixed-size* recurrent state (no KV-length
    axis at all); for a hybrid stack it composes the SSM leaves with the
    attention layers' (possibly window-clamped ring-buffer) K/V slabs in
    one pytree, so one engine serves both state kinds through one store.
    Prefill runs on the batch-1 ``scratch`` (reset to the pristine zero
    state at each ``begin_prefill`` — recurrent state carries across
    chunk calls, which is exactly what prefill continuation needs and
    exactly what a *new* request must not inherit) and the finished state
    is committed with the same traced-slot ``write_slot`` scatter the KV
    slab uses, so slot recycling never recompiles.

    Preemption is trivial by construction: dropping a slot loses nothing
    that ``prompt + committed output`` cannot rebuild, and resume
    re-prefills exactly that stream, making the recomputed state
    token-exact (the SSD update is a pure fold over tokens; pad positions
    are masked out of the fold itself — ``mamba_block(valid_len=...)``).
    """

    def __init__(self, model, ecfg, *, mesh=None):
        self.ecfg = ecfg
        self.paged = False
        self.sharing = False
        # protocol surface the paged implementation populates
        self.alloc = None
        self.block_table = None
        self.gather_fn = None
        self.copy_fn = None
        self.blocks_per_slot = 0
        self.ring = False
        self.ring_full_chain = False
        self.ring_mod = 0
        B = ecfg.max_slots
        self.s_pad = ecfg.max_seq_len
        self.seq_axes = discover_seq_axes(model.init_cache, ecfg.max_seq_len)
        self.batch_axes = discover_batch_axes(model.init_cache,
                                              ecfg.max_seq_len)
        # pure SSM state has no KV-length axis anywhere: prompts are
        # bounded by max_seq_len alone.  Hybrid attention leaves (clamped
        # to a sliding window or not) reinstate the usual minimum.
        self.kv_capacity = min_kv_capacity(
            model.init_cache, ecfg.max_seq_len, self.seq_axes,
            default=ecfg.max_seq_len)
        self.pool = place_cache(
            lambda: model.init_cache(B, ecfg.max_seq_len), mesh)
        self.scratch = place_cache(
            lambda: model.init_cache(1, ecfg.max_seq_len), mesh)
        # pristine zero state for begin_prefill resets: jax arrays are
        # immutable, so holding the initial scratch pytree (never fed back
        # through any jitted update) is a zero-copy template
        self._scratch0 = self.scratch

        def pool_write(pool, scratch, slot):
            return write_slot(pool, scratch, slot, self.batch_axes)
        self.write_fn = jax.jit(pool_write)
        self.scratch_resets = 0

    # ------------------------------------------------------------------
    def begin_prefill(self) -> None:
        self.scratch = self._scratch0
        self.scratch_resets += 1

    def share_plan(self, tokens, resumed: bool) -> AdmitPlan:
        return 0, [], 0, False

    def can_admit(self, plan: AdmitPlan) -> bool:
        return True               # slot-gated: the front checks free slots

    def release(self, rid: int, slot: int) -> None:
        pass                      # slot state is dropped, nothing to free

    def bt_row(self, rid: int) -> np.ndarray:
        raise RuntimeError("SlotStateStore has no block table")

    def probe_prefix(self, tokens) -> int:
        return 0

    def export_kv(self, pad_len: int) -> List[np.ndarray]:
        raise NotImplementedError(
            "recurrent-state handoff is not wired; split prefill/decode "
            "roles require the paged transformer KV store")

    def import_kv(self, kv_leaves, pad_len, bt_row) -> None:
        raise NotImplementedError(
            "recurrent-state handoff is not wired; split prefill/decode "
            "roles require the paged transformer KV store")

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        total = _tree_nbytes(self.pool)
        return {
            "kind": "slot",
            "slots": self.ecfg.max_slots,
            "state_bytes_per_slot": total // max(self.ecfg.max_slots, 1),
            "pool_bytes": total,
            "scratch_resets": self.scratch_resets,
        }

    def jit_counts(self) -> Dict[str, int]:
        return {"write_slot": self.write_fn._cache_size()}


def make_state_store(model, ecfg, *, s_pad: int, ctx: Callable[[], Any],
                     mesh=None):
    """Pick the state-store implementation for ``model``.

    SSM and hybrid families carry recurrent per-sequence state, which has
    no KV-length axis to address through a block table — they get the
    slotted pool (and reject ``paged=True`` loudly).  Everything else
    keeps ``KVOwner`` in whichever of its two modes ``ecfg`` selects.
    """
    from repro.serve.kvstore import KVOwner
    cfg = model.cfg
    if cfg.family in ("ssm", "hybrid"):
        if ecfg.paged:
            raise ValueError(
                f"{cfg.name} ({cfg.family}) carries fixed-size recurrent "
                f"state with no KV-length axis to page; serve it from the "
                f"slotted state pool (EngineConfig.paged=False)")
        return SlotStateStore(model, ecfg, mesh=mesh)
    return KVOwner(model, ecfg, s_pad=s_pad, ctx=ctx, mesh=mesh)
