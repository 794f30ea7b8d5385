"""KV-ownership component of the serving engine.

``KVOwner`` owns everything about *where KV lives*: the physical pool
(slab rows or paged blocks), the block allocator + prefix index, the
batch-1 prefill scratch, and the jitted device plumbing that moves KV
between them (``write_chunk_blocks`` / ``write_slot`` /
``gather_prefix_blocks`` / ``copy_block``).  The engine keeps scheduling
state (slots, positions, the decode batch) and delegates every
pool/allocator touch here — which is what lets an engine run as a
``prefill``-only or ``decode``-only *role*: the prefill role exports a
finished request's KV as a :class:`HandoffRecord` and the decode role
imports it into its own pool, token-exactly, through the same
``write_chunk_blocks`` scatter ordinary prefill uses.

Handoff format: the record carries each scratch cache leaf's first
``pad_len`` KV positions (seq axis moved to the front, so the arrays are
``[pad_len, ...]`` regardless of the leaf's native layout) in
``jax.tree.leaves`` order, plus the token-level request state (prompt,
committed outputs, timestamps).  Plain numpy + ints — picklable, and
``to_npz_bytes``/``from_npz_bytes`` give an explicit wire form.  Import
rebuilds a batch-1 scratch from the record and scatters it chunk-by-chunk
through the importing engine's own block table, so the destination pool's
K/V is bit-identical to what a unified engine would have prefilled.
"""
from __future__ import annotations

import io
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import round_up
from repro.serve.paging import (NULL_BLOCK, BlockAllocator, blocks_for_tokens,
                                copy_block, gather_prefix_blocks,
                                write_chunk_blocks)
from repro.serve.slots import (discover_batch_axes, discover_seq_axes,
                               min_kv_capacity, place_cache, write_slot)


@dataclass
class HandoffRecord:
    """Serializable prefill→decode handoff: one finished prefill's block
    chain contents + committed-prefix state.

    ``kv`` holds each scratch leaf's positions ``[0, pad_len)`` with the
    KV-length axis moved to the front (``jax.tree.leaves`` order of the
    cache pytree); ``pad_len`` is the chunk-rounded committed length, so
    the importer can replay the exact ``write_chunk_blocks`` scatters the
    unified engine would have issued.  ``output`` carries every committed
    token (the prefill role hands off with exactly one — the first token
    its final chunk sampled); timestamps ride along so the decode side's
    completion record keeps the true TTFT.
    """
    rid: int
    prompt_tokens: np.ndarray
    output: List[int]
    pos: int                       # committed KV length (prefill_len)
    pad_len: int                   # chunk-rounded slice length of ``kv``
    prefill_chunk: int             # chunk size the exporter prefilled with
    max_new_tokens: int
    eos_id: Optional[int]
    kv: List[np.ndarray] = field(default_factory=list)
    cached_prefix_tokens: int = 0
    arrival_time: float = 0.0
    admitted_time: float = 0.0
    first_token_time: float = 0.0
    prefill_start_time: Optional[float] = None

    @property
    def nbytes(self) -> int:
        """Wire size of the KV payload (the dominant handoff cost)."""
        return int(sum(a.nbytes for a in self.kv)) \
            + self.prompt_tokens.nbytes + 4 * len(self.output)

    def to_npz_bytes(self) -> bytes:
        """Explicit wire form: one npz blob.  KV leaves travel as raw
        bytes plus sidecar dtype/shape arrays — npz's own dtype headers
        cannot describe ml_dtypes extension types like bfloat16."""
        buf = io.BytesIO()
        header = np.asarray([self.rid, self.pos, self.pad_len,
                             self.prefill_chunk, self.max_new_tokens,
                             -1 if self.eos_id is None else self.eos_id,
                             self.cached_prefix_tokens], np.int64)
        times = np.asarray([self.arrival_time, self.admitted_time,
                            self.first_token_time,
                            np.nan if self.prefill_start_time is None
                            else self.prefill_start_time], np.float64)
        payload = {f"kv_{i}": np.frombuffer(a.tobytes(), np.uint8)
                   for i, a in enumerate(self.kv)}
        np.savez(buf, header=header, times=times,
                 prompt=self.prompt_tokens,
                 output=np.asarray(self.output, np.int64),
                 kv_dtypes=np.asarray([str(a.dtype) for a in self.kv]),
                 kv_shapes=np.asarray([",".join(map(str, a.shape))
                                       for a in self.kv]),
                 **payload)
        return buf.getvalue()

    @classmethod
    def from_npz_bytes(cls, blob: bytes) -> "HandoffRecord":
        z = np.load(io.BytesIO(blob))
        h = z["header"]
        kv = []
        for i, (dt, shp) in enumerate(zip(z["kv_dtypes"], z["kv_shapes"])):
            shape = tuple(int(s) for s in str(shp).split(",") if s)
            kv.append(np.frombuffer(z[f"kv_{i}"].tobytes(),
                                    np.dtype(str(dt))).reshape(shape))
        return cls(rid=int(h[0]), prompt_tokens=z["prompt"].astype(np.int32),
                   output=[int(t) for t in z["output"]], pos=int(h[1]),
                   pad_len=int(h[2]), prefill_chunk=int(h[3]),
                   max_new_tokens=int(h[4]),
                   eos_id=None if int(h[5]) < 0 else int(h[5]),
                   kv=kv, cached_prefix_tokens=int(h[6]),
                   arrival_time=float(z["times"][0]),
                   admitted_time=float(z["times"][1]),
                   first_token_time=float(z["times"][2]),
                   prefill_start_time=(None if np.isnan(z["times"][3])
                                       else float(z["times"][3])))


class KVOwner:
    """Paged-or-slab KV pool + allocator + jitted KV movement: the
    token-indexed implementation of ``statestore.SequenceStateStore``.

    Construction mirrors what ``ServeEngine.__init__`` used to inline:
    structural axis discovery, pool/scratch init (under the engine's mesh
    context), and one jitted entry per movement primitive.  ``pool`` and
    ``scratch`` are plain mutable attributes the engine's step loop
    reassigns; the allocator and block table are owned here.

    Sliding-window models are served paged as **ring buffers**: the pool
    and scratch are built over the *unclamped* cache
    (``init_cache(..., clamp_window=False)`` — chunked prefill attends
    through the full-length scratch, where the window is enforced by the
    attention mask), and each window-clamped leaf gets a per-leaf ring
    modulus ``M = round_up(window, block_size)`` (``ring_mods``): logical
    position ``p`` lives at ring slot ``p % M`` of the slot's chain, both
    in the prefill scatter (``write_chunk_blocks``) and the decode
    write/gather (``paged_ring_decode_attention``).  When *every* KV leaf
    is windowed the chain itself shrinks to ``M / block_size`` blocks —
    fixed-size per slot, allocated whole at admission
    (``ring_full_chain``) — which is where the paged pool's memory win
    over the slab comes from for long-context windowed serving.
    """

    def __init__(self, model, ecfg, *, s_pad: int, ctx: Callable[[], Any],
                 mesh=None):
        self.ecfg = ecfg
        self.paged = ecfg.paged
        self.sharing = ecfg.prefix_sharing
        self._ctx = ctx
        B, C = ecfg.max_slots, ecfg.prefill_chunk
        self.seq_axes = discover_seq_axes(model.init_cache, ecfg.max_seq_len)
        self.alloc: Optional[BlockAllocator] = None
        self.block_table: Optional[np.ndarray] = None
        self.gather_fn = None
        self.copy_fn = None
        self.ring = False
        self.ring_full_chain = False
        self.ring_mod = 0
        if self.paged:
            bs = ecfg.kv_block_size
            if bs < 1:
                raise ValueError("kv_block_size must be >= 1")
            self.s_pad = s_pad
            self.blocks_per_slot = blocks_for_tokens(s_pad, bs)
            # ring discovery: a leaf is windowed iff clamping changes its
            # KV length at s_pad.  Windowed leaves wrap positions modulo
            # M; with every leaf windowed the whole chain shrinks to M.
            window = model.cfg.sliding_window or 0
            M = round_up(window, bs) if window else 0
            clamped = jax.eval_shape(lambda: model.init_cache(1, s_pad))
            full = jax.eval_shape(
                lambda: model.init_cache(1, s_pad, False))
            self.ring_mods = jax.tree.map(
                lambda c, f, ax: (M if ax >= 0
                                  and c.shape[ax] != f.shape[ax] else 0),
                clamped, full, self.seq_axes)
            n_seq = sum(1 for a in jax.tree.leaves(self.seq_axes) if a >= 0)
            n_ring = sum(1 for m in jax.tree.leaves(self.ring_mods) if m)
            self.ring = n_ring > 0
            self.ring_mod = M if self.ring else 0
            self.ring_full_chain = self.ring and n_ring == n_seq
            if self.ring_full_chain:
                # every leaf wraps: a chain of M/bs blocks serves any
                # logical length — fixed-size per slot, like an SSM slot
                self.blocks_per_slot = M // bs
            usable = ecfg.num_kv_blocks or B * self.blocks_per_slot
            if usable < self.blocks_per_slot:
                raise ValueError(
                    f"num_kv_blocks={usable} cannot hold even one "
                    f"worst-case request ({self.blocks_per_slot} blocks)")
            self.alloc = BlockAllocator(usable + 1, bs,   # +1: null block
                                        prefix_cache=self.sharing)
            self.block_table = np.full((B, self.blocks_per_slot),
                                       NULL_BLOCK, np.int32)
            self.kv_capacity = s_pad
            # the pool/scratch are built over the unclamped cache
            # (assert_pageable validates full KV axes at s_pad; the
            # window is enforced by ring_mods + the attention mask,
            # never by silent truncation)
            self.pool = place_cache(
                lambda: model.init_paged_cache(
                    self.alloc.num_blocks, bs, s_pad,
                    seq_axes=self.seq_axes, clamp_window=False),
                mesh)
            self.scratch = place_cache(
                lambda: model.init_cache(1, s_pad, False), mesh)
            ring_mods = self.ring_mods if self.ring else None

            # named entries: a trace's modules are jit_<name>
            def pool_write(pool, scratch, bt_row, start, valid_to):
                return write_chunk_blocks(
                    pool, scratch, bt_row, start, chunk=C, block_size=bs,
                    seq_axes=self.seq_axes, ring_mods=ring_mods,
                    valid_to=valid_to)
            self.write_fn = jax.jit(pool_write)
            if self.sharing:
                def prefix_gather(pool, scratch, bt_row, n):
                    return gather_prefix_blocks(
                        pool, scratch, bt_row, n, s_pad=s_pad,
                        block_size=bs, seq_axes=self.seq_axes)

                def block_copy(pool, src, dst):
                    return copy_block(pool, src, dst, block_size=bs,
                                      seq_axes=self.seq_axes)
                self.gather_fn = jax.jit(prefix_gather)
                self.copy_fn = jax.jit(block_copy)
        else:
            self.s_pad = ecfg.max_seq_len
            self.blocks_per_slot = 0
            self.batch_axes = discover_batch_axes(model.init_cache,
                                                  ecfg.max_seq_len)
            self.kv_capacity = min_kv_capacity(
                model.init_cache, ecfg.max_seq_len, self.seq_axes)
            self.pool = place_cache(
                lambda: model.init_cache(B, ecfg.max_seq_len), mesh)
            self.scratch = place_cache(
                lambda: model.init_cache(1, ecfg.max_seq_len), mesh)

            def pool_write(pool, scratch, slot):
                return write_slot(pool, scratch, slot, self.batch_axes)
            self.write_fn = jax.jit(pool_write)

    # ------------------------------------------------------------------
    # SequenceStateStore protocol (serve/statestore.py)
    # ------------------------------------------------------------------
    def begin_prefill(self) -> None:
        """Token-indexed scratch needs no reset: stale positions sit past
        ``cache_len`` and are dead by masking."""

    def release(self, rid: int, slot: int) -> None:
        """Free request ``rid``'s blocks and park its table row on the
        null block (no-op for the slab: its row is overwritten whole at
        the next admission)."""
        if self.paged:
            self.alloc.release(rid)
            self.block_table[slot, :] = NULL_BLOCK

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "kind": "paged" if self.paged else "slab",
        }
        if self.paged:
            out["kv_block_size"] = self.ecfg.kv_block_size
            out["blocks_per_slot"] = self.blocks_per_slot
            out["usable_blocks"] = self.alloc.usable_blocks
            out["blocks_in_use"] = self.alloc.blocks_in_use
            out["window_ring"] = self.ring
            if self.ring:
                out["ring_tokens"] = self.ring_mod
                out["ring_full_chain"] = self.ring_full_chain
        else:
            out["slots"] = self.ecfg.max_slots
        return out

    # ------------------------------------------------------------------
    # admission planning (block math; the engine owns slot scheduling)
    # ------------------------------------------------------------------
    def share_plan(self, tokens, resumed: bool) -> Tuple[int, List[int],
                                                         int, bool]:
        """Admission plan for a (re)prefill over ``tokens``:
        ``(start_pf, shared_blocks, n_fresh, cow_last)``.

        ``shared_blocks`` is the longest indexed prefix at block
        granularity (empty without prefix sharing) and ``start_pf`` the
        offset prefill resumes from — normally the end of the shared
        prefix.  On a *full*-sequence hit a fresh request still needs the
        last position's logits, so it restarts at ``len - 1``; that write
        lands inside the last shared block, which must be CoW'd first
        (``cow_last``).  A resumed request needs no logits (its pending
        last token is already committed), so a full hit skips prefill
        entirely.  ``n_fresh`` counts the fresh tail blocks covering the
        chunk-padded prefill writes."""
        C, bs = self.ecfg.prefill_chunk, self.ecfg.kv_block_size
        L = len(tokens)
        if self.ring_full_chain:
            # every KV leaf wraps the same fixed ring: a slot's chain is
            # whole-or-nothing, allocated up front regardless of prompt
            # length (sharing is rejected for windowed models — a ring
            # slot's contents depend on the sequence's absolute length)
            return 0, [], self.blocks_per_slot, False
        shared = self.alloc.match_prefix(tokens) if self.sharing else []
        P = len(shared) * bs
        cow_last = False
        if P >= L:                         # full hit (only when L % bs == 0)
            start = L if resumed else L - 1
            cow_last = not resumed
        else:
            start = P
        cover = start + (round_up(L - start, C) if L > start else 0)
        n_fresh = max(blocks_for_tokens(cover, bs), len(shared)) \
            - len(shared)
        return start, shared, n_fresh, cow_last

    def can_admit(self, plan) -> bool:
        start, shared, n_fresh, cow_last = plan
        return self.alloc.can_allocate(n_fresh + int(cow_last), shared)

    def bt_row(self, rid: int) -> np.ndarray:
        """A request's block-table row, built from its live chain (the
        engine-visible table row may still be parked on the null block)."""
        row = np.full((self.blocks_per_slot,), NULL_BLOCK, np.int32)
        chain = self.alloc.chain(rid)
        row[:len(chain)] = chain
        return row

    def probe_prefix(self, tokens) -> int:
        """Longest cached-prefix match in *tokens* (router affinity probe):
        a pure lookup that leaves the LRU ordering untouched, so probing a
        replica that is not chosen never perturbs its eviction order."""
        if not self.sharing:
            return 0
        return len(self.alloc.match_prefix(tokens, touch=False)) \
            * self.ecfg.kv_block_size

    # ------------------------------------------------------------------
    # prefill→decode handoff (paged only; see HandoffRecord)
    # ------------------------------------------------------------------
    def export_kv(self, pad_len: int) -> List[np.ndarray]:
        """Slice the scratch cache's positions ``[0, pad_len)`` out to
        host numpy, seq axis first — after a finished chunked prefill the
        scratch holds the request's full committed K/V (a gathered cached
        prefix included), so this IS the handoff payload."""
        axes = jax.tree.leaves(self.seq_axes)
        leaves = jax.tree.leaves(self.scratch)
        return [np.ascontiguousarray(
                    np.moveaxis(np.asarray(leaf), ax, 0)[:pad_len])
                for leaf, ax in zip(leaves, axes)]

    def import_kv(self, kv_leaves: List[np.ndarray], pad_len: int,
                  bt_row: np.ndarray) -> None:
        """Scatter a handoff record's KV into this pool through ``bt_row``
        using the same jitted ``write_chunk_blocks`` entry ordinary
        prefill uses (chunk by chunk over ``[0, pad_len)``), via a
        temporary batch-1 scratch rebuilt from the record.  Token-exact:
        the written K/V is bit-identical to the exporter's."""
        C = self.ecfg.prefill_chunk
        axes = jax.tree.leaves(self.seq_axes)
        leaves, treedef = jax.tree.flatten(self.scratch)
        if len(kv_leaves) != len(leaves):
            raise ValueError(
                f"handoff record has {len(kv_leaves)} KV leaves; this "
                f"engine's cache has {len(leaves)} — the two roles must "
                f"serve the same model")
        rebuilt = []
        for leaf, ax, rec in zip(leaves, axes, kv_leaves):
            shp = list(leaf.shape)
            seq_len = shp.pop(ax)
            want = (pad_len, *shp)
            if pad_len > seq_len or tuple(rec.shape) != want:
                raise ValueError(
                    f"handoff KV leaf shape {tuple(rec.shape)} does not "
                    f"match this engine's cache slice {want} "
                    f"(leaf {tuple(leaf.shape)}, seq axis {ax})")
            arr = np.zeros((seq_len, *shp), rec.dtype)
            arr[:pad_len] = rec
            # place under the live scratch's sharding: on a multi-device
            # mesh a default-placed (replicated) array would miss the
            # write_fn entry warmup compiled against sharded scratch
            rebuilt.append(jax.device_put(np.moveaxis(arr, 0, ax),
                                          leaf.sharding))
        imp = jax.tree.unflatten(treedef, rebuilt)
        with self._ctx():
            for start in range(0, pad_len, C):
                self.pool = self.write_fn(self.pool, imp, bt_row,
                                          np.int32(start),
                                          np.int32(pad_len))

    # ------------------------------------------------------------------
    def jit_counts(self) -> Dict[str, int]:
        counts = {("write_blocks" if self.paged else "write_slot"):
                  self.write_fn._cache_size()}
        if self.paged and self.sharing:
            counts["gather_prefix"] = self.gather_fn._cache_size()
            counts["copy_block"] = self.copy_fn._cache_size()
        return counts
