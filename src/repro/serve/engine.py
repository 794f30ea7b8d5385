"""Continuous-batching serving engine.

The engine is composed from three components (one file each):

* ``serve/frontend.py`` — ``AdmissionFront``: the arrival queue, free-slot
  pool, prefill pipeline, and preempted-recompute queue, plus the
  admission loop;
* ``serve/stepcore.py`` — ``StepCore``: the jitted prefill/decode/verify
  drivers and their deterministic key streams;
* ``serve/kvstore.py`` — ``KVOwner``: the physical KV pool (slab or
  paged), block allocator + prefix index, prefill scratch, and the jitted
  KV-movement primitives — including the prefill→decode *handoff*
  (``HandoffRecord``) that serializes a finished prefill's block-chain
  contents for a decode-role engine to import token-exactly.

``ServeEngine`` keeps the scheduling state that ties them together (slot
vectors, the decode batch, preemption, metrics) and drives jitted
functions with fixed signatures:

* ``model.prefill_chunk`` on a ``[1, prefill_chunk]`` scratch cache —
  newcomers' prompts are consumed chunk-by-chunk, interleaved with decode
  steps;
* ``model.decode_step`` on the full pool with a per-slot position vector —
  every occupied slot advances one token per step regardless of how long
  each sequence already is.  With ``EngineConfig.speculative_k`` the
  decode entry becomes a ``[B, k + 1]`` *verify* step instead: up to k
  self-drafted tokens per slot are scored in one forward and the accepted
  prefix (plus one token from the verify logits) is committed — 1 to
  k + 1 tokens per step, token-exact for greedy streams
  (``serve/speculative.py``; paged only).

Two pool layouts:

* **slab** (default): ``model.init_cache(max_slots, max_seq_len)`` — each
  slot owns a worst-case-length row; a finished prefill is scattered into
  its slot with ``write_slot`` (traced slot index).
* **paged** (``EngineConfig.paged``): a physical pool of ``num_kv_blocks``
  fixed-size blocks plus a ``[max_slots, max_blocks_per_slot]`` block table
  (see ``paging.py``).  Admission is gated on *free blocks* rather than
  free slots alone, block chains grow incrementally as decode advances,
  blocks are reclaimed the moment a request finishes, and when the
  allocator runs dry the youngest block-holding request is preempted and
  later *recomputed* (its prompt plus committed tokens re-prefilled).
  Finished prefill chunks are scattered straight into allocated blocks
  (``write_chunk_blocks``), and decode gathers K/V through the table.
  With ``EngineConfig.prefix_sharing`` the pool becomes a prefix-sharing
  cache: admission maps each request's longest radix-indexed token prefix
  into its chain with refcount bumps and prefills only the uncached tail
  (the cached prefix is gathered into the prefill scratch), shared blocks
  are copy-on-write, and dead indexed blocks are retained on an LRU
  cached-free list until allocation pressure evicts them (see
  ``paging.py`` and README "Prefix caching").

Engine **roles** (``EngineConfig.role``; paged only for the split roles):

* ``unified`` (default) — prefill and decode on one engine, as above.
* ``prefill`` — runs admission + chunked prefill only.  When a request's
  prefill finishes (first token sampled), instead of joining the decode
  batch it is exported as a ``HandoffRecord`` (block-chain KV + committed
  tokens + timestamps), its blocks are released (indexed prefixes stay
  cached), and the record is queued for ``pop_handoffs()``.
* ``decode`` — admits work only via ``import_handoff(record)``: the KV is
  scattered into its own pool through the same jitted
  ``write_chunk_blocks`` entry ordinary prefill uses, and the request
  joins the decode batch exactly where the exporter left it.  Greedy
  streams are token-identical to a unified engine serving the same
  requests.  (A decode-role engine still prefills when it must: a
  preempted request's recompute runs on the importing engine.)

Because every array shape — including the block table — is fixed at engine
construction, the jit caches hold exactly one entry each across admissions,
slot recycling, block growth, preemption, EOS, and role handoffs —
``report()["jit_entries"]`` asserts this is so.

Requests enter through an ``AdmissionQueue`` (Poisson or trace-driven
arrivals); freed slots are immediately re-admitted from the queue
(preempted requests first).  Per-step MoE schedule diagnostics
(moved_units, drops, max_load), KV-block occupancy, and per-request
TTFT/TPOT/e2e flow into ``ServeMetrics``.

Per-sequence state is owned by a ``serve/statestore.py``
``SequenceStateStore``: ``KVOwner`` (token-indexed K/V, slab or paged —
sliding-window layers are served paged as ring buffers, see kvstore.py)
for transformer families, and the slotted ``SlotStateStore`` for SSM and
hybrid families, whose recurrent state is fixed-size per slot.  The
engine addresses state only through the protocol (admission planning,
begin-prefill scratch reset, the write/gather/release primitives), so
scheduling — continuous batching, chunked prefill, preemption-by-
recompute — is identical across state kinds.

Scope: decoder-only transformer (dense and MoE), SSM, and hybrid
families; the mesh may shard the model/expert axis but not the batch
axis.  Encoder-decoder and prefix-embedding models are follow-ons; split
roles, prefix sharing, and speculative decoding remain paged-transformer
features (EngineConfig.validate + the ring/SSM checks here spell out
each combination's status).
"""
from __future__ import annotations

import contextlib
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import round_up
from repro.core.prefetch import stage_expert_rows
from repro.models import attention as attention_dispatch
from repro.serve.arrivals import WallClock
from repro.serve.frontend import AdmissionFront
from repro.serve.kvstore import HandoffRecord
from repro.serve.metrics import ServeMetrics
from repro.serve.statestore import make_state_store
from repro.serve.paging import NULL_BLOCK, blocks_for_tokens
from repro.serve.rebalance import ExpertRebalancer
from repro.serve.request import Request, RequestState, RequestStatus
from repro.serve.residency import (PREFETCH_POLICIES, ExpertResidencyManager,
                                   TierCostModel)
from repro.serve.sampling import sample_np
from repro.serve.speculative import (greedy_verify, make_proposer,
                                     rejection_verify)
from repro.serve.stepcore import StepCore

ENGINE_ROLES = ("unified", "prefill", "decode")


@dataclass(frozen=True)
class EngineConfig:
    """Static serving shapes — these fix every jitted signature."""
    max_slots: int = 4          # decode batch width (concurrent requests)
    max_seq_len: int = 128      # logical KV length (prompt + generation)
    prefill_chunk: int = 32     # prompt tokens consumed per prefill call
    chunks_per_step: int = 1    # prefill chunks interleaved per engine step
    eos_id: Optional[int] = None
    skew_seed: int = 0          # synthetic router-skew + sampling key stream
    # --- engine role (fleet disaggregation; see module docstring) ---
    # "unified" serves prefill + decode; "prefill" exports finished
    # prefills as HandoffRecords; "decode" imports them.  The split roles
    # hand KV off through the block machinery, so they require paged.
    role: str = "unified"
    # --- paged KV pool ---
    paged: bool = False
    kv_block_size: int = 16     # tokens per physical KV block
    num_kv_blocks: int = 0      # usable blocks (0 = worst case: slab parity)
    # fused Pallas paged-attention decode kernel (kernels/paged_attention):
    # reads K/V block-wise through the block table inside the kernel
    # instead of gathering each row's [L_max] logical view (paged only;
    # interpret mode off-TPU)
    fused_paged_attention: bool = False
    # fused grouped-GEMM Pallas expert FFN (kernels/moe_gmm) for the
    # decode/verify/prefill expert path (MoE models only; interpret mode
    # off-TPU): the scheduled expert batches run one tiled kernel instead
    # of per-expert dense matmuls
    fused_moe_gmm: bool = False
    # --- prefix sharing (paged only) ---
    prefix_sharing: bool = False
    # --- speculative decoding (paged only) ---
    # k > 0: each decode step verifies up to k self-drafted tokens in one
    # static-shape [B, k + 1] forward (serve/speculative.py); greedy
    # streams stay token-identical, sampled streams distribution-identical
    speculative_k: int = 0
    speculative_policy: str = "ngram"   # draft proposer (make_proposer)
    # --- sampling (0 temperature = greedy) ---
    temperature: float = 0.0
    top_k: int = 0              # 0 = full vocab when temperature > 0
    top_p: float = 1.0          # nucleus truncation (1.0 = disabled)
    # --- MoE load balancing (MoE models only) ---
    # decode scheduling policy override (None = the model config's policy):
    # harmoeny / round_robin / even_split / static_opt (core/scheduler.py)
    moe_policy: Optional[str] = None
    # between-window hot-expert replication (serve/rebalance.py): every
    # `rebalance_interval` engine steps the EMA-hottest experts' weights are
    # swapped into the model's static replica slots.  Requires the model to
    # be built with MoEConfig.num_replica_slots == replica_slots (the slots
    # exist from init, so swaps never change shapes or recompile).
    rebalance_interval: int = 0
    replica_slots: int = 0
    # tiered expert residency (serve/residency.py): keep only
    # `resident_experts` expert working-set rows (pod total, split evenly
    # across EP ranks) "HBM-resident"; the rest live in the emulated host
    # tier and are staged in per `prefetch_policy` — `predictive`
    # (EMA-predicted next-layer prefetch, stalls hidden), `on_demand`
    # (stage on first touch, stall every miss), or `none` (frozen initial
    # working set).  0 = residency off (everything device-resident).
    resident_experts: int = 0
    prefetch_policy: str = "predictive"

    def __post_init__(self):
        self.validate()

    def validate(self) -> "EngineConfig":
        """Every model-independent legality check, in one place.

        ``__post_init__`` runs this on construction, so an ``EngineConfig``
        that exists is valid; call sites that build configs field-by-field
        (flag plumbing, tests) can also invoke it directly.  Model-
        *dependent* checks (MoE-only knobs against non-MoE models, replica
        slot counts, sliding-window ring restrictions, family/mesh
        support) live in ``ServeEngine.__init__``/``make_state_store``,
        where the model is in hand.  Returns ``self`` for chaining."""
        # --- shapes ---
        if self.max_slots < 1 or self.max_seq_len < 1:
            raise ValueError("max_slots and max_seq_len must be >= 1")
        if self.prefill_chunk < 1 or self.chunks_per_step < 1:
            raise ValueError("prefill_chunk and chunks_per_step must be "
                             ">= 1")
        # --- role ---
        if self.role not in ENGINE_ROLES:
            raise ValueError(f"unknown engine role {self.role!r}; choose "
                             f"one of {ENGINE_ROLES}")
        if self.role != "unified" and not self.paged:
            raise ValueError(
                "prefill/decode engine roles hand KV off through the paged "
                "block machinery; they require EngineConfig.paged=True")
        # --- paged pool ---
        if self.paged and self.kv_block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        if self.num_kv_blocks < 0:
            raise ValueError("num_kv_blocks must be >= 0 (0 = slab-parity "
                             "worst case)")
        if self.prefix_sharing and not self.paged:
            raise ValueError("prefix_sharing requires the paged KV pool "
                             "(EngineConfig.paged=True)")
        if self.fused_paged_attention and not self.paged:
            raise ValueError("fused_paged_attention is the paged decode "
                             "kernel; it requires EngineConfig.paged=True")
        # --- speculative decoding ---
        if self.speculative_k < 0:
            raise ValueError("speculative_k must be >= 0")
        if self.speculative_k > 0 and not self.paged:
            raise ValueError("speculative decoding verifies through the "
                             "paged KV pool (rollback rides the block "
                             "machinery); it requires EngineConfig."
                             "paged=True")
        # --- sampling ---
        if self.temperature < 0 or self.top_k < 0:
            raise ValueError("temperature and top_k must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        # --- MoE serving knobs ---
        known = ("harmoeny", "round_robin", "even_split", "static_opt")
        if self.moe_policy is not None and self.moe_policy not in known:
            raise ValueError(f"unknown moe_policy {self.moe_policy!r}; "
                             f"choose one of {known}")
        if self.replica_slots < 0 or self.rebalance_interval < 0:
            raise ValueError("replica_slots and rebalance_interval must "
                             "be >= 0")
        if self.rebalance_interval > 0 and self.replica_slots == 0:
            raise ValueError("rebalance_interval > 0 needs replica_slots "
                             "> 0 (there is nowhere to place hot experts)")
        if self.resident_experts < 0:
            raise ValueError("resident_experts must be >= 0")
        if self.prefetch_policy not in PREFETCH_POLICIES:
            raise ValueError(
                f"unknown prefetch_policy {self.prefetch_policy!r}; choose "
                f"one of {PREFETCH_POLICIES}")
        return self


def paged_pool_len(max_seq_len: int, prefill_chunk: int,
                   prefix_sharing: bool, speculative_k: int = 0) -> int:
    """Chunk-padded logical pool length of the paged engine.  Prefix
    sharing pads one extra chunk: its prefill restarts (a block boundary,
    or ``prompt_len - 1`` on a full hit) are not chunk-aligned, so the
    final padded chunk can spill one chunk past the plain bound.
    Speculative decoding pads ``speculative_k`` extra tokens: a verify
    step writes all k + 1 window positions unconditionally (static
    shape), so a slot one token short of ``max_seq_len`` still scatters
    k positions past it — those writes must land inside the slot's own
    chain, never clamp into a neighbouring block.  Shared between the
    engine's ``_s_pad`` and ``engine_config_for``'s sliding-window
    validation so the two can never drift."""
    return round_up(max_seq_len, prefill_chunk) \
        + (prefill_chunk if prefix_sharing else 0) + speculative_k


class ServeEngine:
    def __init__(self, model, params, ecfg: EngineConfig, *, mesh=None,
                 clock=None):
        cfg = model.cfg
        if cfg.is_encoder_decoder or cfg.num_prefix_embeddings:
            raise NotImplementedError(
                f"serve engine supports decoder-only transformer, SSM, "
                f"and hybrid families; got {cfg.name} ({cfg.family})")
        extra = 1
        for ax, n in model.mesh_shape.sizes.items():
            if ax != "model":
                extra *= n
        if extra > 1:
            raise NotImplementedError(
                "serve engine shards the model/expert axis only; run "
                "with data=1 (data-parallel serving is an open item)")
        ecfg.validate()        # field-by-field call sites bypass init

        self.model = model
        self.params = params
        self.ecfg = ecfg
        self.cfg = cfg
        self.mesh = mesh
        self.clock = clock or WallClock()
        self.metrics = ServeMetrics()
        self.role = ecfg.role

        self._skew = bool(cfg.is_moe and cfg.moe.router_skew > 0)
        self._sample = ecfg.temperature > 0
        self._spec = ecfg.speculative_k > 0
        # --- MoE load balancing / hot-expert replication ---
        if (ecfg.moe_policy is not None or ecfg.replica_slots > 0) \
                and not cfg.is_moe:
            raise ValueError("moe_policy / replica_slots need an MoE model")
        if ecfg.fused_moe_gmm and not cfg.is_moe:
            raise ValueError("fused_moe_gmm is the grouped-GEMM expert "
                             "FFN kernel; it needs an MoE model")
        self._moe_policy = ecfg.moe_policy
        self._rebalancer: Optional[ExpertRebalancer] = None
        self._replica_ids: Optional[np.ndarray] = None
        self._rebalances = 0
        self._replica_swaps = 0
        if ecfg.replica_slots > 0:
            spec = model.moe_spec
            if spec is None or spec.tp_mode:
                raise ValueError(
                    "hot-expert replication needs expert-parallel MoE "
                    "(num_experts >= the mesh model degree)")
            if cfg.moe.num_replica_slots != ecfg.replica_slots:
                raise ValueError(
                    f"EngineConfig.replica_slots={ecfg.replica_slots} but "
                    f"the model was built with MoEConfig.num_replica_slots="
                    f"{cfg.moe.num_replica_slots}; the slots must exist "
                    f"from init so swaps never change parameter shapes")
            topo = spec.topo
            self._rebalancer = ExpertRebalancer(topo, ecfg.replica_slots)
            self._replica_ids = np.full(
                (topo.num_ranks, ecfg.replica_slots), -1, np.int32)
            self._swap_fn = jax.jit(swap_replicas)
        # --- tiered expert residency (serve/residency.py) ---
        self._residency: Optional[ExpertResidencyManager] = None
        self._residency_ids: Optional[np.ndarray] = None
        self._pending_stage = None        # decision applied next step start
        self._residency_stages = 0        # staging scatters dispatched
        self._res_base: Optional[Dict[str, float]] = None
        if ecfg.resident_experts > 0:
            spec = model.moe_spec
            if not cfg.is_moe or spec is None or spec.tp_mode:
                raise ValueError(
                    "tiered expert residency needs expert-parallel MoE "
                    "(num_experts >= the mesh model degree)")
            topo = spec.topo
            # the emulated host tier: one host-side copy of every expert
            # weight leaf, in the deterministic order the staging walk
            # visits them.  Device params stay authoritative (compute is
            # bit-exact at any budget); the staged writes copy identical
            # values, emulating the PCIe traffic the cost model prices.
            self._host_tier = [np.asarray(w)
                               for w in _collect_expert_leaves(params)]
            if not self._host_tier:
                raise ValueError("tiered expert residency found no expert "
                                 "weight leaves in the parameter tree")
            rows_axis = self._host_tier[0].ndim - 3
            n_rows = self._host_tier[0].shape[rows_axis]
            expert_bytes = float(sum(h.nbytes // h.shape[h.ndim - 3]
                                     for h in self._host_tier))
            assert n_rows == topo.num_ranks * topo.experts_per_rank
            self._residency = ExpertResidencyManager(
                topo, ecfg.resident_experts, policy=ecfg.prefetch_policy,
                cost=TierCostModel(expert_bytes=expert_bytes))
            self._residency_ids = self._residency._last_ids.copy()
            # one padded stage width => one jit entry across all swaps
            self._stage_width = max(
                topo.num_ranks * self._residency.W, 1)
            self._stage_fn = jax.jit(stage_weights)
        self._proposer = (make_proposer(ecfg.speculative_policy)
                          if self._spec else None)

        # --- jitted step drivers + key streams (serve/stepcore.py) ---
        self.core = StepCore(model, ecfg, skew=self._skew,
                             moe_policy=self._moe_policy,
                             layer_diags=self._residency is not None)

        self._paged = ecfg.paged
        self._sharing = ecfg.prefix_sharing
        B, C = ecfg.max_slots, ecfg.prefill_chunk
        if self._paged:
            bs = ecfg.kv_block_size
            # prefill writes whole padded chunks, so a slot's chain must
            # cover the chunk-rounded logical length (one extra chunk with
            # prefix sharing — see paged_pool_len)
            s_pad = paged_pool_len(ecfg.max_seq_len, C, self._sharing,
                                   ecfg.speculative_k)
            bps = blocks_for_tokens(s_pad, bs)
            w = cfg.sliding_window or 0
            if 0 < w <= bps * bs:
                # window-clamped layers are served as ring buffers
                # (kvstore ring_mods + paged_ring_decode_attention):
                # logical positions wrap modulo M = round_up(window, bs).
                # Ring contents depend on a sequence's absolute length,
                # and the ring gather is single-query — so the features
                # that re-read or hand off block contents are out.
                M = round_up(w, bs)
                blockers = []
                if ecfg.prefill_chunk > M:
                    blockers.append(
                        f"prefill_chunk {ecfg.prefill_chunk} > ring "
                        f"{M} tokens (a chunk must never self-overlap "
                        f"a ring slot; shrink prefill_chunk)")
                if ecfg.speculative_k > 0:
                    blockers.append("speculative verify is multi-query; "
                                    "the ring gather is single-query")
                if self._sharing:
                    blockers.append("prefix sharing keys blocks by "
                                    "content, but a ring slot's content "
                                    "depends on absolute sequence length")
                if ecfg.fused_paged_attention:
                    blockers.append("the fused paged kernel has no ring "
                                    "arithmetic")
                if ecfg.role != "unified":
                    blockers.append("KV handoff replays absolute-"
                                    "position scatters, not ring writes")
                if blockers:
                    raise ValueError(
                        f"{cfg.name} (sliding_window={w}) serves paged "
                        f"through the window ring buffer, which rejects: "
                        + "; ".join(blockers))
        else:
            s_pad = ecfg.max_seq_len
        # --- sequence-state store (serve/statestore.py): KVOwner for
        # transformer K/V, SlotStateStore for SSM/hybrid recurrent state —
        # the engine talks only to the SequenceStateStore protocol ---
        self.kv = make_state_store(model, ecfg, s_pad=s_pad, ctx=self._ctx,
                                   mesh=mesh)
        # --- admission/scheduling front (serve/frontend.py) ---
        self.front = AdmissionFront(B)
        self._register_sections()

        self.pos = np.zeros((B,), np.int32)      # per-slot sequence length
        self.tok = np.zeros((B,), np.int32)      # per-slot last token
        self.active = np.zeros((B,), bool)       # slot in the decode batch
        self._step_idx = 0
        self._chunk_idx = 0
        # allocator lifetime counters at window start (report() deltas)
        self._evict0 = 0
        self._cow0 = 0
        self._warm_counts: Optional[Dict[str, int]] = None
        # --- prefill→decode handoff state (split roles) ---
        self._handoffs_out: deque = deque()      # exported, awaiting pickup
        self.handoffs_exported = 0
        self.handoffs_imported = 0
        self.handoff_bytes_out = 0
        self.handoff_bytes_in = 0
        # attention dispatch-log snapshot taken right after warmup's traces;
        # when warmup() is skipped (tests drive run() directly) report()
        # falls back to the live log, which this reset scopes to the
        # engine built last
        self._attn_dispatch: Optional[List[Dict[str, Any]]] = None
        attention_dispatch.reset_dispatch_log()

    # ------------------------------------------------------------------
    # component delegation — the pre-refactor attribute surface.  Tests,
    # benchmarks, and the fleet router address engine state through these
    # names; they forward to the owning component.
    # ------------------------------------------------------------------
    @property
    def _alloc(self):
        return self.kv.alloc

    @property
    def pool(self):
        return self.kv.pool

    @pool.setter
    def pool(self, v):
        self.kv.pool = v

    @property
    def _scratch(self):
        return self.kv.scratch

    @_scratch.setter
    def _scratch(self, v):
        self.kv.scratch = v

    @property
    def block_table(self):
        return self.kv.block_table

    @property
    def blocks_per_slot(self):
        return self.kv.blocks_per_slot

    @property
    def kv_capacity(self):
        return self.kv.kv_capacity

    @property
    def _s_pad(self):
        return self.kv.s_pad

    @property
    def _seq_axes(self):
        return self.kv.seq_axes

    @property
    def _write_fn(self):
        return self.kv.write_fn

    @property
    def _gather_fn(self):
        return self.kv.gather_fn

    @property
    def _copy_fn(self):
        return self.kv.copy_fn

    @property
    def _prefill_fn(self):
        return self.core.prefill_fn

    @property
    def _decode_fn(self):
        return self.core.decode_fn

    @property
    def _base_key(self):
        return self.core.base_key

    @property
    def _pf_key(self):
        return self.core.pf_key

    @property
    def _dec_key(self):
        return self.core.dec_key

    @property
    def _samp_rng(self):
        return self.core.samp_rng

    @property
    def queue(self):
        return self.front.queue

    @property
    def free_slots(self):
        return self.front.free_slots

    @property
    def state_by_slot(self):
        return self.front.state_by_slot

    @property
    def slot_history(self):
        return self.front.slot_history

    @property
    def _pf(self):
        return self.front.pf

    @_pf.setter
    def _pf(self, v):
        self.front.pf = v

    @property
    def _pf_queue(self):
        return self.front.pf_queue

    @property
    def _resume(self):
        return self.front.resume

    @property
    def _admit_seq(self):
        return self.front.admit_seq

    @_admit_seq.setter
    def _admit_seq(self, v):
        self.front.admit_seq = v

    # ------------------------------------------------------------------
    def _ctx(self):
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _eos_id(self, req: Request) -> Optional[int]:
        """Per-request EOS override, falling back to the engine default."""
        return req.eos_id if req.eos_id is not None else self.ecfg.eos_id

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if self.role == "decode":
            raise ValueError(
                "decode-role engine takes work via import_handoff(), not "
                "submit(); route arrivals to a prefill or unified engine")
        L, C = req.prompt_len, self.ecfg.prefill_chunk
        if round_up(L, C) > self.kv_capacity:
            raise ValueError(
                f"request {req.rid}: prompt of {L} (padded to "
                f"{round_up(L, C)}) exceeds the per-layer KV capacity "
                f"{self.kv_capacity}")
        if L + req.max_new_tokens > self.ecfg.max_seq_len:
            raise ValueError(
                f"request {req.rid}: prompt {L} + max_new "
                f"{req.max_new_tokens} exceeds max_seq_len "
                f"{self.ecfg.max_seq_len}")
        self.queue.push(req)

    def has_work(self) -> bool:
        return bool(len(self.queue) or self._in_flight())

    def _in_flight(self) -> bool:
        """Admitted work whose timestamps already live on the current clock
        (queued-but-unadmitted requests carry none — their arrival_time is
        relative to the measurement window, not the clock origin).
        Preempted requests and exported-but-unclaimed handoffs hold
        timestamps too."""
        return self.front.in_flight(bool(self.active.any())) \
            or bool(self._handoffs_out)

    # ------------------------------------------------------------------
    # admission (block-aware in paged mode; preempted requests first)
    # ------------------------------------------------------------------
    def _share_plan(self, tokens, resumed: bool) -> Tuple[int, List[int],
                                                          int, bool]:
        return self.kv.share_plan(tokens, resumed)

    def _can_admit(self, plan) -> bool:
        return self.kv.can_admit(plan)

    def _place(self, st: RequestState, now: float, plan=None) -> None:
        slot = self.free_slots.popleft()
        st.slot = slot
        st.status = RequestStatus.PREFILL
        st.admit_seq = self._admit_seq
        self._admit_seq += 1
        self.state_by_slot[slot] = st
        self.slot_history.append((st.req.rid, slot))
        if self._paged:
            start, shared, n_fresh, cow_last = plan
            chain = self._alloc.alloc_chain(st.req.rid, n_fresh,
                                            shared=shared)
            assert chain is not None      # gated by the caller
            if cow_last:
                # full-prompt hit: the last-position recompute writes into
                # the final shared block — give this chain a private copy
                ok = self._cow_block(st, len(shared) - 1)
                assert ok                 # the CoW block was gated too
            st.prefill_pos = start
            # nothing to gather when no cached prefix was mapped: prefill
            # starts at 0 and builds the scratch itself
            st.prefix_loaded = start == 0
            if st.n_preempted == 0:
                st.cached_prefix_tokens = start
            elif self._sharing:
                self.metrics.resume_cached_tokens += start
            if st.resumed and start >= st.prefill_len:
                # full-sequence hit on recompute: every committed position's
                # K/V is already cached — no prefill at all, the pending
                # last token decodes next step
                self._activate(st, st.prefill_len, st.output[-1])
                return
        # self.block_table[slot] stays all-null until the slot joins
        # the decode batch: decode steps write every row's (garbage,
        # for inactive rows) K/V through the table, and a real entry
        # here would let that garbage clobber the mid-prefill blocks.
        # Prefill writes go through _bt_row(st) instead.
        self._pf_queue.append(st)

    def _bt_row(self, st: RequestState) -> np.ndarray:
        """This request's block-table row, built from its live chain (the
        engine-visible table row may still be parked on the null block)."""
        return self.kv.bt_row(st.req.rid)

    def _activate(self, st: RequestState, pos: int, tok: int) -> None:
        """Move a finished prefill into the decode batch."""
        s = st.slot
        st.status = RequestStatus.DECODE
        self.pos[s] = pos
        self.tok[s] = tok
        self.active[s] = True
        if self._paged:
            self.block_table[s] = self._bt_row(st)

    def _admit(self, now: float) -> None:
        self.front.admit(now, paged=self._paged, plan_fn=self._share_plan,
                         can_admit_fn=self._can_admit, place_fn=self._place)

    # ------------------------------------------------------------------
    # preemption: drop a request's state, recompute on re-admission.
    # Allocator pressure triggers it in paged mode (reclaim the youngest
    # holder's blocks); any store supports it — a slot store's state is
    # rebuilt token-exactly by re-prefilling prompt + committed output.
    # ------------------------------------------------------------------
    def _youngest_holder(self) -> Optional[RequestState]:
        cands = [st for st in self.state_by_slot if st is not None]
        return max(cands, key=lambda st: st.admit_seq) if cands else None

    def _preempt(self, st: RequestState) -> None:
        s = st.slot
        self.kv.release(st.req.rid, s)
        self.active[s] = False
        self.pos[s] = 0
        self.tok[s] = 0
        self.state_by_slot[s] = None
        self.free_slots.append(s)
        if self._pf is st:
            self._pf = None
        elif st in self._pf_queue:
            self._pf_queue.remove(st)
        st.slot = -1
        st.status = RequestStatus.QUEUED
        st.prefill_pos = 0
        st.prefix_loaded = False
        st.n_preempted += 1
        self._resume.append(st)
        self.metrics.preemptions += 1

    def _reclaim_until(self, st: RequestState, op):
        """Run allocator ``op`` (returns None while the pool is dry),
        preempting the youngest block holder between attempts.  Returns
        the op's result, or None if ``st`` itself was preempted to make
        room."""
        while True:
            res = op()
            if res is not None:
                return res
            victim = self._youngest_holder()
            if victim is None:
                raise RuntimeError("KV allocator dry with no block holders")
            self._preempt(victim)
            if victim is st:
                return None

    def _cow_block(self, st: RequestState, j: int) -> bool:
        """Give ``st`` a private copy of logical block ``j`` before a write
        would mutate it, preempting younger holders while the pool is dry.
        Returns False if ``st`` itself was preempted to make room."""
        res = self._reclaim_until(st, lambda: self._alloc.cow(st.req.rid, j))
        if res is None:
            return False
        old, new = res
        with self._ctx():
            self.pool = self._copy_fn(self.pool, np.int32(old),
                                      np.int32(new))
        if st.slot >= 0 and self.active[st.slot]:
            self.block_table[st.slot, j] = new
        return True

    def _grow_chain(self, st: RequestState) -> bool:
        """Extend ``st``'s block chain by one, preempting younger holders
        while the allocator is dry.  Returns False if ``st`` itself was the
        youngest and got preempted to make room."""
        blk = self._reclaim_until(st,
                                  lambda: self._alloc.extend(st.req.rid))
        if blk is None:
            return False
        n = len(self._alloc.chain(st.req.rid))
        self.block_table[st.slot, n - 1] = blk
        return True

    def _ensure_decode_blocks(self) -> None:
        """Before a decode step, every active slot needs its chain to cover
        the write range ``[pos[s], pos[s] + speculative_k]`` (a verify step
        writes all k + 1 window positions unconditionally; plain decode is
        the k = 0 case) — grow incrementally, oldest requests first so
        scarce blocks go to the work closest to finishing."""
        if self.kv.ring_full_chain:
            # every KV leaf wraps the fixed ring: chains were allocated
            # whole at admission and never grow
            return
        bs = self.ecfg.kv_block_size
        span = self.ecfg.speculative_k
        order = sorted(np.nonzero(self.active)[0],
                       key=lambda s: self.state_by_slot[s].admit_seq)
        for s in order:
            if not self.active[s]:        # preempted earlier in this pass
                continue
            st = self.state_by_slot[s]
            last = self.pos[s] + span     # deepest position written
            if self._sharing:
                # copy-on-write guard: every block this step writes into
                # must be private to this chain (a shared block is
                # immutable — and rejected-draft positions write garbage,
                # which must never land in another chain's prefix)
                preempted = False
                for j in range(self.pos[s] // bs, last // bs + 1):
                    chain = self._alloc.chain(st.req.rid)
                    if j < len(chain) \
                            and self._alloc.refcount(chain[j]) > 1:
                        if not self._cow_block(st, j):
                            preempted = True  # st itself evicted for room
                            break
                if preempted:
                    continue
            while len(self._alloc.chain(st.req.rid)) * bs <= last:
                if not self._grow_chain(st):
                    break

    # ------------------------------------------------------------------
    def _next_key(self, stream_key, idx: int):
        return self.core.next_key(stream_key, idx)

    def _prefill_work(self, now: float) -> bool:
        did = False
        C = self.ecfg.prefill_chunk
        for _ in range(self.ecfg.chunks_per_step):
            if self._pf is None:
                if not self._pf_queue:
                    break
                self._pf = self._pf_queue.popleft()
                # recurrent-state stores reset the scratch to the pristine
                # zero state here: chunked prefill *carries* state across
                # chunk calls (that is prefill continuation), so a new
                # request must not inherit the previous one's fold
                self.kv.begin_prefill()
            st = self._pf
            if st.prefill_start_time is None:
                # the step's clock reading: prefill runs first in a step
                st.prefill_start_time = now
            seq = st.prefill_tokens
            start, L = st.prefill_pos, st.prefill_len
            n = min(C, L - start)
            with self.metrics.span("serve.prefill", rid=st.req.rid,
                                   tokens=n, start=start) as sp:
                if self._sharing and start > 0 and not st.prefix_loaded:
                    # mid-prompt restart off a cached prefix: the uncached
                    # tail's attention reads the prefix K/V from the
                    # scratch, so gather it out of the shared blocks first
                    with self._ctx():
                        self._scratch = self._gather_fn(
                            self.pool, self._scratch, self._bt_row(st),
                            np.int32(start))
                    st.prefix_loaded = True
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :n] = seq[start:start + n]
                key = self._next_key(self._pf_key, self._chunk_idx)
                self._chunk_idx += 1
                with self._ctx():
                    logits, self._scratch, _, diags = self._prefill_fn(
                        self.params, chunk, self._scratch, np.int32(start),
                        np.int32(n - 1), key, self._replica_ids)
                    if self._paged:
                        # finished chunk -> straight into the allocated
                        # blocks (valid_to diverts ring-leaf pad writes to
                        # the null block so they cannot clobber in-window
                        # ring slots)
                        self.pool = self._write_fn(
                            self.pool, self._scratch, self._bt_row(st),
                            np.int32(start), np.int32(start + n))
                    jax.block_until_ready(logits)
            st.prefill_pos += n
            if self._sharing:
                # every block fully covered by committed K/V joins the
                # prefix index (keyed on its token-id chain)
                self._alloc.commit_prefix(st.req.rid,
                                          seq[:st.prefill_pos])
            self.metrics.record_step(diags if self.cfg.is_moe else {}, 0,
                                     phase="prefill")
            # prefix-tail: the request restarted mid-sequence off a prefix
            # cache hit, so its chunks attend a deeper window than a plain
            # prefill of the same tail length
            self.metrics.record_phase(
                ("prefix_tail" if (self._sharing
                                   and (st.cached_prefix_tokens or 0) > 0)
                 else "prefill"),
                n, sp.seconds)
            did = True
            if st.prefill_done:
                if st.resumed:
                    # recompute finished: the re-prefill rebuilt the state
                    # for prompt + output[:-1]; the pending last token
                    # decodes next step.  No TTFT restamp, no logits
                    # consumed.  Paged chains were written chunk-by-chunk
                    # above; a slab/slot store commits its rebuilt
                    # scratch state to the slot now — without this the
                    # resumed request would decode off the stale slot.
                    if not self._paged:
                        with self._ctx():
                            self.pool = self._write_fn(
                                self.pool, self._scratch, np.int32(st.slot))
                    self._activate(st, L, st.output[-1])
                    self._pf = None
                    continue
                with self.metrics.span("serve.first_token", rid=st.req.rid):
                    first = sample_np(np.asarray(logits)[0], self._samp_rng,
                                      temperature=self.ecfg.temperature,
                                      top_k=self.ecfg.top_k,
                                      top_p=self.ecfg.top_p)
                if not self._paged:
                    with self._ctx():
                        self.pool = self._write_fn(self.pool, self._scratch,
                                                   np.int32(st.slot))
                # stamp AFTER the host sync: TTFT must include the prefill
                # compute, not just the queueing ahead of it
                now = self.clock.now()
                st.first_token_time = now
                st.output.append(first)
                eos = self._eos_id(st.req)
                if (eos is not None and first == eos) \
                        or st.n_generated >= st.req.max_new_tokens:
                    self._finish(st, now)
                elif self.role == "prefill":
                    # prefill role: the request leaves this engine here —
                    # the scratch still holds its full committed K/V, so
                    # export before the next chunk overwrites it
                    self._export_handoff(st)
                else:
                    self._activate(st, L, first)
                self._pf = None
        return did

    def _decode_work(self, now: float) -> bool:
        if self._spec:
            return self._speculative_decode_work(now)
        if self._paged and self.active.any():
            with self.metrics.span("serve.ensure_blocks"):
                self._ensure_decode_blocks()
        if not self.active.any():
            return False
        self._apply_pending_stage()
        key = self._next_key(self._dec_key, self._step_idx)
        bt_args = (self.block_table.copy(),) if self._paged else ()
        rows = int(self.active.sum())
        with self.metrics.span("serve.decode", rows=rows) as sp:
            with self._ctx():
                nxt, self.pool, diags = self._decode_fn(
                    self.params, self.tok[:, None], self.pool, self.pos,
                    *bt_args, key, self.active.copy(), self._replica_ids,
                    self._residency_ids)
            nxt = np.asarray(nxt)
        self.metrics.record_phase("decode", rows, sp.seconds)
        with self.metrics.span("serve.commit"):
            self._commit_decode(nxt, diags)
        return True

    def _commit_decode(self, nxt: np.ndarray, diags) -> None:
        """Everything after a decode step's tokens are back on the host:
        diagnostics, load and residency observation, KV occupancy, and
        each slot's new token (finishing the requests that are done)."""
        now = self.clock.now()       # post-sync: token times include compute
        diags = dict(diags)
        layer_loads = diags.pop("expert_load_layers", None)
        self.metrics.record_step(diags if self.cfg.is_moe else {},
                                 int(self.active.sum()), phase="decode")
        self._observe_load(diags)
        self._observe_residency(layer_loads)
        if self._paged:
            self.metrics.record_kv(self._alloc.blocks_in_use,
                                   self._alloc.usable_blocks)
        for s in np.nonzero(self.active)[0]:
            st = self.state_by_slot[s]
            self.pos[s] += 1
            t = int(nxt[s])
            st.output.append(t)
            if self._sharing and self.pos[s] % self.ecfg.kv_block_size == 0:
                # this step's write just filled a block: index it so later
                # prompts extending this sequence (e.g. multi-turn) can hit
                full = np.concatenate([st.req.tokens,
                                       np.asarray(st.output, np.int32)])
                self._alloc.commit_prefix(st.req.rid, full[:self.pos[s]])
            eos = self._eos_id(st.req)
            if (eos is not None and t == eos) \
                    or st.n_generated >= st.req.max_new_tokens:
                self._finish(st, now)
            else:
                self.tok[s] = t

    def _speculative_decode_work(self, now: float) -> bool:
        """One speculative decode step: draft up to k tokens per active
        slot (self-drafting, host-side), verify them all in one static
        ``[B, k + 1]`` forward against the paged pool, and commit the
        accepted prefix plus one token from the verify logits — between 1
        and k + 1 tokens per step.  Rejected window positions' K/V writes
        are rolled back by masking: they sit past the committed length
        (``pos`` never counts them), each is rewritten with real K/V
        before ``pos`` reaches it, and the CoW guard in
        ``_ensure_decode_blocks`` keeps them out of shared blocks — so
        sharing, preemption-by-recompute, and the prefix index all stay
        token-exact."""
        if self.active.any():
            with self.metrics.span("serve.ensure_blocks"):
                self._ensure_decode_blocks()
        if not self.active.any():
            return False
        self._apply_pending_stage()
        B, k = self.ecfg.max_slots, self.ecfg.speculative_k
        toks = np.zeros((B, k + 1), np.int32)
        draft_len = np.zeros((B,), np.int32)
        for s in np.nonzero(self.active)[0]:
            st = self.state_by_slot[s]
            toks[s, 0] = self.tok[s]
            # never draft past the generation budget: the step commits up
            # to draft_len + 1 tokens, and max_new caps committed tokens
            cap = min(k, st.req.max_new_tokens - st.n_generated - 1)
            if cap > 0:
                ctx = np.concatenate([st.req.tokens,
                                      np.asarray(st.output, np.int32)])
                d = self._proposer.propose(ctx, cap)
                toks[s, 1:1 + len(d)] = d
                draft_len[s] = len(d)
        key = self._next_key(self._dec_key, self._step_idx)
        with self.metrics.span("serve.verify",
                               rows=int(self.active.sum())) as sp:
            with self._ctx():
                logits, self.pool, diags = self._decode_fn(
                    self.params, toks, self.pool, self.pos,
                    self.block_table.copy(), key, self.active.copy(),
                    self._replica_ids, self._residency_ids)
            logits = np.asarray(logits)          # [B, k+1, V]
        with self.metrics.span("serve.commit"):
            total_commit = self._commit_verify(toks, draft_len, logits,
                                               diags)
        self.metrics.record_phase("verify", total_commit, sp.seconds)
        return True

    def _commit_verify(self, toks: np.ndarray, draft_len: np.ndarray,
                       logits: np.ndarray, diags) -> int:
        """Everything after a verify step's logits are back on the host:
        diagnostics, acceptance of each slot's drafts and the committed
        tokens; returns the number of tokens committed."""
        bs = self.ecfg.kv_block_size
        now = self.clock.now()   # post-sync: token times include compute
        diags = dict(diags)
        layer_loads = diags.pop("expert_load_layers", None)
        self.metrics.record_step(diags if self.cfg.is_moe else {},
                                 int(self.active.sum()), phase="decode")
        self._observe_load(diags)
        self._observe_residency(layer_loads)
        self.metrics.record_kv(self._alloc.blocks_in_use,
                               self._alloc.usable_blocks)
        self.metrics.spec_steps += 1
        self.metrics.spec_slot_steps += int(self.active.sum())
        total_commit = 0
        for s in np.nonzero(self.active)[0]:
            st = self.state_by_slot[s]
            drafts = toks[s, 1:1 + int(draft_len[s])].tolist()
            if self._sample:
                n_acc, nxt = rejection_verify(
                    logits[s], drafts, self._samp_rng,
                    temperature=self.ecfg.temperature,
                    top_k=self.ecfg.top_k, top_p=self.ecfg.top_p)
            else:
                n_acc, nxt = greedy_verify(logits[s], drafts)
            self.metrics.spec_drafted += len(drafts)
            self.metrics.spec_accepted += n_acc
            old_pos = int(self.pos[s])
            eos = self._eos_id(st.req)
            finished = False
            n_commit = 0
            for t in drafts[:n_acc] + [nxt]:
                st.output.append(int(t))
                n_commit += 1
                if (eos is not None and t == eos) \
                        or st.n_generated >= st.req.max_new_tokens:
                    finished = True
                    break
            self.pos[s] += n_commit
            self.metrics.spec_committed += n_commit
            total_commit += n_commit
            if self._sharing and self.pos[s] // bs > old_pos // bs:
                # crossed >= 1 block boundary this step: index every newly
                # full block so later prompts can hit them
                full = np.concatenate([st.req.tokens,
                                       np.asarray(st.output, np.int32)])
                self._alloc.commit_prefix(st.req.rid, full[:self.pos[s]])
            if finished:
                self._finish(st, now)
            else:
                self.tok[s] = st.output[-1]
        return total_commit

    # ------------------------------------------------------------------
    # prefill→decode handoff (split engine roles; serve/kvstore.py)
    # ------------------------------------------------------------------
    def _export_handoff(self, st: RequestState) -> None:
        """Package a finished prefill as a ``HandoffRecord`` and release
        its slot + blocks.  The scratch cache still holds the request's
        full committed K/V (gathered cached prefix included), so the
        export is a pure host-side slice; indexed prefix blocks stay on
        the cached-free list, so the prefill side's prefix cache keeps
        serving later arrivals."""
        C = self.ecfg.prefill_chunk
        pad = round_up(st.prefill_len, C)
        rec = HandoffRecord(
            rid=st.req.rid, prompt_tokens=st.req.tokens.copy(),
            output=list(st.output), pos=st.prefill_len, pad_len=pad,
            prefill_chunk=C, max_new_tokens=st.req.max_new_tokens,
            eos_id=st.req.eos_id, kv=self.kv.export_kv(pad),
            cached_prefix_tokens=int(st.cached_prefix_tokens or 0),
            arrival_time=st.req.arrival_time,
            admitted_time=st.admitted_time,
            first_token_time=st.first_token_time,
            prefill_start_time=st.prefill_start_time)
        self._handoffs_out.append(rec)
        self.handoffs_exported += 1
        self.handoff_bytes_out += rec.nbytes
        st.status = RequestStatus.HANDED_OFF
        s = st.slot
        self.state_by_slot[s] = None
        self.free_slots.append(s)
        self._alloc.release(st.req.rid)
        self.block_table[s, :] = NULL_BLOCK

    def pop_handoffs(self) -> List[HandoffRecord]:
        """Drain the exported-handoff queue (prefill role; the fleet
        router moves these to a decode-role engine)."""
        out = list(self._handoffs_out)
        self._handoffs_out.clear()
        return out

    def import_handoff(self, rec: HandoffRecord) -> bool:
        """Adopt a handed-off request: allocate a slot + block chain,
        scatter the record's KV into this engine's pool, and join the
        decode batch at the exporter's committed position.  Returns False
        (record untouched, retry later) when no slot or not enough blocks
        are free right now; raises when the record can never fit this
        engine's shapes."""
        if not self._paged:
            raise RuntimeError("import_handoff needs the paged KV pool")
        C, bs = self.ecfg.prefill_chunk, self.ecfg.kv_block_size
        if rec.prefill_chunk != C:
            raise ValueError(
                f"handoff was prefilled with chunk {rec.prefill_chunk}, "
                f"this engine uses {C}; the import replays the exporter's "
                f"chunk-aligned scatters, so the two must match")
        L = len(rec.prompt_tokens)
        if L + rec.max_new_tokens > self.ecfg.max_seq_len:
            raise ValueError(
                f"handoff {rec.rid}: prompt {L} + max_new "
                f"{rec.max_new_tokens} exceeds max_seq_len "
                f"{self.ecfg.max_seq_len}")
        if rec.pad_len > self.kv_capacity:
            raise ValueError(
                f"handoff {rec.rid}: padded prefill {rec.pad_len} exceeds "
                f"the per-layer KV capacity {self.kv_capacity}")
        if not self.free_slots:
            return False
        n_blocks = blocks_for_tokens(rec.pad_len, bs)
        if not self._alloc.can_allocate(n_blocks, []):
            return False
        req = Request(rid=rec.rid, tokens=rec.prompt_tokens,
                      max_new_tokens=rec.max_new_tokens,
                      arrival_time=rec.arrival_time, eos_id=rec.eos_id)
        st = RequestState(req=req, slot=-1,
                          admitted_time=rec.admitted_time,
                          prefill_start_time=rec.prefill_start_time,
                          first_token_time=rec.first_token_time,
                          output=list(rec.output), prefill_pos=rec.pos,
                          cached_prefix_tokens=rec.cached_prefix_tokens)
        slot = self.free_slots.popleft()
        st.slot = slot
        st.admit_seq = self._admit_seq
        self._admit_seq += 1
        self.state_by_slot[slot] = st
        self.slot_history.append((req.rid, slot))
        chain = self._alloc.alloc_chain(req.rid, n_blocks)
        assert chain is not None          # gated by can_allocate above
        self.kv.import_kv(rec.kv, rec.pad_len, self.kv.bt_row(req.rid))
        if self._sharing:
            # the imported K/V is bit-identical to a local prefill's, so
            # its full blocks are index-worthy here too
            self._alloc.commit_prefix(req.rid,
                                      st.prefill_tokens[:rec.pos])
        self.handoffs_imported += 1
        self.handoff_bytes_in += rec.nbytes
        self._activate(st, rec.pos, st.output[-1])
        return True

    # ------------------------------------------------------------------
    # fleet routing probes (serve/fleet.py)
    # ------------------------------------------------------------------
    def load_stats(self) -> Dict[str, Any]:
        """Cheap scheduler-state snapshot the fleet router scores replicas
        by — no device sync, no allocator mutation."""
        if self._paged:
            bs = self.ecfg.kv_block_size
            kv_tokens = self._alloc.blocks_in_use * bs
            kv_util = (self._alloc.blocks_in_use
                       / max(self._alloc.usable_blocks, 1))
        else:
            kv_tokens = int(self.pos.sum())
            kv_util = float(self.active.sum()) / self.ecfg.max_slots
        return {
            "queued_tokens": self.front.queued_tokens(),
            "kv_tokens": int(kv_tokens),
            "kv_utilization": float(kv_util),
            "active_slots": int(self.active.sum()),
            "free_slots": len(self.free_slots),
            "pending_handoffs": len(self._handoffs_out),
        }

    def probe_prefix(self, tokens) -> int:
        """Longest cached-prefix match for ``tokens`` in this engine's
        prefix index, in tokens (0 without prefix sharing).  Pure lookup —
        probing a replica that is not chosen never perturbs its LRU."""
        return self.kv.probe_prefix(tokens)

    # ------------------------------------------------------------------
    # between-window hot-expert replication (serve/rebalance.py)
    # ------------------------------------------------------------------
    def _observe_load(self, diags) -> None:
        """Fold this decode step's global per-expert load into the
        rebalancer's EMA (the [Ep] ``expert_load`` vector the MoE layer
        emits alongside its scalar diagnostics)."""
        if self._rebalancer is None or "expert_load" not in diags:
            return
        self._rebalancer.observe(
            np.asarray(diags["expert_load"]).reshape(-1))

    def _rebalance_now(self) -> None:
        """Close a load window: re-derive the hot-expert set from the EMA
        and, if it changed, gather the hot experts' weight rows into every
        non-host rank's replica slots.  Pure value updates — the swap fn
        and the decode fn keep their single jit entries, and the new
        ``replica_ids`` flow into the next step as a traced argument."""
        dec = self._rebalancer.propose()
        self._rebalances += 1
        if not dec.changed:
            return
        with self._ctx():
            self.params = self._swap_fn(self.params, dec.weight_rows)
        self._replica_ids = dec.replica_ids
        self._replica_swaps += 1

    # ------------------------------------------------------------------
    # tiered expert residency (serve/residency.py)
    # ------------------------------------------------------------------
    def _observe_residency(self, layer_loads) -> None:
        """Feed this step's stacked per-layer expert loads (the
        ``expert_load_layers`` diagnostic, [n_moe_layers, Ep]) to the
        residency manager.  Its decision — new table + stage rows — is
        held as the *pending* stage and applied at the start of the next
        decode step, double-buffering the emulated host→HBM copy against
        that step's compute."""
        if self._residency is None or layer_loads is None:
            return
        self._pending_stage = self._residency.step(np.asarray(layer_loads))

    def _apply_pending_stage(self) -> None:
        """Apply the previous step's residency decision: dispatch its
        jitted staging scatter now (jax's async dispatch overlaps the
        copy with the decode compute that follows), then publish the new
        ``[G, W]`` table as this step's traced argument."""
        dec = self._pending_stage
        if dec is None:
            return
        self._pending_stage = None
        if dec.stage_rows.size:
            self._dispatch_stage(dec.stage_rows)
        self._residency_ids = dec.residency_ids

    def _dispatch_stage(self, rows: np.ndarray) -> None:
        """Run the host→HBM staging scatter for ``rows`` (stacked
        weight-row indices).  Rows are padded/clipped to the fixed stage
        width so the jit cache keeps one entry; padding repeats row 0,
        which is safe because every staged value is gathered from the
        host tier — a bit-identical copy of the device rows."""
        padded = np.zeros((self._stage_width,), np.int32)
        n = min(len(rows), self._stage_width)
        padded[:n] = rows[:n]
        vals = [np.take(h, padded, axis=h.ndim - 3)
                for h in self._host_tier]
        with self._ctx():
            self.params = self._stage_fn(self.params, padded, vals)
        self._residency_stages += 1

    def _finish(self, st: RequestState, now: float) -> None:
        st.finish_time = now
        st.status = RequestStatus.FINISHED
        self.metrics.complete(st)
        s = st.slot
        self.active[s] = False
        self.pos[s] = 0
        self.tok[s] = 0
        self.state_by_slot[s] = None
        self.free_slots.append(s)
        # immediate reclamation: paged blocks return to the free list now
        # (slab/slot stores drop nothing — the row is overwritten whole at
        # the next admission)
        self.kv.release(st.req.rid, s)

    # ------------------------------------------------------------------
    def reset_metrics(self) -> None:
        """Fresh metrics AND a re-zeroed clock for a new measurement window;
        slot state, jit caches, and warmup status are kept. Queued (not yet
        admitted) requests are fine — like ``run()``'s rebase, only their
        window-relative arrival times carry over — but admitted work holds
        timestamps on the current clock, so the engine must have nothing in
        flight."""
        if self._in_flight():
            raise RuntimeError("cannot reset metrics while work is in flight")
        self.metrics = ServeMetrics()
        self._register_sections()
        self.slot_history.clear()
        if self._paged:
            self._evict0 = self._alloc.evictions
            self._cow0 = self._alloc.cow_copies
        if self._residency is not None:
            self._res_base = self._residency.counters()
        self.clock.reset()

    def warmup(self) -> None:
        """Compile the jitted functions on dummy data so the first request's
        TTFT measures serving latency, not XLA compilation.  Overwrites pool
        slot 0 (slab) / the null block (paged) and the scratch cache, so the
        engine must be idle (enforced) — call before submitting work."""
        if self.has_work() or any(st is not None for st in self.state_by_slot):
            raise RuntimeError(
                "warmup() overwrites pool slot 0 and the scratch cache; it "
                "must run on an idle engine (no queued or in-flight "
                "requests, no occupied slots)")
        C = self.ecfg.prefill_chunk
        chunk = np.zeros((1, C), np.int32)
        # warmup traces every jitted entry exactly once per shape, so the
        # attention dispatch log captured around it is the engine's full
        # kernel-coverage map (fused vs reference per branch) — reset it
        # here so other engines' traces don't bleed in
        attention_dispatch.reset_dispatch_log()
        # two passes: the first compiles against the freshly-initialized
        # cache shardings, the second against jit's steady-state output
        # shardings (they can differ on multi-device meshes)
        for i in range(2):
            key = self._next_key(self._pf_key, 2 ** 31 - 1 - i)
            with self._ctx():
                _, self._scratch, _, _ = self._prefill_fn(
                    self.params, chunk, self._scratch, np.int32(0),
                    np.int32(C - 1), key, self._replica_ids)
                if self._paged:
                    # an all-null table row: every write lands in the
                    # null block's garbage
                    self.pool = self._write_fn(
                        self.pool, self._scratch,
                        np.full((self.blocks_per_slot,), NULL_BLOCK,
                                np.int32), np.int32(0), np.int32(C))
                else:
                    self.pool = self._write_fn(self.pool, self._scratch,
                                               np.int32(0))
                key = self._next_key(self._dec_key, 2 ** 31 - 1 - i)
                bt_args = ((np.full_like(self.block_table, NULL_BLOCK),)
                           if self._paged else ())
                # speculative: the decode entry is the [B, k+1] verify step
                warm_tok = (np.zeros((self.ecfg.max_slots,
                                      self.ecfg.speculative_k + 1), np.int32)
                            if self._spec else self.tok[:, None])
                nxt, self.pool, _ = self._decode_fn(
                    self.params, warm_tok, self.pool, self.pos,
                    *bt_args, key, self.active.copy(), self._replica_ids,
                    self._residency_ids)
                if self._paged and self._sharing:
                    # gather through an all-null row (masked to 0 tokens)
                    # and copy the null block onto itself: both compile
                    # against garbage nothing reads
                    self._scratch = self._gather_fn(
                        self.pool, self._scratch,
                        np.full((self.blocks_per_slot,), NULL_BLOCK,
                                np.int32), np.int32(0))
                    self.pool = self._copy_fn(self.pool,
                                              np.int32(NULL_BLOCK),
                                              np.int32(NULL_BLOCK))
            jax.block_until_ready(nxt)
        if self._rebalancer is not None:
            # compile the weight-swap gather too: replica slots are empty
            # (ids all -1) so the copied values are dead, and the real
            # swaps later must not show up as post-warmup compiles
            G, R = self._replica_ids.shape
            with self._ctx():
                self.params = self._swap_fn(
                    self.params, np.zeros((G * R,), np.int32))
        if self._residency is not None:
            # compile the staging scatter too: row-0 identity writes, so
            # real residency swaps never show up as post-warmup compiles
            self._dispatch_stage(np.zeros((0,), np.int32))
            self._residency_stages = 0
        # multi-device: the first call may trace twice while cache shardings
        # settle to jit's steady state; anything beyond this is a regression
        self._warm_counts = self._jit_counts()
        # snapshot the per-trace attention dispatch records: every branch
        # (prefill / prefill_continue / decode / verify) has now been traced
        # once per layer, so this is the engine's kernel-coverage map
        self._attn_dispatch = attention_dispatch.dispatch_log()

    def step(self, now: Optional[float] = None, *,
             wait_when_idle: bool = True) -> bool:
        """One scheduler tick: admit, prefill chunk(s), decode the batch.

        ``now`` lets a fleet router drive several replicas off one shared
        clock reading per tick (each engine-side ``clock.now()`` call
        advances a VirtualClock, so per-replica reads would skew time);
        ``wait_when_idle=False`` defers the idle wait to the router, which
        knows every replica's next arrival.  Returns whether any prefill
        or decode work ran.

        A step with work runs inside the ``serve.step`` span (its children
        name each part, module docstring of metrics.py); a tick with
        nothing to do waits inside ``serve.idle`` instead, so
        ``serve.step``'s count is the number of working steps."""
        if now is None:
            now = self.clock.now()
        did = False
        if self._work_ready(now):
            with self.metrics.span("serve.step", step_num=self._step_idx):
                with self.metrics.span("serve.admit"):
                    self._admit(now)
                did = self._prefill_work(now)
                did = self._decode_work(now) or did
                self._end_step()
        else:
            self._end_step()
        if not did and wait_when_idle:
            nxt = self.queue.next_arrival()
            if nxt is not None:
                with self.metrics.span("serve.idle"):
                    self.clock.wait(min(max(nxt - now, 0.0), 0.01))
        return did

    def _work_ready(self, now: float) -> bool:
        """Whether this tick has prefill or decode work, or a request
        admission would place (a preempted one, or an arrival due by
        ``now``)."""
        return bool(self.active.any() or self._pf is not None
                    or self._pf_queue
                    or (self.free_slots
                        and (self._resume
                             or self.queue.peek_ready(now) is not None)))

    def _end_step(self) -> None:
        self._step_idx += 1
        if self._rebalancer is not None \
                and self.ecfg.rebalance_interval > 0 \
                and self._step_idx % self.ecfg.rebalance_interval == 0 \
                and self._rebalancer.steps_observed > 0:
            with self.metrics.span("serve.rebalance"):
                self._rebalance_now()

    def run(self, requests: Sequence[Request] = (), *,
            max_steps: int = 1_000_000) -> Dict[str, Any]:
        """Drive the engine until all work drains.

        At the start of a fresh measurement window — nothing in flight and
        no metrics recorded yet — the clock is rebased to 0 so that arrival
        times (which start at 0) are measured from this call, not from
        engine construction: warmup/compile time and prior windows' wall
        time stay out of TTFT/e2e/queue_delay, and open-loop Poisson
        arrivals stay in the future rather than all already arrived.
        Requests submitted via ``submit()`` before this call don't block
        the rebase (their arrival times are window-relative); in-flight
        work or already-recorded metrics do, since their timestamps live on
        the current timebase — accumulating several ``run()`` calls into
        one window therefore keeps one continuous clock, and the caller
        owns any arrival-time offsets for the later batches.
        """
        if not self._in_flight() and self.metrics.empty:
            self.clock.reset()
        for r in requests:
            self.submit(r)
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"serve engine exceeded {max_steps} steps "
                                   f"with work remaining")
        return self.report()

    def _register_sections(self) -> None:
        """Engine-owned report sections, attached through the metrics
        section convention (metrics.py) — re-registered whenever the
        metrics object is replaced (reset_metrics)."""
        self.metrics.register_section("state_pool", self._state_pool_section)

    def _state_pool_section(self) -> Dict[str, Any]:
        """The sequence-state store's report section: pool kind plus
        store-specific occupancy/counters (``SequenceStateStore.stats``),
        with the scheduler-side pressure counters that give them meaning."""
        sec = self.kv.stats()
        sec["preemptions"] = self.metrics.preemptions
        return sec

    def report(self) -> Dict[str, Any]:
        if self._paged:
            self.metrics.evictions = self._alloc.evictions - self._evict0
            self.metrics.cow_copies = self._alloc.cow_copies - self._cow0
        if self._residency is not None:
            # window counters: lifetime minus the reset_metrics snapshot
            cur = self._residency.counters()
            base = self._res_base or {}
            win = {k: cur[k] - base.get(k, 0)
                   for k in cur if k != "hit_rate"}
            win["hit_rate"] = (win["hits"] / win["lookups"]
                               if win["lookups"] else None)
            self.metrics.residency = win
        rep = self.metrics.report()
        rep["engine"] = {
            "max_slots": self.ecfg.max_slots,
            "max_seq_len": self.ecfg.max_seq_len,
            "prefill_chunk": self.ecfg.prefill_chunk,
            "kv_capacity": self.kv_capacity,
            "steps": self._step_idx,
            "paged": self._paged,
            "role": self.role,
            "pallas_interpret": self.model.pallas_interpret,
        }
        if self._paged:
            rep["engine"]["kv_block_size"] = self.ecfg.kv_block_size
            rep["engine"]["num_kv_blocks"] = self._alloc.usable_blocks
            rep["engine"]["blocks_per_slot"] = self.blocks_per_slot
            rep["engine"]["prefix_sharing"] = self._sharing
            rep["engine"]["fused_paged_attention"] = \
                self.ecfg.fused_paged_attention
            rep["engine"]["speculative_k"] = self.ecfg.speculative_k
            if self._spec:
                rep["engine"]["speculative_policy"] = \
                    self.ecfg.speculative_policy
        if self.role != "unified" or self.handoffs_exported \
                or self.handoffs_imported:
            rep["engine"]["handoffs"] = {
                "exported": self.handoffs_exported,
                "imported": self.handoffs_imported,
                "bytes_out": self.handoff_bytes_out,
                "bytes_in": self.handoff_bytes_in,
                "pending": len(self._handoffs_out),
            }
        if self.cfg.is_moe:
            rep["engine"]["moe_policy"] = \
                self._moe_policy or self.cfg.moe.policy
            rep["engine"]["fused_moe_gmm"] = self.ecfg.fused_moe_gmm
            rep["engine"]["replica_slots"] = self.ecfg.replica_slots
            if self._rebalancer is not None:
                rep["engine"]["rebalance_interval"] = \
                    self.ecfg.rebalance_interval
                rep["engine"]["rebalances"] = self._rebalances
                rep["engine"]["replica_swaps"] = self._replica_swaps
                rep["engine"]["replica_ids"] = self._replica_ids.tolist()
                rep["engine"]["hot_experts"] = self._rebalancer.hot()
            rep["engine"]["resident_experts"] = self.ecfg.resident_experts
            if self._residency is not None:
                rep["engine"]["prefetch_policy"] = self.ecfg.prefetch_policy
                rep["engine"]["residency_stages"] = self._residency_stages
                rep["engine"]["residency_ids"] = \
                    self._residency_ids.tolist()
        snap = (self._attn_dispatch if self._attn_dispatch is not None
                else attention_dispatch.dispatch_log())
        if snap:
            # per-branch kernel coverage captured at warmup trace time: the
            # last record per branch wins (all traces of one branch agree)
            branches: Dict[str, Dict[str, Any]] = {}
            for d in snap:
                branches[d["branch"]] = {
                    k: v for k, v in d.items() if k != "branch"}
            rep["attention_dispatch"] = branches
            rep["attention_fallbacks"] = \
                attention_dispatch.fallback_counts(snap)
        rep["jit_entries"] = self._jit_counts()
        if self._warm_counts is not None:
            rep["recompiled_after_warmup"] = \
                rep["jit_entries"] != self._warm_counts
        return rep

    def _jit_counts(self) -> Dict[str, int]:
        counts = {**self.core.jit_counts(), **self.kv.jit_counts()}
        if self._rebalancer is not None:
            counts["replica_swap"] = self._swap_fn._cache_size()
        if self._residency is not None:
            counts["residency_stage"] = self._stage_fn._cache_size()
        return counts


# ----------------------------------------------------------------------
_EXPERT_LEAF_NAMES = ("w_in", "w_out", "w_gate")


def _collect_expert_leaves(params) -> List:
    """Every MoE expert weight leaf, in the deterministic order
    ``stage_weights`` visits them.  MoE parameter dicts are
    the ones carrying a ``router`` — dense MLP blocks reuse the
    ``w_in``/``w_out`` names but have no router."""
    out: List = []

    def walk(tree):
        if isinstance(tree, dict):
            if "router" in tree and "w_in" in tree:
                for name in _EXPERT_LEAF_NAMES:
                    if name in tree:
                        out.append(tree[name])
                return
            for v in tree.values():
                walk(v)
        elif isinstance(tree, (list, tuple)):
            for v in tree:
                walk(v)
    walk(params)
    return out


def stage_weights(params, rows, vals):
    """Scatter staged expert rows into every expert weight leaf.

    ``rows`` [n] indexes the rank-major stacked expert-row axis (the
    same layout ``swap_replicas`` gathers from); ``vals`` is the
    flat list of gathered host-tier slices in ``_collect_expert_leaves``
    order.  The writes are value-identity (the emulated host tier is a
    bit-exact copy of the device rows) — what's real is the dispatched
    copy whose bytes the residency cost model prices.  Shapes never
    change, so the jit cache holds one entry across all swaps."""
    it = iter(vals)

    def walk(tree):
        if isinstance(tree, dict):
            if "router" in tree and "w_in" in tree:
                out = dict(tree)
                for name in _EXPERT_LEAF_NAMES:
                    if name in tree:
                        out[name] = stage_expert_rows(tree[name], rows,
                                                      next(it))
                return out
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree
    return walk(params)


def swap_replicas(params, rows):
    """Gather expert weight rows into every replica leaf of the parameter
    tree.  ``rows`` [G*R] indexes the rank-major stacked expert-row axis
    (``row = host_rank * experts_per_rank + local_slot``, the layout
    ``init_moe_params`` documents); each MoE parameter dict carries both
    the ``w_*`` source rows and the ``w_rep_*`` destination slots, so the
    swap is a pure per-leaf ``jnp.take`` — shapes (and therefore the jit
    cache) never change.  Works on stacked ([n_steps, rows, d, f]) and
    plain ([rows, d, f]) leaves alike: the row axis is always third from
    the end."""
    def walk(tree):
        if isinstance(tree, dict):
            if "w_rep_in" in tree and "w_in" in tree:
                out = dict(tree)
                for rep_name, src_name in (("w_rep_in", "w_in"),
                                           ("w_rep_out", "w_out"),
                                           ("w_rep_gate", "w_gate")):
                    if rep_name in tree:
                        w = tree[src_name]
                        out[rep_name] = jnp.take(w, rows, axis=w.ndim - 3)
                return out
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree
    return walk(params)


def engine_config_for(cfg, *, max_slots: int, prompt_len: int,
                      max_new_tokens: int, prefill_chunk: int = 0,
                      eos_id: Optional[int] = None,
                      skew_seed: int = 0, role: str = "unified",
                      paged: bool = False,
                      kv_block_size: int = 16, num_kv_blocks: int = 0,
                      prefix_sharing: bool = False,
                      fused_paged_attention: bool = False,
                      fused_moe_gmm: bool = False,
                      speculative_k: int = 0,
                      speculative_policy: str = "ngram",
                      temperature: float = 0.0,
                      top_k: int = 0, top_p: float = 1.0,
                      moe_policy: Optional[str] = None,
                      rebalance_interval: int = 0,
                      replica_slots: int = 0,
                      resident_experts: int = 0,
                      prefetch_policy: str = "predictive") -> EngineConfig:
    """Derive serving shapes from a workload: pool length covers prompt +
    generation, the prefill chunk divides the (padded) prompt, and the
    padded prompt fits every layer's KV capacity (sliding-window layers
    clamp their *slab* cache to the window; the paged pool serves them as
    ring buffers instead, so only the chunk-vs-ring bound applies there).
    Model-independent legality lives in ``EngineConfig.validate()``,
    which the returned config has already passed."""
    chunk = prefill_chunk or min(max(prompt_len, 1), 32)
    window = cfg.sliding_window or 0
    pad = round_up(prompt_len, chunk)
    if window and not paged and pad > window:
        # slab prefill writes into the window-clamped scratch; the paged
        # pool has no such limit (windowed leaves wrap a ring of
        # round_up(window, kv_block_size) positions — see kvstore.py)
        raise ValueError(
            f"padded prompt {pad} exceeds the sliding window {window}; "
            f"slab chunked prefill must fit the window-clamped KV cache "
            f"(the paged ring buffer lifts this — pass paged=True)")
    if window and paged and chunk > round_up(window, kv_block_size):
        raise ValueError(
            f"prefill_chunk {chunk} exceeds the sliding-window ring of "
            f"{round_up(window, kv_block_size)} tokens; one chunk must "
            f"never self-overlap a ring slot — shrink prefill_chunk")
    max_seq = max(prompt_len + max_new_tokens, pad)
    return EngineConfig(
        max_slots=max_slots,
        max_seq_len=max_seq,
        prefill_chunk=chunk, eos_id=eos_id, skew_seed=skew_seed,
        role=role,
        paged=paged, kv_block_size=kv_block_size,
        num_kv_blocks=num_kv_blocks, prefix_sharing=prefix_sharing,
        fused_paged_attention=fused_paged_attention,
        fused_moe_gmm=fused_moe_gmm,
        speculative_k=speculative_k,
        speculative_policy=speculative_policy,
        temperature=temperature, top_k=top_k, top_p=top_p,
        moe_policy=moe_policy, rebalance_interval=rebalance_interval,
        replica_slots=replica_slots,
        resident_experts=resident_experts,
        prefetch_policy=prefetch_policy)
