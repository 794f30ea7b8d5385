"""build_model(): unified functional model API for every assigned arch.

Returned ``Model`` exposes:
  init(key)                         -> params
  train_loss(params, batch, key)    -> (loss, diags)
  prefill(params, batch)            -> (logits [B, Vp], caches, pos)
  prefill_chunk(params, tokens, caches, pos) -> chunked-prefill continuation
  decode_step(params, token, caches, pos) -> (logits, caches)
      (pos may be a per-sequence [B] vector — slotted continuous batching;
       block_table= switches to the paged physical pool)
  input_specs(shape_kind)           -> pytree of ShapeDtypeStruct (dry-run)
  init_cache(batch, s_max)          -> decode caches (the serve slot pool)
  init_paged_cache(num_blocks, block_size) -> paged physical KV pool

The modality frontends are stubs per the assignment: whisper consumes
precomputed frame embeddings [B, 1500, d]; pixtral consumes precomputed patch
embeddings [B, n_patch, d] prepended to the token sequence.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.core.moe_layer import MoEBlockSpec
from repro.kernels import pallas_interpret
from repro.models import attention as A
from repro.models import transformer as T
from repro.models.layers import (init_embedding, init_mlp, init_norm, mlp,
                                 norm, sinusoidal_positions)
from repro.models.losses import chunked_softmax_xent, logits_head


@dataclass(frozen=True)
class MeshShape:
    """Static mesh info the model needs (sizes + axis names)."""
    axes: Tuple[Tuple[str, int], ...] = (("data", 1), ("model", 1))

    @property
    def sizes(self) -> Dict[str, int]:
        return dict(self.axes)

    @property
    def ep_degree(self) -> int:
        return self.sizes.get("model", 1)

    def batch_axes(self, global_batch: int) -> Tuple[str, ...]:
        """Largest prefix of (pod, data) that divides the batch."""
        cand = [a for a in ("pod", "data") if a in self.sizes]
        chosen: Tuple[str, ...] = ()
        prod = 1
        for a in cand:
            if global_batch % (prod * self.sizes[a]) == 0:
                chosen += (a,)
                prod *= self.sizes[a]
        return chosen

    def batch_shards(self, global_batch: int) -> int:
        prod = 1
        for a in self.batch_axes(global_batch):
            prod *= self.sizes[a]
        return prod


@dataclass
class Model:
    cfg: ModelConfig
    pcfg: ParallelConfig
    mesh_shape: MeshShape
    batch: int
    seq_len: int
    init: Callable[..., Any] = None
    train_loss: Callable[..., Any] = None
    prefill: Callable[..., Any] = None
    prefill_chunk: Callable[..., Any] = None
    decode_step: Callable[..., Any] = None
    init_cache: Callable[..., Any] = None
    init_paged_cache: Callable[..., Any] = None
    input_specs: Callable[..., Any] = None
    moe_spec: Optional[MoEBlockSpec] = None
    pallas_interpret: bool = False


def _dtype_of(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


def build_model(cfg: ModelConfig, pcfg: ParallelConfig, *, batch: int,
                seq_len: int, mesh_shape: MeshShape = MeshShape(),
                mesh: Optional[jax.sharding.Mesh] = None) -> Model:
    dtype = _dtype_of(cfg)
    interpret = pallas_interpret()
    Vp = cfg.padded_vocab
    d = cfg.d_model
    b_shards = mesh_shape.batch_shards(batch)
    b_local = batch // b_shards
    batch_axes = mesh_shape.batch_axes(batch)

    # Activation batch constraint: pins [B, ...] activations to the batch
    # axes so XLA resolves FSDP conflicts by all-gathering weights (the
    # intended ZeRO-3 dataflow) instead of replicating activations.
    ep = mesh_shape.ep_degree
    # SP policy (EXPERIMENTS.md §Perf 1.2): in train mode, remat re-pays
    # every SP->TP all-gather, so SP is only worth it when attention heads
    # cannot TP-shard (then seq is the only parallelism for attention math).
    sp_train = (cfg.num_heads % ep != 0) if cfg.num_heads else False

    def constrain(x, mode: str = "none"):
        if mesh is None or "data" not in mesh.axis_names:
            return x
        # sequence parallelism: the residual stream is sharded over 'model'
        # between attention/MoE blocks (norm/elementwise work and workspace
        # divide by ep); XLA inserts the all-gather where TP weights need the
        # full sequence.
        seq = mode in ("prefill", "encode") or (mode == "train" and sp_train)
        seq_spec = "model" if (seq and x.ndim == 3 and ep > 1
                               and x.shape[1] % ep == 0) else None
        spec = jax.sharding.PartitionSpec(
            *([batch_axes if batch_axes else None, seq_spec]
              + [None] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))

    moe_spec = None
    if cfg.is_moe:
        moe_spec = MoEBlockSpec(
            moe=cfg.moe, d_model=d, ep_axis="model", batch_axes=batch_axes,
            ep_degree=mesh_shape.ep_degree,
            # per-STEP tokens: microbatching divides the batch per grad step
            tokens_local=max(b_local // max(pcfg.microbatch, 1), 1) * seq_len,
            act="silu" if cfg.act == "swiglu" else "gelu",
            cf_pair=pcfg.moe_cf_pair,
            block_m=pcfg.moe_block_m,
            use_pallas=pcfg.use_pallas,
            interpret=interpret,
            tp_mode=cfg.moe.num_experts < mesh_shape.ep_degree,
            seq_sharded=(seq_len % mesh_shape.ep_degree == 0
                         and mesh_shape.ep_degree > 1))

    # MoE decode uses a separate spec sized for one token per sequence.
    # Foreign slots at decode depend on the policy:
    #   * even_split schedules units for EVERY expert to EVERY rank, so each
    #     rank needs a group per non-local expert (with K = 0 those units
    #     have nowhere to land and are counted as drops);
    #   * harmoeny keeps the configured K so serving-time redistribution can
    #     move hot-expert load to non-host ranks (paper Alg. 2 at decode);
    #   * round_robin / static_opt never leave the initial placement.
    def _decode_foreign_slots(policy: str) -> int:
        if moe_spec.tp_mode:
            return 0
        topo = moe_spec.topo
        if policy == "even_split":
            return topo.padded_experts - topo.experts_per_rank
        if policy == "harmoeny":
            return cfg.moe.num_foreign_slots
        return 0

    moe_spec_decode = None
    if cfg.is_moe:
        moe_spec_decode = dataclasses.replace(
            moe_spec,
            tokens_local=b_local,
            seq_sharded=False,
            block_m=128,   # decode batches are tiny; big tiles = pure padding
            moe=dataclasses.replace(
                cfg.moe,
                num_foreign_slots=_decode_foreign_slots(cfg.moe.policy)))

    is_encdec = cfg.is_encoder_decoder
    n_prefix = cfg.num_prefix_embeddings

    # ------------------------------------------------------------------
    def init(key: jax.Array):
        ks = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": init_embedding(ks[0], Vp, d, dtype),
            "final_norm": init_norm(d, cfg.norm),
            "stack": (T.init_hybrid(ks[1], cfg, dtype)
                      if cfg.family == "hybrid"
                      else T.init_stack(ks[1], cfg, moe_spec, dtype)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_embedding(ks[2], Vp, d, dtype)
        if is_encdec:
            enc_cfg = dataclasses.replace(
                cfg, num_layers=cfg.encoder_layers, is_encoder_decoder=False)
            params["encoder"] = {
                "stack": T.init_stack(ks[3], enc_cfg, None, dtype),
                "final_norm": init_norm(d, cfg.norm),
            }
            params["cross"] = _init_cross_layers(ks[4], cfg, dtype)
        return params

    # ------------------------------------------------------------------
    def _backbone(params, h, *, mode, cache=None, cache_len=None,
                  q_offset=0, spec=None, skew_key=None, enc_out=None,
                  continue_prefill=False, valid_mask=None,
                  block_table=None, block_size=0, pcfg_run=None,
                  moe_replica_ids=None, moe_residency_ids=None,
                  moe_layer_diags=False):
        pc = pcfg_run if pcfg_run is not None else pcfg
        h = constrain(h, mode)
        if block_table is not None and (cfg.family == "hybrid" or is_encdec):
            raise NotImplementedError(
                "paged KV decode supports plain decoder stacks only")
        if cfg.family == "hybrid":
            h, new_cache, diags = T.run_hybrid(
                h, params["stack"], cfg, pc, mode=mode, cache=cache,
                cache_len=cache_len, q_offset=q_offset, mesh=mesh,
                constrain=constrain, continue_prefill=continue_prefill,
                valid_mask=valid_mask)
        elif is_encdec:
            h, new_cache, diags = _run_encdec_decoder(
                h, params, cfg, pc, mode=mode, cache=cache,
                cache_len=cache_len, q_offset=q_offset, enc_out=enc_out,
                constrain=constrain)
        else:
            h, new_cache, diags = T.run_stack(
                h, params["stack"], cfg, pc, mode=mode, cache=cache,
                cache_len=cache_len, q_offset=q_offset,
                moe_spec=spec, mesh=mesh, skew_key=skew_key,
                constrain=constrain, continue_prefill=continue_prefill,
                valid_mask=valid_mask, block_table=block_table,
                block_size=block_size, moe_replica_ids=moe_replica_ids,
                moe_residency_ids=moe_residency_ids,
                moe_layer_diags=moe_layer_diags, interpret=interpret)
        with jax.named_scope("lm_head"):
            h = norm(h, params["final_norm"], cfg.norm)
        return h, new_cache, diags

    def _encode(params, frames):
        """Whisper encoder over stubbed frame embeddings [B, S_enc, d]."""
        enc_cfg = dataclasses.replace(
            cfg, num_layers=cfg.encoder_layers, is_encoder_decoder=False)
        pos = sinusoidal_positions(frames.shape[1], d).astype(frames.dtype)
        h = frames + pos[None]
        h, _, _ = T.run_stack(h, params["encoder"]["stack"], enc_cfg,
                              dataclasses.replace(pcfg),
                              mode="encode", moe_spec=None, mesh=mesh,
                              causal=False, interpret=interpret)
        return norm(h, params["encoder"]["final_norm"], cfg.norm)

    def _embed_tokens(params, tokens, offset=0):
        h = params["embed"][tokens]
        if cfg.rope_theta <= 0 and cfg.ssm is None:  # absolute pos (whisper)
            table = sinusoidal_positions(seq_len + 65, d).astype(h.dtype)
            S = tokens.shape[1]
            off = jnp.asarray(offset, jnp.int32)
            if off.ndim:  # per-sequence offsets (slotted decode)
                h = h + table[off[:, None] + jnp.arange(S)[None]]
            else:
                pos_emb = jax.lax.dynamic_slice_in_dim(table, off, S, axis=0)
                h = h + pos_emb[None]
        if cfg.name.startswith("gemma"):
            h = h * jnp.asarray(d ** 0.5, h.dtype)
        return h

    def _vocab_w(params):
        return params["embed"] if cfg.tie_embeddings else params["lm_head"]

    # ------------------------------------------------------------------
    def train_loss(params, batch_in, skew_key=None):
        tokens, labels = batch_in["tokens"], batch_in["labels"]
        h = _embed_tokens(params, tokens)
        enc_out = None
        if is_encdec:
            enc_out = _encode(params, batch_in["frames"])
        if n_prefix:
            h = jnp.concatenate(
                [batch_in["patches"].astype(h.dtype), h], axis=1)
        h, _, diags = _backbone(params, h, mode="train", spec=moe_spec,
                                skew_key=skew_key, enc_out=enc_out)
        if n_prefix:
            h = h[:, n_prefix:]
        loss = chunked_softmax_xent(
            h, _vocab_w(params), labels, real_vocab=cfg.vocab_size,
            chunk=pcfg.loss_chunk, softcap=cfg.final_logit_softcap)
        if "aux_loss" in diags:
            loss = loss + 0.01 * diags["aux_loss"]
        return loss, diags

    # ------------------------------------------------------------------
    def init_cache(b: int, s_max: int, clamp_window: bool = True):
        cache: Dict[str, Any] = {}
        if cfg.family == "hybrid":
            cache["stack"] = T.init_hybrid_cache(cfg, b, s_max, dtype)
        else:
            cache["stack"] = T.init_stack_cache(cfg, b, s_max, dtype,
                                                clamp_window)
        if is_encdec:
            # encoder K/V per decoder layer; contents filled by prefill
            hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            z = jnp.zeros((cfg.num_layers, b, cfg.encoder_seq_len, hkv, hd),
                          dtype)
            cache["cross"] = A.AttnCache(z, z)
        return cache

    def init_paged_cache(num_blocks: int, block_size: int,
                         s_ref: Optional[int] = None, seq_axes: Any = None,
                         clamp_window: bool = True):
        """Paged variant of ``init_cache``: a batch-1 *physical* block pool
        of ``num_blocks * block_size`` KV positions per leaf, addressed
        through block tables in ``decode_step``.  ``s_ref`` (default the
        model's ``seq_len``) is the logical length the layout is validated
        at — every leaf must expose a full, unclamped KV axis there.
        ``seq_axes`` skips re-discovery when the caller (the serve engine)
        already holds the per-leaf KV-axis pytree.  ``clamp_window=False``
        builds the pool over unclamped (full-length) leaves — the serve
        engine's sliding-window ring mode."""
        from repro.serve.paging import make_paged_pool
        from repro.serve.slots import discover_seq_axes
        s = s_ref or seq_len

        def _ic(b, s_max):
            return init_cache(b, s_max, clamp_window)
        if seq_axes is None:
            seq_axes = discover_seq_axes(_ic, s)
        return make_paged_pool(_ic, s, seq_axes, num_blocks, block_size)

    def prefill(params, batch_in, s_max: Optional[int] = None):
        tokens = batch_in["tokens"]
        B, S = tokens.shape
        s_max = s_max or (S + 64)
        h = _embed_tokens(params, tokens)
        enc_out = None
        if is_encdec:
            enc_out = _encode(params, batch_in["frames"])
        if n_prefix:
            h = jnp.concatenate(
                [batch_in["patches"].astype(h.dtype), h], axis=1)
        cache = init_cache(B, s_max)
        pos = jnp.int32(h.shape[1])
        h, new_cache, diags = _backbone(
            params, h, mode="prefill", cache=cache["stack"],
            cache_len=pos, spec=moe_spec, enc_out=enc_out,
            skew_key=batch_in.get("skew_key"))
        out_cache = {"stack": new_cache}
        if is_encdec:
            out_cache["cross"] = _cross_kv(params, enc_out, cfg)
        logits = logits_head(h[:, -1], _vocab_w(params),
                             real_vocab=cfg.vocab_size,
                             softcap=cfg.final_logit_softcap)
        return logits, out_cache, pos, diags

    def prefill_chunk(params, tokens, caches, pos, last_index=None,
                      skew_key=None, moe_replica_ids=None,
                      fused_attention=None, fused_moe=None):
        """Chunked-prefill continuation for the serving engine.

        tokens [Bc, C] is the next prompt chunk, appended to ``caches`` at
        position ``pos`` (scalar — all Bc rows share the offset). Returns
        (logits, caches, pos + C, diags) where logits are taken at
        ``last_index`` within the chunk (default C - 1); pad the final chunk
        to C and pass the true last-token index. The caller owns position
        bookkeeping for partially-filled final chunks.
        ``fused_attention`` (static) overrides ``pcfg.use_pallas`` for this
        chunk's attention blocks — the q-tiled paged kernel then runs the
        whole chunk over the slab scratch (strict: an inapplicable fused
        path raises instead of silently falling back); ``fused_moe``
        (static) overrides the MoE spec's ``use_pallas`` so the chunk's
        Bc * C expert tokens go through the grouped-GEMM Pallas kernel —
        both wired by the serve engine without rebuilding the model.
        """
        Bc, C = tokens.shape
        spec = moe_spec
        if spec is not None:
            spec = dataclasses.replace(
                spec, tokens_local=Bc * C,
                seq_sharded=(C % mesh_shape.ep_degree == 0
                             and mesh_shape.ep_degree > 1))
            if fused_moe is not None:
                spec = dataclasses.replace(spec, use_pallas=bool(fused_moe))
        h = _embed_tokens(params, tokens, offset=pos)
        new_pos = pos + C
        # pad tokens beyond last_index are dead: keep them out of MoE
        # routing/capacity (their K/V writes are masked by cache_len anyway)
        # and out of SSM recurrent-state updates (state has no cache_len to
        # mask behind — pad tokens would fold in permanently)
        vmask = None
        if (cfg.is_moe or cfg.ssm is not None) and last_index is not None:
            li = jnp.asarray(last_index, jnp.int32)
            vmask = jnp.arange(C)[None, :] <= (li[..., None] if li.ndim
                                               else li)
            vmask = jnp.broadcast_to(vmask, (Bc, C))
        pcfg_step = None
        if fused_attention is not None:
            pcfg_step = dataclasses.replace(
                pcfg, use_pallas=bool(fused_attention),
                pallas_strict=bool(fused_attention))
        h, new_stack, diags = _backbone(
            params, h, mode="prefill", cache=caches["stack"],
            cache_len=new_pos, q_offset=pos, spec=spec, skew_key=skew_key,
            continue_prefill=True, valid_mask=vmask,
            pcfg_run=pcfg_step, moe_replica_ids=moe_replica_ids)
        idx = jnp.asarray(C - 1 if last_index is None else last_index,
                          jnp.int32)
        if idx.ndim:
            hl = h[jnp.arange(Bc), idx]
        else:
            hl = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        with jax.named_scope("lm_head"):
            logits = logits_head(hl, _vocab_w(params),
                                 real_vocab=cfg.vocab_size,
                                 softcap=cfg.final_logit_softcap)
        out = dict(caches)
        out["stack"] = new_stack
        return logits, out, new_pos, diags

    def decode_step(params, token, caches, pos, skew_key=None,
                    active_mask=None, block_table=None, block_size=0,
                    fused_attention=None, fused_moe=None, moe_policy=None,
                    moe_replica_ids=None, moe_residency_ids=None,
                    moe_layer_diags=False):
        """token [B, S] int32 (S = 1 is plain decode; S = k + 1 is a
        speculative-verify window, paged only); pos = current length BEFORE
        appending the window (scalar, or a per-sequence [B] vector for
        slotted batches) — window position i lands at ``pos + i``.
        ``active_mask`` [B] bool excludes vacated slots' garbage tokens from
        MoE routing and capacity (their logits are garbage either way).
        ``block_table`` [B, max_blocks_per_slot] switches the cache to a
        paged physical pool (``caches`` from ``init_paged_cache``): K/V
        writes and attention gathers go through each row's block chain,
        causal within the window when S > 1.
        ``fused_attention`` (static, paged mode only) overrides
        ``pcfg.use_pallas`` for this step's attention blocks (strict: an
        inapplicable fused path raises instead of silently falling back)
        and ``fused_moe`` (static) overrides the MoE spec's ``use_pallas``
        (grouped-GEMM Pallas expert FFN for the B or B * S routed tokens),
        letting the serve engine opt into the fused kernels without
        rebuilding the model.
        ``moe_policy`` (static) overrides the decode-path scheduling policy
        for this step; ``moe_replica_ids`` [G, R] (traced, -1 = empty) names
        the experts occupying the replica slots — both wired by the serve
        engine (EngineConfig.moe_policy / serve/rebalance.py).
        ``moe_residency_ids`` [G, W] (traced, -1 = pad) is the tiered
        residency table (serve/residency.py): each rank's HBM-resident
        working set, demoting swapped-out experts in the schedule;
        ``moe_layer_diags`` (static) emits the per-layer
        ``expert_load_layers`` diagnostic the residency predictor consumes.

        Returns logits [B, Vp] at the last position when S == 1, else
        [B, S, Vp] at every window position (the verify step scores all
        drafted continuations in one pass)."""
        B, S = token.shape
        if S > 1 and block_table is None:
            raise NotImplementedError(
                "multi-token decode (speculative verify) goes through the "
                "paged pool: pass block_table/block_size")
        h = _embed_tokens(params, token, offset=pos)
        new_pos = pos + S
        vmask = None
        if cfg.is_moe and active_mask is not None:
            am = jnp.asarray(active_mask).reshape(-1, 1)       # [B, 1]
            vmask = jnp.broadcast_to(am, (B, S)) if S > 1 else am
        spec_dec = moe_spec_decode
        if spec_dec is not None and moe_policy is not None \
                and moe_policy != spec_dec.moe.policy:
            spec_dec = dataclasses.replace(
                spec_dec, moe=dataclasses.replace(
                    spec_dec.moe, policy=moe_policy,
                    num_foreign_slots=_decode_foreign_slots(moe_policy)))
        if spec_dec is not None and S > 1:
            # the verify window routes B * S tokens per step, not B
            spec_dec = dataclasses.replace(
                spec_dec, tokens_local=spec_dec.tokens_local * S)
        if spec_dec is not None and fused_moe is not None:
            spec_dec = dataclasses.replace(
                spec_dec, use_pallas=bool(fused_moe))
        pcfg_step = None
        if fused_attention is not None and block_table is not None:
            pcfg_step = dataclasses.replace(
                pcfg, use_pallas=bool(fused_attention),
                pallas_strict=bool(fused_attention))
        h, new_stack, diags = _backbone(
            params, h, mode="decode", cache=caches["stack"],
            cache_len=new_pos, q_offset=pos, spec=spec_dec,
            skew_key=skew_key,
            enc_out=caches.get("cross"), valid_mask=vmask,
            block_table=block_table, block_size=block_size,
            pcfg_run=pcfg_step, moe_replica_ids=moe_replica_ids,
            moe_residency_ids=moe_residency_ids,
            moe_layer_diags=moe_layer_diags)
        with jax.named_scope("lm_head"):
            if S == 1:
                logits = logits_head(h[:, -1], _vocab_w(params),
                                     real_vocab=cfg.vocab_size,
                                     softcap=cfg.final_logit_softcap)
            else:
                logits = logits_head(h.reshape(B * S, -1),
                                     _vocab_w(params),
                                     real_vocab=cfg.vocab_size,
                                     softcap=cfg.final_logit_softcap)
                logits = logits.reshape(B, S, -1)
        out = dict(caches)
        out["stack"] = new_stack
        return logits, out, new_pos, diags

    # ------------------------------------------------------------------
    def input_specs(kind: str):
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
        specs: Dict[str, Any] = {"tokens": tok}
        if kind == "train":
            specs["labels"] = tok
        if is_encdec:
            specs["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.encoder_seq_len, d), dtype)
        if n_prefix:
            specs["patches"] = jax.ShapeDtypeStruct((batch, n_prefix, d), dtype)
        if kind == "decode":
            specs["tokens"] = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        return specs

    return Model(cfg=cfg, pcfg=pcfg, mesh_shape=mesh_shape, batch=batch,
                 seq_len=seq_len, init=init, train_loss=train_loss,
                 prefill=prefill, prefill_chunk=prefill_chunk,
                 decode_step=decode_step,
                 init_cache=init_cache, init_paged_cache=init_paged_cache,
                 input_specs=input_specs,
                 moe_spec=moe_spec, pallas_interpret=interpret)


# ----------------------------------------------------------------------
# Whisper-style cross-attention decoder
# ----------------------------------------------------------------------
def _init_cross_layers(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, cfg.num_layers)

    def one(k):
        k1, _ = jax.random.split(k)
        return {"norm": init_norm(cfg.d_model, cfg.norm),
                "attn": A.init_attention(k1, cfg, dtype)}
    return jax.vmap(one)(ks)


def _cross_kv(params, enc_out, cfg: ModelConfig):
    """Precompute encoder K/V for every decoder layer at prefill."""
    def one(p_cross):
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p_cross["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p_cross["attn"]["wv"])
        return A.AttnCache(k, v)
    return jax.vmap(one)(params["cross"])


def _run_encdec_decoder(h, params, cfg: ModelConfig, pcfg, *, mode, cache,
                        cache_len, q_offset, enc_out, constrain=lambda x, seq=False: x):
    """Decoder stack with interleaved cross-attention (scan over layers)."""
    n = cfg.num_layers
    blocks = params["stack"]["blocks"]
    cross = params["cross"]

    # cross K/V: computed from enc_out at train/prefill; at decode enc_out is
    # the precomputed AttnCache pytree (stacked per layer)
    if mode == "decode":
        cross_kv = enc_out
    else:
        cross_kv = _cross_kv(params, enc_out, cfg)

    def step(carry, inp):
        x = carry
        p_step, c_step, p_cross, ckv = inp
        p = p_step["sub0"]
        c = c_step["sub0"] if c_step is not None else None
        x = constrain(x, mode)
        # self-attention
        hh = norm(x, p["norm1"], cfg.norm)
        hh, nc = A.attention_block(hh, p["attn"], cfg, causal=True,
                                   q_offset=q_offset, cache=c,
                                   cache_len=cache_len,
                                   attn_chunk=pcfg.attn_chunk)
        x = x + hh
        # cross-attention against fixed encoder K/V
        hh = norm(x, p_cross["norm"], cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", hh, p_cross["attn"]["wq"])
        out = A.chunked_attention(q, ckv.k, ckv.v, causal=False,
                                  chunk=pcfg.attn_chunk)
        x = x + jnp.einsum("bshk,hkd->bsd", out, p_cross["attn"]["wo"])
        # mlp
        x = x + mlp(norm(x, p["norm2"], cfg.norm), p["mlp"], cfg.act)
        return x, (nc,)

    body = step
    if pcfg.remat != "none" and mode == "train":
        body = jax.checkpoint(step)

    c_blocks = cache["blocks"] if cache is not None else None
    if c_blocks is None:
        def wrapped(carry, inp):
            p_step, p_cross, ckv = inp
            x, (nc,) = body(carry, (p_step, None, p_cross, ckv))
            return x, nc
        x, _ = jax.lax.scan(wrapped, h, (blocks, cross, cross_kv))
        return x, None, {}

    def wrapped2(carry, inp):
        x, (nc,) = body(carry, inp)
        return x, nc
    x, ncs = jax.lax.scan(wrapped2, h, (blocks, c_blocks, cross, cross_kv))
    return x, {"blocks": {"sub0": ncs}}, {}
