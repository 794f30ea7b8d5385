"""Attention: GQA/MHA with RoPE, sliding window, logit softcap.

Prefill/train path: chunked ("flash-style") attention — a lax.scan over KV
blocks with an online softmax, so the [S, S] score matrix is never
materialized (memory-roofline honest at 32k). The Pallas flash kernel
(kernels/flash_attention) is the TPU-target equivalent; this is its oracle
twin used for dry-runs and CPU tests.

Decode path: one query position against a static-size KV cache with position
masking. Distributed long-context decode works by *sharding constraint*: the
cache's sequence dim carries P('data') and XLA partitions the reduction
(distributed softmax) — no shard_map needed (DESIGN.md §3 SP).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope

_NEG_INF = -1e30


class FusedPathUnavailable(NotImplementedError):
    """``use_pallas`` was required (strict) but no fused kernel applies."""


# Trace-time dispatch record: ``attention_block`` runs under jit, so each
# record is appended exactly once per traced call site (one trace covers
# every execution of that entry) — the log is therefore a faithful
# kernel-coverage map of which branches dispatched the fused Pallas path
# vs the reference gather, and *why* a requested fused path fell back.
# The serve engine resets it before warmup and snapshots it after.
_dispatch_log: list = []
_DISPATCH_LOG_CAP = 4096


def reset_dispatch_log() -> None:
    _dispatch_log.clear()


def dispatch_log() -> list:
    return list(_dispatch_log)


def fallback_counts(log: Optional[list] = None) -> Dict[str, int]:
    """Branches where ``use_pallas`` was requested but the reference path
    ran anyway (the previously *silent* fallbacks), keyed by branch.

    Counts over the live module log by default; pass a snapshot from
    ``dispatch_log()`` to count over a captured window instead."""
    out: Dict[str, int] = {}
    for rec in (_dispatch_log if log is None else log):
        if rec["requested"] and not rec["fused"]:
            out[rec["branch"]] = out.get(rec["branch"], 0) + 1
    return out


def _record_dispatch(branch: str, *, fused: bool, requested: bool,
                     strict: bool = False, reason: str = "",
                     **kernel) -> None:
    """``kernel`` names the fused kernel a branch took and its static
    tiling (e.g. ``kernel="paged_decode", pages_per_step=8``)."""
    if len(_dispatch_log) < _DISPATCH_LOG_CAP:
        _dispatch_log.append({"branch": branch, "fused": bool(fused),
                              "requested": bool(requested),
                              "reason": reason, **kernel})
    if requested and not fused and strict:
        raise FusedPathUnavailable(
            f"attention_block: use_pallas was explicitly required but the "
            f"fused path cannot apply on branch {branch!r}: {reason}")


def init_attention(key: jax.Array, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    s = (2.0 / d) ** 0.5
    return {
        "wq": (jax.random.normal(ks[0], (d, H, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, Hkv, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, Hkv, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (H, hd, d)) * s).astype(dtype),
    }


def _softcap(scores: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0:
        return scores
    return cap * jnp.tanh(scores / cap)


def _repeat_kv(k: jnp.ndarray, rep: int) -> jnp.ndarray:
    """GQA: repeat kv heads to the q-head count. An explicit repeat keeps the
    q-head sharding intact (a [Hkv, rep] reshape would split across the
    sharded head dim and force XLA to all-gather q — observed 8.6 GB/chunk
    score blowups on mixtral prefill before this change)."""
    return jnp.repeat(k, rep, axis=2) if rep > 1 else k


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, window: int = 0, softcap: float = 0.0,
                      chunk: int = 1024,
                      q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax attention.

    q: [B, Sq, H, hd]; k/v: [B, Sk, Hkv, hd] with H = Hkv * rep.
    Scans KV in blocks of ``chunk``; running (max, denom, acc) carried.
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    Skp = n_chunks * chunk
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, 0), (0, 0)))
    k = _repeat_kv(k, rep)
    v = _repeat_kv(v, rep)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale                     # [B, Sq, H, hd]
    q_pos = q_offset + jnp.arange(Sq)

    kc = k.reshape(B, n_chunks, chunk, H, hd)
    vc = v.reshape(B, n_chunks, chunk, H, hd)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, c0 = inp
        kv_pos = c0 + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        s = _softcap(s, softcap)
        mask = kv_pos[None, :] < Sk
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & (q_pos[:, None] - kv_pos[None, :] < window)
        s = jnp.where(mask[None, None, :, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    c0s = jnp.arange(n_chunks) * chunk
    # checkpoint the chunk body: backward recomputes the [Sq, chunk] score
    # block instead of storing it per chunk (flash-style backward memory)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), c0s))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def full_attention_ref(q, k, v, *, causal, window=0, softcap=0.0, q_offset=0):
    """Naive O(S^2)-memory oracle for tests."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    k = _repeat_kv(k, rep)
    v = _repeat_kv(v, rep)
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    s = _softcap(s, softcap)
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len: jnp.ndarray, *, window: int = 0,
                     softcap: float = 0.0) -> jnp.ndarray:
    """q: [B, 1, H, hd]; caches: [B, S_max, Hkv, hd]; cache_len: scalar int or
    per-sequence [B] vector (entries < cache_len are valid; the new token's
    K/V must already be written at cache_len - 1). The vector form is what
    lets a continuous-batching slot pool hold sequences of different lengths
    in one static-shape decode step."""
    B, _, H, hd = q.shape
    S_max, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    kr = _repeat_kv(k_cache, rep)
    vr = _repeat_kv(v_cache, rep)
    qf = (q.astype(jnp.float32) * hd ** -0.5)[:, 0]        # [B, H, hd]
    s = jnp.einsum("bhd,bkhd->bhk", qf, kr.astype(jnp.float32))
    s = _softcap(s, softcap)
    kv_pos = jnp.arange(S_max)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 0:
        cl = cl[None]                                      # broadcast over B
    mask = kv_pos[None, :] < cl[:, None]                   # [B|1, S_max]
    if window > 0:
        mask = mask & (kv_pos[None, :] >= cl[:, None] - window)
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, vr.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           cache_len: jnp.ndarray, *, block_size: int,
                           softcap: float = 0.0) -> jnp.ndarray:
    """Decode attention through a paged KV pool.

    q: [B, S, H, hd] with S >= 1 query positions (S = 1 is plain decode;
    S = k + 1 is a speculative-verify window); k_pool/v_pool:
    [1, P, Hkv, hd] *physical* pools with P = num_blocks * block_size;
    block_table: [B, max_blocks_per_slot] int32 mapping each row's logical
    block j to a physical block id; cache_len: per-row [B] valid lengths
    INCLUDING the S window positions (query i sits at absolute position
    ``cache_len - S + i``).  Each row's logical K/V view is gathered
    through its table row (unallocated entries point at the null block,
    whose garbage the validity mask hides), then reduced by the same
    masked-softmax decode attention the slab pool uses — causal within
    the window when S > 1.
    """
    n_logical = block_table.shape[1]
    log = jnp.arange(n_logical * block_size)
    phys = block_table[:, log // block_size] * block_size \
        + log % block_size                                  # [B, L_max]
    k = k_pool[0, phys]                                     # [B, L_max, Hkv, hd]
    v = v_pool[0, phys]
    S = q.shape[1]
    if S == 1:
        return decode_attention(q, k, v, cache_len, softcap=softcap)
    # multi-query verify window: per-query causal mask inside the window
    B, _, H, hd = q.shape
    rep = H // k.shape[2]
    kr = _repeat_kv(k, rep)
    vr = _repeat_kv(v, rep)
    qf = q.astype(jnp.float32) * hd ** -0.5                 # [B, S, H, hd]
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kr.astype(jnp.float32))
    s = _softcap(s, softcap)
    cl = jnp.broadcast_to(jnp.asarray(cache_len).reshape(-1), (B,))
    q_pos = cl[:, None] - S + jnp.arange(S)[None]           # [B, S]
    mask = log[None, None, :] <= q_pos[:, :, None]          # [B, S, L_max]
    s = jnp.where(mask[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, vr.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def paged_ring_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                                v_pool: jnp.ndarray,
                                block_table: jnp.ndarray,
                                cache_len: jnp.ndarray, *, window: int,
                                block_size: int,
                                softcap: float = 0.0) -> jnp.ndarray:
    """Single-query decode through a paged pool whose logical positions wrap
    a ring of M = round_up(window, block_size) positions.

    Absolute position p lives at ring slot p % M (block ``(p % M) //
    block_size`` of the row's chain), so a chain of M/block_size blocks
    serves an unbounded logical length: the decode write at
    ``(cache_len - 1) % M`` overwrites the age-M position, which the window
    (window <= M) has already expired.  Ring slot r holds absolute position
    ``cache_len - 1 - ((cache_len - 1 - r) mod M)`` — valid iff that age is
    < min(window, cache_len).  K is stored post-RoPE at its absolute
    position, exactly as in the slab ring, so scores stay position-exact
    across wraps.
    """
    B = q.shape[0]
    M = -(-window // block_size) * block_size
    r = jnp.arange(M)
    phys = block_table[:, r // block_size] * block_size \
        + r % block_size                                    # [B, M]
    k = k_pool[0, phys]                                     # [B, M, Hkv, hd]
    v = v_pool[0, phys]
    cl = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    age = jnp.mod(cl[:, None] - 1 - r[None, :], M)          # [B, M]
    valid = (age < window) & (age < cl[:, None])
    H, hd = q.shape[2], q.shape[3]
    rep = H // k.shape[2]
    kr = _repeat_kv(k, rep)
    vr = _repeat_kv(v, rep)
    qf = (q.astype(jnp.float32) * hd ** -0.5)[:, 0]         # [B, H, hd]
    s = jnp.einsum("bhd,bkhd->bhk", qf, kr.astype(jnp.float32))
    s = _softcap(s, softcap)
    s = jnp.where(valid[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, vr.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


class AttnCache(NamedTuple):
    k: jnp.ndarray   # [B, S_max, Hkv, hd]
    v: jnp.ndarray


@jax.named_scope("attention")
def attention_block(x: jnp.ndarray, p: Dict[str, jnp.ndarray],
                    cfg: ModelConfig, *, causal: bool = True,
                    is_global: bool = True, q_offset: int = 0,
                    cache: Optional[AttnCache] = None,
                    cache_len: Optional[jnp.ndarray] = None,
                    kv_source: Optional[jnp.ndarray] = None,
                    attn_chunk: int = 1024,
                    use_pallas: bool = False, interpret: bool = False,
                    continue_prefill: bool = False,
                    block_table: Optional[jnp.ndarray] = None,
                    block_size: int = 0,
                    strict_pallas: bool = False,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    ) -> Tuple[jnp.ndarray, Optional[AttnCache]]:
    """Full attention sub-layer (projections + RoPE + attention + out-proj).

    Modes:
      * prefill/train: cache=None -> chunked attention over x itself
        (or ``kv_source`` for cross-attention), returns fresh cache if
        cache_len is not None.
      * decode: cache given, x is [B, 1, d]; writes K/V at cache_len-1.
        ``q_offset``/``cache_len`` may be per-sequence [B] vectors (slotted
        continuous batching), in which case K/V lands at each row's own slot.
      * paged decode (``block_table`` given): cache is a batch-1 *physical*
        block pool; each row's K/V is written at its block-translated
        position and attention reads through the table — either the
        reference gather (``paged_decode_attention``) or, with
        ``use_pallas``, the fused Pallas kernel
        (``kernels.paged_attention``) that walks the block table inside
        the kernel and never materializes the logical view.  Requires
        window-free attention over the logical range (validated here: a
        binding sliding window raises).
      * chunked-prefill continuation (``continue_prefill``): cache given and
        x is a [B, C] prompt chunk starting at position ``q_offset`` (scalar);
        writes K/V at [q_offset, q_offset + C).  With ``use_pallas`` the
        slab cache is viewed as a pool of contiguous per-row blocks with
        an identity block table and ``cache_len = q_offset + C``, so the
        SAME q-tiled paged kernel serves chunked prefill and prefix-tail
        prefill (the kernel's causal pruning skips kv tiles past
        ``q_offset + C`` — the reference ``chunked_attention`` scans the
        whole [B, S_max] slab every chunk).  Otherwise the chunked
        reference attends over the full cache, the causal mask hiding
        the unwritten tail.

    Every branch records its dispatch decision (fused kernel vs reference)
    into the module-level trace-time log — see ``dispatch_log`` /
    ``fallback_counts``.  ``strict_pallas=True`` turns a requested-but-
    inapplicable fused path from a silent fallback into a loud
    ``FusedPathUnavailable`` at trace time.  ``mesh`` (the model's mesh)
    lets the fused paged kernel run per device under ``shard_map``.
    """
    B, S, d = x.shape
    window = 0 if (is_global and cfg.global_attn_every) else cfg.sliding_window
    softcap = cfg.attn_logit_softcap
    kv_in = x if kv_source is None else kv_source

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_in, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_in, p["wv"])

    if cfg.rope_theta > 0 and kv_source is None:
        qo = jnp.asarray(q_offset)
        off = qo[..., None] if qo.ndim else qo     # [B, 1] or scalar
        q = apply_rope(q, off + jnp.arange(S), cfg.rope_theta)
        k = apply_rope(k, off + jnp.arange(kv_in.shape[1]), cfg.rope_theta)

    new_cache = None
    if cache is not None and S > 1 and continue_prefill:
        S_max = cache.k.shape[1]
        start = jnp.asarray(q_offset, jnp.int32)
        k_cache = jax.lax.dynamic_update_slice(
            cache.k, k.astype(cache.k.dtype), (0, start, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache.v, v.astype(cache.v.dtype), (0, start, 0, 0))
        # fused path: view the [B, S_max] slab as B contiguous block
        # chains and run the q-tiled paged kernel with an identity table
        # and cache_len = q_offset + S — the kernel's mask (query i at
        # absolute position cache_len - S + i = q_offset + i) matches
        # chunked_attention(..., q_offset=q_offset) exactly, and its
        # causal pruning stops at q_offset + S instead of scanning the
        # whole slab.  A window can only be ignored when it cannot bind
        # over the slab (window >= S_max).
        fuse = use_pallas and causal and (window == 0 or window >= S_max)
        if fuse:
            from repro.kernels.paged_attention.ops import (
                largest_block_divisor, paged_attention)
            bs_slab = largest_block_divisor(S_max)
            nb = S_max // bs_slab
            Hkv, hd = k_cache.shape[2], k_cache.shape[3]
            table = (jnp.arange(B, dtype=jnp.int32)[:, None] * nb
                     + jnp.arange(nb, dtype=jnp.int32)[None, :])
            cl = jnp.broadcast_to(start + S, (B,))
            _record_dispatch("prefill_continue", fused=True,
                             requested=use_pallas)
            out = paged_attention(
                q, k_cache.reshape(1, B * S_max, Hkv, hd),
                v_cache.reshape(1, B * S_max, Hkv, hd), table, cl,
                block_size=bs_slab, softcap=softcap, interpret=interpret,
                mesh=mesh)
        else:
            _record_dispatch(
                "prefill_continue", fused=False, requested=use_pallas,
                strict=strict_pallas,
                reason=("non-causal attention" if not causal else
                        f"binding sliding window {window} < slab {S_max}"))
            out = chunked_attention(q, k_cache, v_cache, causal=causal,
                                    window=window, softcap=softcap,
                                    chunk=attn_chunk, q_offset=q_offset)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, AttnCache(k_cache, v_cache)
    if cache is not None and S > 1 and block_table is None:
        # prefill with a pre-allocated cache: full causal attention over x,
        # then write the computed K/V into the cache prefix [0, S).
        if use_pallas and causal and window == 0 and softcap == 0.0:
            from repro.kernels.flash_attention.ops import flash_attention
            _record_dispatch("prefill_cache", fused=True, requested=True)
            out = flash_attention(q, k, v, causal=True, interpret=interpret)
        else:
            _record_dispatch(
                "prefill_cache", fused=False, requested=use_pallas,
                strict=strict_pallas,
                reason=(f"flash kernel guards failed (causal={causal}, "
                        f"window={window}, softcap={softcap})"))
            out = chunked_attention(q, k, v, causal=causal, window=window,
                                    softcap=softcap, chunk=attn_chunk,
                                    q_offset=0)
        S_max = cache.k.shape[1]
        kw = k[:, :S_max].astype(cache.k.dtype)
        vw = v[:, :S_max].astype(cache.v.dtype)
        if S >= S_max and window > 0 and S_max <= window:
            # ring: keep the window tail, each position p at its ring slot
            # p % S_max — decode writes land at (cache_len - 1) % S_max, so
            # storing the tail flat at [0, S_max) would leave the ring
            # rotated by S % S_max and decode would evict a mid-window
            # token instead of the oldest whenever S % S_max != 0
            kw = jnp.roll(k[:, S - S_max:], S % S_max,
                          axis=1).astype(cache.k.dtype)
            vw = jnp.roll(v[:, S - S_max:], S % S_max,
                          axis=1).astype(cache.v.dtype)
        k_cache = jax.lax.dynamic_update_slice(cache.k, kw, (0, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache.v, vw, (0, 0, 0, 0))
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, AttnCache(k_cache, v_cache)
    if cache is not None and block_table is not None:
        # paged decode / speculative verify: translate each row's S write
        # positions through its block-table row, scatter into the physical
        # pool, gather-attend (causal within the window when S > 1).
        # Inactive rows (cache_len=S, all-null table) write into the null
        # block — garbage that the validity mask keeps unread.
        L_max = block_table.shape[1] * block_size
        # ring mode: a binding sliding window wraps the logical position
        # into a ring of M = round_up(window, block_size) positions, so a
        # chain of M/block_size blocks serves unbounded logical lengths.
        # window > L_max cannot bind (the engine caps logical positions at
        # L_max there) and keeps the window-free path; window == L_max is
        # equivalent under either arithmetic (cl <= M => pos % M == pos).
        ring = 0 < window <= L_max
        if ring and S > 1:
            raise NotImplementedError(
                f"paged sliding-window ring decode (window={window}) is "
                f"single-query only; speculative verify windows are "
                f"rejected for windowed models at EngineConfig validation")
        cl = jnp.broadcast_to(
            jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
        pos = cl[:, None] - S + jnp.arange(S)[None]         # [B, S]
        if ring:
            M = -(-window // block_size) * block_size
            pos = pos % M
        widx = block_table[jnp.arange(B)[:, None], pos // block_size] \
            * block_size + pos % block_size                 # [B, S]
        k_cache = cache.k.at[0, widx].set(k.astype(cache.k.dtype))
        v_cache = cache.v.at[0, widx].set(v.astype(cache.v.dtype))
        branch = "verify" if S > 1 else "decode"
        if ring:
            _record_dispatch(
                "decode_ring", fused=False, requested=use_pallas,
                strict=strict_pallas,
                reason=f"sliding-window ring decode (window={window}) has "
                       f"no fused kernel")
            out = paged_ring_decode_attention(
                q, k_cache, v_cache, block_table, cl, window=window,
                block_size=block_size, softcap=softcap)
        elif use_pallas:
            from repro.kernels.paged_attention.ops import (decode_plan,
                                                           paged_attention)
            plan = (decode_plan(k_cache, block_size=block_size,
                                n_blocks=block_table.shape[1], mesh=mesh)
                    if S == 1 else {})
            _record_dispatch(branch, fused=True, requested=True, **plan)
            out = paged_attention(q, k_cache, v_cache, block_table, cl,
                                  block_size=block_size, softcap=softcap,
                                  interpret=interpret, mesh=mesh)
        else:
            _record_dispatch(branch, fused=False, requested=False,
                             reason="use_pallas not requested")
            out = paged_decode_attention(q, k_cache, v_cache, block_table,
                                         cl, block_size=block_size,
                                         softcap=softcap)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, AttnCache(k_cache, v_cache)
    if cache is not None:
        # decode: write the new K/V at position cache_len-1, attend over cache.
        # Sliding-window caches sized to the window act as ring buffers
        # (mixtral long_500k): slots are overwritten in place and the window
        # constraint is enforced by the overwrite itself.
        S_max = cache.k.shape[1]
        ring = window > 0 and S_max <= window
        cl = jnp.asarray(cache_len)
        pos = ((cl - 1) % S_max) if ring else (cl - 1)
        if cl.ndim:
            # per-slot positions: scatter each row's K/V at its own index
            bidx = jnp.arange(B)
            k_cache = cache.k.at[bidx, pos].set(k[:, 0].astype(cache.k.dtype))
            v_cache = cache.v.at[bidx, pos].set(v[:, 0].astype(cache.v.dtype))
        else:
            k_cache = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, pos, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, pos, 0, 0))
        eff_len = jnp.minimum(cl, S_max) if ring else cl
        _record_dispatch(
            "decode_slab", fused=False, requested=use_pallas,
            strict=strict_pallas,
            reason="slab decode has no fused kernel (paged pool required)")
        out = decode_attention(q, k_cache, v_cache, eff_len,
                               window=0 if ring else window, softcap=softcap)
        new_cache = AttnCache(k_cache, v_cache)
    else:
        if use_pallas and causal and window == 0 and softcap == 0.0:
            from repro.kernels.flash_attention.ops import flash_attention
            _record_dispatch("prefill", fused=True, requested=True)
            out = flash_attention(q, k, v, causal=True, interpret=interpret)
        else:
            _record_dispatch(
                "prefill", fused=False, requested=use_pallas,
                strict=strict_pallas,
                reason=(f"flash kernel guards failed (causal={causal}, "
                        f"window={window}, softcap={softcap})"))
            out = chunked_attention(q, k, v, causal=causal, window=window,
                                    softcap=softcap, chunk=attn_chunk,
                                    q_offset=q_offset)
        if cache_len is not None:
            # prefill: keep the K/V we just computed as the cache prefix
            new_cache = AttnCache(k, v)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache
