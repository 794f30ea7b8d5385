"""Paged KV pool tests: the block allocator, the chunk-to-block scatter,
and the block-aware engine — admission gated on free blocks, incremental
chain growth, preemption-by-recompute, and the equal-memory concurrency
win over the slab pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models.model import build_model
from repro.serve import (BlockAllocator, Request, ServeEngine, VirtualClock,
                         blocks_for_tokens, engine_config_for,
                         make_paged_pool, poisson_requests,
                         write_chunk_blocks)
from repro.serve.slots import discover_seq_axes

from _serve_helpers import captured_run

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                   num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                   head_dim=16, dtype="float32")


def _model(cfg, batch, seq_len):
    m = build_model(cfg, ParallelConfig(attn_chunk=8, loss_chunk=8),
                    batch=batch, seq_len=seq_len)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(model, params, *, slots, prompt_len, max_new, chunk, **kw):
    ecfg = engine_config_for(model.cfg, max_slots=slots,
                             prompt_len=prompt_len, max_new_tokens=max_new,
                             prefill_chunk=chunk, **kw)
    return ServeEngine(model, params, ecfg, clock=VirtualClock(0.1))


# ----------------------------------------------------------------------
# allocator
# ----------------------------------------------------------------------
def test_block_allocator_alloc_extend_release():
    a = BlockAllocator(num_blocks=6, block_size=4)    # block 0 reserved
    assert a.usable_blocks == 5 and a.free_blocks == 5
    c1 = a.alloc_chain(1, 2)
    assert c1 is not None and len(c1) == 2 and 0 not in c1
    assert a.blocks_in_use == 2
    assert a.alloc_chain(2, 4) is None                # only 3 free: no-op
    assert a.free_blocks == 3
    c2 = a.alloc_chain(2, 3)
    assert a.free_blocks == 0
    assert a.extend(1) is None                        # dry
    assert a.release(2) == 3
    blk = a.extend(1)
    assert blk in c2                                  # recycled
    assert a.chain(1) == tuple(c1) + (blk,)
    assert a.release(1) == 3 and a.free_blocks == 5
    assert a.alloc_chain(3, 1) is not None
    with pytest.raises(ValueError, match="already holds"):
        a.alloc_chain(3, 1)                           # double alloc same rid


def test_blocks_for_tokens():
    assert blocks_for_tokens(1, 4) == 1
    assert blocks_for_tokens(4, 4) == 1
    assert blocks_for_tokens(5, 4) == 2


# ----------------------------------------------------------------------
# physical pool + chunk scatter (structural, fake cache layouts)
# ----------------------------------------------------------------------
def _fake_init_cache(b, s_max):
    """Scan-stacked blocks (batch axis 1, seq axis 2) + an unscanned lead
    layer (batch axis 0, seq axis 1) — full-length KV on every leaf."""
    return {
        "blocks": (jnp.zeros((3, b, s_max, 2, 4)),
                   jnp.zeros((3, b, s_max, 2, 4))),
        "lead": [jnp.zeros((b, s_max, 2, 4))],
    }


def test_make_paged_pool_resizes_seq_axis():
    seq = discover_seq_axes(_fake_init_cache, 16)
    pool = make_paged_pool(_fake_init_cache, 16, seq, num_blocks=5,
                           block_size=4)
    assert pool["blocks"][0].shape == (3, 1, 20, 2, 4)
    assert pool["lead"][0].shape == (1, 20, 2, 4)


def test_make_paged_pool_rejects_clamped_and_seqless_leaves():
    def clamped(b, s):
        return {"kv": jnp.zeros((b, min(s, 6), 2, 4))}   # window ring buffer

    with pytest.raises(NotImplementedError, match="pageable"):
        make_paged_pool(clamped, 16, discover_seq_axes(clamped, 16), 4, 4)

    def seqless(b, s):
        return {"kv": jnp.zeros((b, s, 2, 4)), "state": jnp.zeros((b, 8))}

    with pytest.raises(NotImplementedError, match="pageable"):
        make_paged_pool(seqless, 16, discover_seq_axes(seqless, 16), 4, 4)


def test_write_chunk_blocks_scatters_through_table():
    """Chunk [start, start+C) of the scratch lands at the block-translated
    physical positions; everything else in the pool stays untouched."""
    bs, C, s_max = 4, 4, 16
    seq = discover_seq_axes(_fake_init_cache, s_max)
    pool = make_paged_pool(_fake_init_cache, s_max, seq, num_blocks=6,
                           block_size=bs)
    # scratch leaf value = logical position + 1 along the seq axis
    def fill(leaf, ax):
        r = jnp.arange(1, s_max + 1, dtype=leaf.dtype)
        shape = [1] * leaf.ndim
        shape[ax] = s_max
        return jnp.broadcast_to(r.reshape(shape), leaf.shape)
    scratch = jax.tree.map(fill, _fake_init_cache(1, s_max), seq)

    bt_row = np.zeros((4,), np.int32)
    bt_row[:2] = [3, 1]        # logical block 0 -> phys 3, block 1 -> phys 1
    out = jax.jit(lambda p, s, r, st: write_chunk_blocks(
        p, s, r, st, chunk=C, block_size=bs, seq_axes=seq))(
            pool, scratch, bt_row, np.int32(4))    # second logical chunk
    lead = np.asarray(out["lead"][0])              # [1, 24, 2, 4]
    # logical positions 4..7 (values 5..8) live in physical block 1
    assert (lead[0, 4:8, 0, 0] == np.arange(5, 9)).all()
    # physical block 3 (logical block 0's home) untouched by this chunk
    assert (lead[0, 12:16] == 0).all()
    stacked = np.asarray(out["blocks"][0])         # [3, 1, 24, 2, 4]
    assert (stacked[:, 0, 4:8, 0, 0] == np.arange(5, 9)).all()


# ----------------------------------------------------------------------
# block-aware engine
# ----------------------------------------------------------------------
def test_paged_recycling_zero_recompilation():
    """Six requests through two slots on a paged pool: admission, chain
    growth, EOS reclamation, and slot recycling never add a jit entry."""
    L, gen, slots = 8, 4, 2
    model, params = _model(TINY, slots, L)
    eng = _engine(model, params, slots=slots, prompt_len=L, max_new=gen,
                  chunk=4, paged=True, kv_block_size=4)
    reqs = poisson_requests(6, rate=0.0, vocab_size=TINY.vocab_size,
                            prompt_len=L, max_new_tokens=gen, seed=0)
    rep = eng.run(reqs)
    assert rep["n_requests"] == 6
    assert rep["total_new_tokens"] == 6 * gen
    used = [s for _, s in eng.slot_history]
    assert sorted(set(used)) == [0, 1] and max(np.bincount(used)) >= 2
    assert rep["jit_entries"] == {"prefill_chunk": 1, "decode": 1,
                                  "write_blocks": 1}, rep["jit_entries"]
    # all blocks reclaimed once the pool drains
    assert eng._alloc.blocks_in_use == 0
    assert (eng.block_table == 0).all()
    assert 0 < rep["kv_utilization"] <= 1.0


def test_preemption_by_recompute_is_token_exact():
    """A block budget too small for every admitted request forces
    preemption; the preempted request is recomputed and still emits exactly
    its solo greedy stream, with zero recompilation."""
    L, gen = 8, 8
    model, params = _model(TINY, 3, L)

    def mk():
        rng = np.random.default_rng(3)
        return [Request(rid=i,
                        tokens=rng.integers(0, TINY.vocab_size,
                                            (L,)).astype(np.int32),
                        max_new_tokens=gen) for i in range(5)]

    reqs_a, reqs_b = mk(), mk()

    solo = _engine(model, params, slots=1, prompt_len=L, max_new=gen,
                   chunk=4)
    out_ref, _ = captured_run(solo, reqs_a)
    # worst case 16 tokens = 4 blocks/request; 6 usable blocks for 3 slots
    eng = _engine(model, params, slots=3, prompt_len=L, max_new=gen,
                  chunk=4, paged=True, kv_block_size=4, num_kv_blocks=6)
    out, rep = captured_run(eng, reqs_b)
    assert rep["preemptions"] > 0
    assert rep["n_requests"] == 5
    for rid in out_ref:
        assert out[rid] == out_ref[rid], rid
    assert rep["jit_entries"] == {"prefill_chunk": 1, "decode": 1,
                                  "write_blocks": 1}
    assert eng._alloc.blocks_in_use == 0     # everything reclaimed


def test_admission_gated_on_free_blocks():
    """With blocks for only one worst-case request, a second request waits
    even though a slot is free — admission is block-aware, not slot-aware."""
    L, gen = 8, 4
    model, params = _model(TINY, 2, L)
    eng = _engine(model, params, slots=2, prompt_len=L, max_new=gen,
                  chunk=4, paged=True, kv_block_size=4, num_kv_blocks=3)
    reqs = poisson_requests(2, rate=0.0, vocab_size=TINY.vocab_size,
                            prompt_len=L, max_new_tokens=gen, seed=1)
    rep = eng.run(reqs)
    assert rep["n_requests"] == 2            # both finish eventually
    assert rep["max_occupancy"] == 1         # but never decode together
    assert rep["preemptions"] == 0           # waiting, not thrashing


def test_paged_outlives_slab_at_equal_memory():
    """Equal KV token budget, mixed prompt lengths: the paged engine
    decodes strictly more requests concurrently than the slab pool's
    worst-case slot count allows."""
    gen, C = 6, 4
    max_prompt = 16
    model, params = _model(TINY, 8, max_prompt)
    # slab: 2 slots x (16 + 6 -> padded 24) = 48 KV tokens reserved
    slab = _engine(model, params, slots=2, prompt_len=max_prompt,
                   max_new=gen, chunk=C)
    budget = 2 * slab.ecfg.max_seq_len
    # paged: same 48 tokens as 12 4-token blocks, decode width 8
    paged = _engine(model, params, slots=8, prompt_len=max_prompt,
                    max_new=gen, chunk=C, paged=True, kv_block_size=4,
                    num_kv_blocks=budget // 4)
    reqs = poisson_requests(8, rate=0.0, vocab_size=TINY.vocab_size,
                            prompt_len=max_prompt, max_new_tokens=gen,
                            seed=2, prompt_len_range=(4, 8))
    rep_s = slab.run(list(reqs))
    rep_p = paged.run(list(reqs))
    assert rep_s["n_requests"] == rep_p["n_requests"] == 8
    assert rep_p["max_occupancy"] > rep_s["max_occupancy"]
    assert rep_p["max_occupancy"] > 2        # beyond the slab's hard cap
    assert rep_p["decode_steps"] < rep_s["decode_steps"]


def test_paged_rejects_window_clamped_cache():
    """A model whose sliding window binds below the padded pool length
    is paged as a window ring of round_up(window, block_size) positions.
    engine_config_for rejects a prefill chunk wider than the ring, and a
    hand-built EngineConfig that sneaks past it is rejected at engine
    construction by the ring's named blockers (every blocker at once)."""
    from repro.serve import EngineConfig
    cfg = TINY.replace(sliding_window=8)
    model, params = _model(cfg, 1, 16)
    # max_seq_len 16+8=24 > window 8 -> ring of 8 positions
    with pytest.raises(ValueError, match="sliding-window ring"):
        engine_config_for(cfg, max_slots=1, prompt_len=16,
                          max_new_tokens=8, prefill_chunk=16,
                          paged=True, kv_block_size=4)
    ring = ServeEngine(model, params,
                       EngineConfig(max_slots=1, max_seq_len=24,
                                    prefill_chunk=8, paged=True,
                                    kv_block_size=4))
    assert ring.kv.stats()["window_ring"] and ring.blocks_per_slot == 2
    with pytest.raises(ValueError, match="window ring buffer") as err:
        ServeEngine(model, params,
                    EngineConfig(max_slots=1, max_seq_len=24,
                                 prefill_chunk=16, paged=True,
                                 kv_block_size=4, speculative_k=2,
                                 fused_paged_attention=True))
    for blocker in ("prefill_chunk 16 > ring 8", "single-query",
                    "no ring arithmetic"):
        assert blocker in str(err.value)
    # a window the padded pool fits inside never binds: accepted, and the
    # engine still serves token streams (window-free == exact there)
    cfg_wide = TINY.replace(sliding_window=64)
    model_w, params_w = _model(cfg_wide, 1, 16)
    eng = ServeEngine(model_w, params_w,
                      EngineConfig(max_slots=1, max_seq_len=24,
                                   prefill_chunk=8, paged=True,
                                   kv_block_size=4))
    assert eng.blocks_per_slot == 6
    # prefix sharing pads one extra chunk: shapes that fit a window
    # without sharing make the window bind with it, and the ring then
    # rejects sharing by name
    cfg64 = TINY.replace(sliding_window=64)
    model64, params64 = _model(cfg64, 1, 16)
    ServeEngine(model64, params64, engine_config_for(
        cfg64, max_slots=1, prompt_len=56, max_new_tokens=8,
        prefill_chunk=8, paged=True, kv_block_size=4))
    with pytest.raises(ValueError, match="absolute sequence length"):
        ServeEngine(model64, params64, engine_config_for(
            cfg64, max_slots=1, prompt_len=56, max_new_tokens=8,
            prefill_chunk=8, paged=True, kv_block_size=4,
            prefix_sharing=True))


def test_paged_mixed_lengths_decode_together():
    """Different prompt lengths share one paged decode batch and each still
    reproduces its solo stream (per-row block chains + validity masks)."""
    model, params = _model(TINY, 2, 12)
    rng = np.random.default_rng(7)
    pa = rng.integers(0, TINY.vocab_size, (12,)).astype(np.int32)
    pb = rng.integers(0, TINY.vocab_size, (5,)).astype(np.int32)
    gen = 5

    def run(reqs):
        eng = _engine(model, params, slots=2, prompt_len=12, max_new=gen,
                      chunk=4, paged=True, kv_block_size=4)
        out, _ = captured_run(eng, reqs)
        return out

    together = run([Request(rid=0, tokens=pa, max_new_tokens=gen),
                    Request(rid=1, tokens=pb, max_new_tokens=gen)])
    solo_a = run([Request(rid=0, tokens=pa, max_new_tokens=gen)])
    solo_b = run([Request(rid=1, tokens=pb, max_new_tokens=gen)])
    assert together[0] == solo_a[0]
    assert together[1] == solo_b[1]
