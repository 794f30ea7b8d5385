"""Attention correctness: chunked-flash vs naive, SWA, softcap, GQA, decode
against ring and linear caches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep; deterministic fallback sampler
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import ModelConfig
from repro.models.attention import (AttnCache, attention_block,
                                    chunked_attention, decode_attention,
                                    full_attention_ref, init_attention)


@pytest.mark.parametrize("window,softcap,causal", [
    (0, 0.0, True), (0, 0.0, False), (16, 0.0, True), (0, 30.0, True),
    (8, 50.0, True),
])
def test_chunked_matches_naive(window, softcap, causal):
    B, Sq, H, Hkv, hd = 2, 48, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, Sq, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Sq, Hkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Sq, Hkv, hd))
    a = chunked_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, chunk=16)
    b = full_attention_ref(q, k, v, causal=causal, window=window,
                           softcap=softcap)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([8, 24, 33]),
       st.sampled_from([4, 16, 32]))
def test_chunked_chunk_size_independent(seed, S, chunk):
    B, H, hd = 1, 2, 8
    key = jax.random.PRNGKey(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, hd))
               for i in range(3))
    a = chunked_attention(q, k, v, causal=True, chunk=chunk)
    b = full_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_decode_matches_full():
    """Decode at position t == row t of full causal attention."""
    B, S, H, hd = 1, 10, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    full = full_attention_ref(q, k, v, causal=True)
    for t in [0, 4, 9]:
        out = decode_attention(q[:, t:t + 1], k, v, jnp.int32(t + 1))
        np.testing.assert_allclose(np.asarray(out)[:, 0],
                                   np.asarray(full)[:, t], atol=2e-5)


def _swa_cfg(window):
    return ModelConfig(name="tiny-swa", family="dense", num_layers=1,
                       d_model=16, num_heads=2, num_kv_heads=2, d_ff=32,
                       vocab_size=32, head_dim=8, dtype="float32",
                       sliding_window=window)


@pytest.mark.parametrize("S", [10, 13, 16])
def test_prefill_ring_rotation(S):
    """Prefill of S > S_max tokens into a window-sized ring cache, then
    decode: must match a full-length cache with an explicit window mask.

    Regression: the ring tail used to be stored at indices [0, S_max), but
    decode writes land at (cache_len - 1) % S_max — whenever S % S_max != 0
    the ring was rotated relative to the write cursor and decode evicted a
    mid-window token instead of the oldest one."""
    W = 8
    cfg = _swa_cfg(W)
    p = init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, n_dec = 1, 2 * W
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (B, S + n_dec, cfg.d_model)) * 0.3

    def run(s_max):
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache = AttnCache(jnp.zeros((B, s_max, hkv, hd)),
                          jnp.zeros((B, s_max, hkv, hd)))
        outs = []
        y, cache = attention_block(xs[:, :S], p, cfg, cache=cache,
                                   cache_len=jnp.int32(S))
        outs.append(y[:, -1])
        for t in range(S, S + n_dec):
            y, cache = attention_block(xs[:, t:t + 1], p, cfg,
                                       q_offset=jnp.int32(t), cache=cache,
                                       cache_len=jnp.int32(t + 1))
            outs.append(y[:, 0])
        return np.asarray(jnp.stack(outs, axis=1))

    ring, full = run(W), run(S + n_dec)
    np.testing.assert_allclose(ring, full, atol=3e-5)


def test_paged_decode_rejects_binding_window():
    """A binding sliding window (window < logical range) pages through the
    window ring: single-query decode runs the ring gather, while what the
    ring cannot serve is rejected by name — a multi-query verify window,
    and a strictly required fused kernel (the kernel has no ring
    arithmetic)."""
    from repro.models.attention import (FusedPathUnavailable,
                                        dispatch_log, reset_dispatch_log)
    cfg = _swa_cfg(8)
    p = init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, bs, n_logical = 2, 4, 4                 # L_max = 16 > window = 8
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    P = (1 + B * n_logical) * bs
    pool = AttnCache(jnp.zeros((1, P, hkv, hd)), jnp.zeros((1, P, hkv, hd)))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.d_model))
    bt = jnp.ones((B, n_logical), jnp.int32)
    reset_dispatch_log()
    y, _ = attention_block(x, p, cfg, q_offset=jnp.zeros((B,), jnp.int32),
                           cache=pool, cache_len=jnp.ones((B,), jnp.int32),
                           block_table=bt, block_size=bs)
    assert y.shape == (B, 1, cfg.d_model)
    assert [r["branch"] for r in dispatch_log()] == ["decode_ring"]
    x2 = jax.random.normal(jax.random.PRNGKey(2), (B, 2, cfg.d_model))
    with pytest.raises(NotImplementedError, match="single-query"):
        attention_block(x2, p, cfg, q_offset=jnp.zeros((B,), jnp.int32),
                        cache=pool, cache_len=jnp.full((B,), 2, jnp.int32),
                        block_table=bt, block_size=bs)
    with pytest.raises(FusedPathUnavailable, match="no fused kernel"):
        attention_block(x, p, cfg, q_offset=jnp.zeros((B,), jnp.int32),
                        cache=pool, cache_len=jnp.ones((B,), jnp.int32),
                        block_table=bt, block_size=bs, use_pallas=True,
                        strict_pallas=True)
    # a window that can never bind (window >= L_max) is dropped exactly
    cfg_wide = _swa_cfg(bs * n_logical)
    y, _ = attention_block(x, p, cfg_wide,
                           q_offset=jnp.zeros((B,), jnp.int32),
                           cache=pool, cache_len=jnp.ones((B,), jnp.int32),
                           block_table=bt, block_size=bs)
    assert y.shape == (B, 1, cfg.d_model)


def test_decode_ring_buffer_equivalence():
    """A window-sized ring cache gives the same result as masking a full
    cache to the window (mixtral long_500k mechanism)."""
    B, H, hd, W = 1, 2, 8, 8
    total = 20
    ks = jax.random.normal(jax.random.PRNGKey(0), (B, total, H, hd))
    vs = jax.random.normal(jax.random.PRNGKey(1), (B, total, H, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd))
    t = 15  # cache_len
    # full cache + window mask
    ref = decode_attention(q, ks[:, :t], vs[:, :t], jnp.int32(t), window=W)
    # ring cache holding the last W entries at wrapped positions
    ring_k = jnp.zeros((B, W, H, hd))
    ring_v = jnp.zeros((B, W, H, hd))
    for pos in range(t - W, t):
        ring_k = ring_k.at[:, pos % W].set(ks[:, pos])
        ring_v = ring_v.at[:, pos % W].set(vs[:, pos])
    out = decode_attention(q, ring_k, ring_v, jnp.int32(W))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
