"""Fused paged-attention kernels (decode and q-tiled): interpret-mode
parity vs the gather reference, jit stability, and end-to-end
serve-stream identity."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, ParallelConfig
from repro.kernels.paged_attention.ops import decode_plan, paged_attention
from repro.kernels.paged_attention.paged_attention import paged_decode_kernel
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models.attention import paged_decode_attention
from repro.models.model import build_model
from repro.serve import (ServeEngine, VirtualClock, engine_config_for,
                         poisson_requests)

from _serve_helpers import captured_run


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _setup(seed, *, B, Hkv, rep, hd, bs, n_logical, lengths, dtype,
           q_len=1):
    """Physical pools + ragged block tables.  Each row's chain covers its
    length with distinct shuffled physical blocks; entries past the chain
    stay on the null block (0) — the engine's partially-filled-table
    convention ("holes").  ``q_len > 1`` builds a speculative-verify
    query window (each row's length must then be >= q_len)."""
    H = Hkv * rep
    num_blocks = 1 + B * n_logical
    P = num_blocks * bs
    key = jax.random.PRNGKey(seed)
    k_pool = jax.random.normal(jax.random.fold_in(key, 1),
                               (1, P, Hkv, hd)).astype(dtype)
    v_pool = jax.random.normal(jax.random.fold_in(key, 2),
                               (1, P, Hkv, hd)).astype(dtype)
    q = jax.random.normal(jax.random.fold_in(key, 3),
                          (B, q_len, H, hd)).astype(dtype)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, num_blocks))
    bt = np.zeros((B, n_logical), np.int32)
    i = 0
    for b in range(B):
        nv = -(-int(lengths[b]) // bs)
        bt[b, :nv] = perm[i:i + nv]
        i += nv
    return q, k_pool, v_pool, jnp.asarray(bt), \
        jnp.asarray(np.asarray(lengths, np.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_attention_parity(dtype, rep, softcap):
    """Kernel vs the standalone oracle AND the model-layer gather
    reference, over ragged per-row lengths (including the inactive-row
    length-1 convention) and null-block table holes."""
    bs, n_logical = 4, 6
    lengths = [1, 5, 11, 24]        # ragged; 24 = full chain, no holes
    q, kp, vp, bt, cl = _setup(0, B=4, Hkv=2, rep=rep, hd=16, bs=bs,
                               n_logical=n_logical, lengths=lengths,
                               dtype=dtype)
    out = paged_attention(q, kp, vp, bt, cl, block_size=bs,
                          softcap=softcap, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, cl, block_size=bs,
                              softcap=softcap)
    gather = paged_decode_attention(q, kp, vp, bt, cl, block_size=bs,
                                    softcap=softcap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gather, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("pages", [1, 3, 4])
def test_paged_decode_kernel_parity(pages, softcap, rep, dtype):
    """The decode kernel at P pages a grid step vs both references over a
    7-page table (ragged last group at P = 3 and 4): an inactive row
    (length 1, all-null table), lengths ending mid-group, exactly at a
    group boundary and two groups deep, and full tables."""
    bs, n_logical, Hkv, hd = 4, 7, 2, 16
    span = pages * bs
    lengths = [1, 5, span, min(2 * span, 28), 28, 27]
    q, kp, vp, bt, cl = _setup(6, B=len(lengths), Hkv=Hkv, rep=rep, hd=hd,
                               bs=bs, n_logical=n_logical, lengths=lengths,
                               dtype=dtype)
    bt = bt.at[0].set(0)
    B = len(lengths)
    out = paged_decode_kernel(
        q.reshape(B, Hkv, rep, hd), kp[0].reshape(-1, bs, Hkv, hd),
        vp[0].reshape(-1, bs, Hkv, hd), bt, cl, block_size=bs,
        softcap=softcap, pages_per_step=pages, interpret=True)
    out = np.asarray(out.reshape(q.shape), np.float32)
    for ref in (paged_attention_ref, paged_decode_attention):
        want = ref(q, kp, vp, bt, cl, block_size=bs, softcap=softcap)
        np.testing.assert_allclose(out, np.asarray(want, np.float32),
                                   atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_pages_per_step_from_shapes():
    """P is the most pages whose K and V fit the step's byte target, from
    each device's local kv heads, at least 1 and at most the table."""
    from repro.kernels.paged_attention.paged_attention import \
        DECODE_STEP_BYTES

    def pool(hkv, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, 2801 * 16, hkv, 128), dtype)
    page = 16 * 16 * 128 * 2                  # a 16-token page, 16 heads
    cell = decode_plan(pool(16), block_size=16, n_blocks=288)
    assert cell == {"kernel": "paged_decode",
                    "pages_per_step": DECODE_STEP_BYTES // (2 * page)}
    local = decode_plan(pool(4), block_size=16, n_blocks=288)
    assert local["pages_per_step"] == 4 * cell["pages_per_step"]
    assert decode_plan(pool(16), block_size=16,
                       n_blocks=2)["pages_per_step"] == 2
    assert decode_plan(pool(16, jnp.float32), block_size=4096,
                       n_blocks=288)["pages_per_step"] == 1


def test_paged_attention_block_size_sweep():
    """Kernel/reference parity holds at every block size (tile shape must
    not change the math)."""
    for bs, n_logical in [(2, 12), (4, 6), (8, 3)]:
        q, kp, vp, bt, cl = _setup(1, B=2, Hkv=2, rep=2, hd=8, bs=bs,
                                   n_logical=n_logical, lengths=[3, 17],
                                   dtype=jnp.float32)
        out = paged_attention(q, kp, vp, bt, cl, block_size=bs,
                              interpret=True)
        ref = paged_decode_attention(q, kp, vp, bt, cl, block_size=bs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("q_len", [1, 2, 4])
@pytest.mark.parametrize("rep", [1, 4])
def test_paged_attention_multiquery_parity(q_len, rep):
    """Multi-query tiles (speculative verify window): kernel vs oracle vs
    the model-layer gather over ragged lengths — including rows whose
    valid length is exactly the window (the engine's inactive-row
    convention at cache_len = q_len)."""
    bs, n_logical = 4, 6
    # lengths INCLUDE the q_len window positions; min length = q_len
    lengths = [q_len, 5 + q_len, 20 + q_len]
    q, kp, vp, bt, cl = _setup(3, B=3, Hkv=2, rep=rep, hd=16, bs=bs,
                               n_logical=n_logical, lengths=lengths,
                               dtype=jnp.float32, q_len=q_len)
    out = paged_attention(q, kp, vp, bt, cl, block_size=bs, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, cl, block_size=bs)
    gather = paged_decode_attention(q, kp, vp, bt, cl, block_size=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gather),
                               atol=2e-5, rtol=2e-5)


def test_multiquery_last_query_aligns_with_single_query():
    """The last query of a verify window attends exactly the positions a
    plain decode query at the same state does, so the q-tiled kernel's
    output for it must agree with the decode kernel's (the q_len=1 call)
    to float-associativity noise: the two kernels scale the scores at
    different points and fold the online softmax over different page
    groupings."""
    bs, n_logical, S = 4, 6, 3
    lengths = [6, 9, 13]
    q, kp, vp, bt, cl = _setup(4, B=3, Hkv=2, rep=2, hd=16, bs=bs,
                               n_logical=n_logical, lengths=lengths,
                               dtype=jnp.float32, q_len=S)
    multi = paged_attention(q, kp, vp, bt, cl, block_size=bs,
                            interpret=True)
    single = paged_attention(q[:, -1:], kp, vp, bt, cl, block_size=bs,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(multi[:, -1:]),
                               np.asarray(single), atol=1e-6, rtol=1e-6)


def test_paged_attention_jit_stability():
    """One cache entry across decode steps: growing lengths and mutated
    block tables must re-use the same compilation."""
    bs, n_logical = 4, 6
    q, kp, vp, bt, cl = _setup(2, B=3, Hkv=2, rep=2, hd=8, bs=bs,
                               n_logical=n_logical, lengths=[2, 9, 15],
                               dtype=jnp.float32)
    fn = jax.jit(functools.partial(paged_attention, block_size=bs,
                                   softcap=0.0, interpret=True))
    outs = [fn(q, kp, vp, bt, cl)]
    for step in range(3):
        cl = cl + 1
        bt2 = jnp.where(bt == 0, (step + 1) % (bt.max() + 1), bt)
        outs.append(fn(q, kp, vp, bt2, cl))
    assert fn._cache_size() == 1
    ref = paged_decode_attention(q, kp, vp, bt, cl - 3, block_size=bs)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(ref),
                               atol=2e-5)


# ----------------------------------------------------------------------
# end-to-end: the serve engine with the kernel on vs off
# ----------------------------------------------------------------------
TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                   num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                   head_dim=16, dtype="float32")


def _paged_engine(fused: bool):
    model = build_model(TINY, ParallelConfig(attn_chunk=8, loss_chunk=8),
                        batch=3, seq_len=16)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = engine_config_for(TINY, max_slots=3, prompt_len=12,
                             max_new_tokens=6, prefill_chunk=4,
                             paged=True, kv_block_size=4,
                             fused_paged_attention=fused)
    return ServeEngine(model, params, ecfg, clock=VirtualClock(0.05))


def test_engine_greedy_streams_identical_fused_vs_gather():
    """Greedy serve streams are token-for-token identical with the fused
    kernel on vs off, and the decode jit cache stays at one entry."""
    streams = {}
    for fused in (False, True):
        eng = _paged_engine(fused)
        reqs = poisson_requests(6, rate=50.0, vocab_size=TINY.vocab_size,
                                prompt_len=12, max_new_tokens=6, seed=7,
                                prompt_len_range=(5, 12))
        outs, rep = captured_run(eng, reqs)
        assert rep["jit_entries"]["decode"] == 1
        assert rep["engine"]["fused_paged_attention"] is fused
        decode = rep["attention_dispatch"]["decode"]
        assert decode["fused"] is fused
        if fused:
            # the decode kernel, at the pages per step the pool's shape
            # gives: the whole table, which is far under the byte target
            assert decode["kernel"] == "paged_decode"
            assert decode["pages_per_step"] == eng.block_table.shape[1]
        streams[fused] = outs
    assert streams[False] == streams[True]


# ----------------------------------------------------------------------
# q-tiled prefill windows (the tentpole: one kernel for every phase)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("q_tile", [None, 7])
def test_qtiled_prefill_window_parity(dtype, rep, q_tile):
    """Large query windows (chunked-prefill regime) through the q-tiled
    kernel vs both references, with q_tile=7 forcing ragged last q tiles
    (48 = 6*7 + 6) and lengths mixing q_offset = 0 (prefill from
    scratch: length == S) with mid-sequence starts (length > S).  The
    cache_len contract: lengths INCLUDE the S-token query window."""
    bs, S = 4, 48
    n_logical = 20
    lengths = [S, S + 13, S + 30]       # q_offset 0 / 13 / 30
    q, kp, vp, bt, cl = _setup(5, B=3, Hkv=2, rep=rep, hd=16, bs=bs,
                               n_logical=n_logical, lengths=lengths,
                               dtype=dtype, q_len=S)
    out = paged_attention(q, kp, vp, bt, cl, block_size=bs,
                          q_tile=q_tile, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, cl, block_size=bs)
    gather = paged_decode_attention(q, kp, vp, bt, cl, block_size=bs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gather, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("q_offset", [0, 48])
def test_slab_as_pool_matches_chunked_attention(q_offset):
    """The fused continue-prefill construction: a [B, S_max] slab viewed
    as per-row contiguous block chains with an identity table and
    cache_len = q_offset + S must agree with the reference
    ``chunked_attention(..., q_offset=q_offset)`` over the same slab —
    including garbage in the unwritten tail, which both paths must mask."""
    from repro.kernels.paged_attention.ops import largest_block_divisor
    from repro.models.attention import chunked_attention
    B, S_max, S, Hkv, rep, hd = 2, 144, 48, 2, 2, 8
    key = jax.random.PRNGKey(11)
    k_slab = jax.random.normal(jax.random.fold_in(key, 0),
                               (B, S_max, Hkv, hd))
    v_slab = jax.random.normal(jax.random.fold_in(key, 1),
                               (B, S_max, Hkv, hd))
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, S, Hkv * rep, hd))
    bs = largest_block_divisor(S_max)
    nb = S_max // bs
    assert nb > 1                       # multi-block chains per row
    table = (jnp.arange(B, dtype=jnp.int32)[:, None] * nb
             + jnp.arange(nb, dtype=jnp.int32)[None, :])
    cl = jnp.full((B,), q_offset + S, jnp.int32)
    out = paged_attention(q, k_slab.reshape(1, B * S_max, Hkv, hd),
                          v_slab.reshape(1, B * S_max, Hkv, hd),
                          table, cl, block_size=bs, interpret=True)
    ref = chunked_attention(q, k_slab, v_slab, causal=True, chunk=32,
                            q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("q_offset", [0, 10])
def test_attention_block_fused_continue_prefill_matches_reference(q_offset):
    """attention_block's chunked-prefill continuation with use_pallas on
    (slab-as-pool q-tiled kernel) vs off (chunked reference): identical
    outputs and caches at chunk starts 0 and mid-sequence."""
    from repro.models.attention import (AttnCache, attention_block,
                                        init_attention)
    cfg = TINY
    B, S, S_max = 2, 10, 24
    key = jax.random.PRNGKey(13)
    p = init_attention(jax.random.fold_in(key, 0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    # pre-populated slab prefix [0, q_offset) + garbage tail
    slab = jax.random.normal(jax.random.fold_in(key, 2),
                             (B, S_max, Hkv, hd))
    outs, caches = {}, {}
    for fused in (False, True):
        cache = AttnCache(slab, slab * 0.5)
        y, nc = attention_block(x, p, cfg, causal=True, q_offset=q_offset,
                                cache=cache, cache_len=None,
                                attn_chunk=8, use_pallas=fused,
                                interpret=True, continue_prefill=True)
        outs[fused], caches[fused] = y, nc
    np.testing.assert_allclose(np.asarray(outs[True]),
                               np.asarray(outs[False]),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(caches[True].k),
                               np.asarray(caches[False].k), atol=0)


def test_strict_pallas_raises_on_inapplicable_fused_path():
    """pallas_strict turns the (previously silent) reference fallback into
    FusedPathUnavailable; non-strict still falls back, and the dispatch
    log counts it."""
    from repro.models import attention as A
    cfg = TINY.replace(sliding_window=8)    # binds: window < S_max = 24
    B, S, S_max = 2, 10, 24
    key = jax.random.PRNGKey(17)
    p = A.init_attention(jax.random.fold_in(key, 0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    slab = jnp.zeros((B, S_max, cfg.num_kv_heads, cfg.resolved_head_dim))
    cache = A.AttnCache(slab, slab)
    with pytest.raises(A.FusedPathUnavailable):
        A.attention_block(x, p, cfg, causal=True, q_offset=0, cache=cache,
                          attn_chunk=8, use_pallas=True, interpret=True,
                          continue_prefill=True, strict_pallas=True)
    A.reset_dispatch_log()
    y, _ = A.attention_block(x, p, cfg, causal=True, q_offset=0,
                             cache=cache, attn_chunk=8, use_pallas=True,
                             interpret=True, continue_prefill=True)
    assert y.shape == (B, S, cfg.d_model)
    assert A.fallback_counts().get("prefill_continue", 0) == 1
    A.reset_dispatch_log()


def test_engine_fused_everywhere_greedy_identical():
    """The full unified path — fused q-tiled prefill, prefix-tail resume,
    speculative k=4 verify — serves greedy streams token-identical to the
    all-reference engine, with no fused branch silently falling back."""
    streams = {}
    for fused in (False, True):
        model = build_model(TINY, ParallelConfig(attn_chunk=8,
                                                 loss_chunk=8),
                            batch=3, seq_len=16)
        params = model.init(jax.random.PRNGKey(0))
        ecfg = engine_config_for(TINY, max_slots=3, prompt_len=16,
                                 max_new_tokens=8, prefill_chunk=4,
                                 paged=True, kv_block_size=4,
                                 prefix_sharing=True, speculative_k=4,
                                 fused_paged_attention=fused)
        eng = ServeEngine(model, params, ecfg, clock=VirtualClock(0.05))
        reqs = poisson_requests(6, rate=50.0, vocab_size=TINY.vocab_size,
                                prompt_len=16, max_new_tokens=8, seed=11,
                                shared_prefix_len=8)
        outs, rep = captured_run(eng, reqs)
        streams[fused] = outs
        if fused:
            assert rep["attention_fallbacks"] == {}
            disp = rep["attention_dispatch"]
            assert disp["prefill_continue"]["fused"]
            assert disp["verify"]["fused"]
        assert set(rep["phases"]) >= {"prefill", "verify"}
        spans = rep["spans"]
        for name, ph in rep["phases"].items():
            assert ph["tokens"] > 0 and ph["seconds"] > 0
            # each phase's seconds are those of the engine's span around
            # its jitted call
            span = "serve.verify" if name == "verify" else "serve.prefill"
            assert ph["seconds"] <= spans[span]["seconds"] + 1e-12
    assert streams[False] == streams[True]


def test_moe_engine_fused_gmm_greedy_identical():
    """Grouped-GEMM expert FFN on the serve path (prefill chunks AND the
    [B, k+1] verify batch): greedy streams token-identical with
    fused_moe_gmm on vs off."""
    from repro.configs.base import MoEConfig
    cfg = ModelConfig(
        name="tiny-moe", family="moe", num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16,
        dtype="float32",
        moe=MoEConfig(num_experts=4, num_experts_per_tok=2, d_ff_expert=32,
                      policy="harmoeny", num_foreign_slots=1))
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import MeshShape
    streams = {}
    for fused in (False, True):
        mesh = make_host_mesh(1, 1)
        ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
        model = build_model(cfg, ParallelConfig(attn_chunk=8, loss_chunk=8),
                            batch=2, seq_len=16, mesh_shape=ms, mesh=mesh)
        with mesh:
            params = model.init(jax.random.PRNGKey(1))
        ecfg = engine_config_for(cfg, max_slots=2, prompt_len=16,
                                 max_new_tokens=6, prefill_chunk=8,
                                 paged=True, kv_block_size=4,
                                 speculative_k=3,
                                 fused_paged_attention=fused,
                                 fused_moe_gmm=fused)
        eng = ServeEngine(model, params, ecfg, mesh=mesh,
                          clock=VirtualClock(0.05))
        reqs = poisson_requests(3, rate=50.0, vocab_size=cfg.vocab_size,
                                prompt_len=16, max_new_tokens=6, seed=5)
        outs, rep = captured_run(eng, reqs)
        assert rep["engine"]["fused_moe_gmm"] is fused
        streams[fused] = outs
    assert streams[False] == streams[True]
