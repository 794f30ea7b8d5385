"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.moe_gmm.ops import fused_expert_ffn, tile_group_map
from repro.kernels.moe_gmm.ref import moe_gmm_ref


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("sizes", [
    [16, 0, 24, 8],          # empty group in the middle
    [0, 0, 0, 48],           # all load on the last expert (heavy skew)
    [8, 8, 8, 8],            # uniform
    [48, 0, 0, 0],           # all load on the first expert
])
def test_moe_gmm_sweep(dtype, gated, sizes):
    bm, d, f, G = 8, 32, 64, 4
    M = 64
    key = jax.random.PRNGKey(0)
    sizes = jnp.array(sizes, jnp.int32)
    x = (jax.random.normal(key, (M, d)) * 0.5).astype(dtype)
    # zero rows beyond group content (dispatch buffer invariant)
    row_group = jnp.repeat(tile_group_map(sizes, M // bm, bm), bm)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)[:-1]])
    w_in = (jax.random.normal(jax.random.PRNGKey(1), (G, d, f)) * 0.1).astype(dtype)
    w_gate = (jax.random.normal(jax.random.PRNGKey(2), (G, d, f)) * 0.1).astype(dtype)
    w_out = (jax.random.normal(jax.random.PRNGKey(3), (G, f, d)) * 0.1).astype(dtype)
    kw = dict(w_gate=w_gate, act="silu") if gated else dict(act="gelu")
    out_k = fused_expert_ffn(x, w_in, w_out, sizes, block_m=bm, block_f=32,
                             interpret=True, **kw)
    tg = tile_group_map(sizes, M // bm, bm)
    out_r = moe_gmm_ref(x, w_in, w_out, tg, block_m=bm, **kw)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_moe_gmm_matches_grouped_ffn_ref():
    """The kernel and the XLA tile-scan reference (core/grouped_ffn) agree."""
    from repro.core.grouped_ffn import grouped_ffn_ref
    bm, d, f, G, M = 8, 16, 32, 3, 48
    sizes = jnp.array([16, 8, 24], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (M, d))
    w_in = jax.random.normal(jax.random.PRNGKey(1), (G, d, f)) * 0.1
    w_out = jax.random.normal(jax.random.PRNGKey(2), (G, f, d)) * 0.1
    a = fused_expert_ffn(x, w_in, w_out, sizes, act="gelu", block_m=bm,
                         block_f=16, interpret=True)
    b = grouped_ffn_ref(x, w_in, w_out, sizes, act="gelu", block_m=bm)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("d,f,want", [
    (2048, 1408, 1408),      # Qwen1.5-MoE: all of f in one ~38 MB tile
    (4096, 14336, 1024),     # Mixtral: largest 128-multiple under budget
])
def test_pick_block_f_within_vmem_budget(d, f, want):
    from repro.kernels.moe_gmm.moe_gmm import (VMEM_TILE_BUDGET,
                                               pick_block_f, tile_vmem_bytes)
    bf = pick_block_f(d, f, block_m=128, itemsize=2, gated=True)
    assert bf == want and f % bf == 0
    assert tile_vmem_bytes(128, bf, d, 2, True) <= VMEM_TILE_BUDGET
    with pytest.raises(ValueError, match="no block_f"):
        pick_block_f(d, f, block_m=128, itemsize=2, gated=True, budget=2 ** 20)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    (1, 4, 4, 64, 16),    # MHA
    (2, 4, 2, 128, 16),   # GQA rep=2
    (1, 8, 1, 64, 32),    # MQA
])
def test_flash_attention_sweep(dtype, causal, shape):
    B, H, Hkv, S, hd = shape
    q = (jax.random.normal(jax.random.PRNGKey(0), (B, H, S, hd))).astype(dtype)
    k = (jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, S, hd))).astype(dtype)
    v = (jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, S, hd))).astype(dtype)
    o_k = flash_attention_kernel(q, k, v, causal=causal, block_q=32,
                                 block_k=32, interpret=True)
    o_r = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_block_shape_independence():
    B, H, S, hd = 1, 2, 128, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, hd))
    outs = [flash_attention_kernel(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 32), (128, 64), (32, 128)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5)
