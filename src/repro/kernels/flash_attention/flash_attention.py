"""Pallas TPU kernel: causal flash attention (online softmax).

Grid (batch*q_heads, q_tiles, kv_tiles); running (m, l, acc) live in VMEM
scratch across the kv dimension; fully-masked kv tiles above the causal
diagonal are skipped with @pl.when (no MXU work, no VMEM traffic beyond the
pipelined block fetch). GQA is handled in the BlockSpec index_map
(q head h reads kv head h // rep) so K/V are never materialized per-q-head.

VMEM budget per step (block_q=block_k=512, hd=128, bf16):
q 128 kB + k/v 256 kB + acc/l/m f32 ~290 kB — far under 16 MB, leaving the
pipeline room to double-buffer K/V blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_q: int, block_k: int, n_kv: int,
            causal: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks entirely above the diagonal
    run = (not causal) or (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, block_q: int = 512,
                           block_k: int = 512,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, Sq, hd]; k/v: [B, Hkv, Sk, hd] -> [B, H, Sq, hd]."""
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_kv = Sq // block_q, Sk // block_k
    grid = (B * H, n_q, n_kv)
    scale = hd ** -0.5

    qr = q.reshape(B * H, Sq, hd)
    kr = k.reshape(B * Hkv, Sk, hd)
    vr = v.reshape(B * Hkv, Sk, hd)

    def kv_index(bh, qi, kj):
        b, h = bh // H, bh % H
        return (b * Hkv + h // rep, kj, 0)

    fn = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, n_kv=n_kv, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        name="flash_attention",
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret)
    return fn(qr, kr, vr).reshape(B, H, Sq, hd)
