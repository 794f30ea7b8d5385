"""Pallas TPU kernel: paged attention (fused block-table gather) with
q-tiling — one kernel for decode, speculative verify, and chunked /
prefix-tail prefill.

One row's query token(s) attend over that row's KV block chain *through
the block table inside the kernel*: grid (batch, kv_heads, q tiles, kv
block tiles); the [B, n_blocks] block table and the [B] valid lengths
ride in as scalar-prefetch operands, so kv tile j of row b fetches
physical block ``block_table[b, j]`` straight out of the pool in the K/V
BlockSpec index_map — the [B, L_max] logical index gather and the
per-q-head K/V repeat of the XLA reference
(``models.attention.paged_decode_attention``) never materialize.

Q-tiling (flash-style, both axes): queries are split into tiles of
``q_blk`` positions x ``rep`` q-heads-per-kv-head rows; the kv-tile
dimension is innermost, so each (b, g, t) program walks the whole block
chain with running (m, l, acc) in VMEM scratch (online softmax), reset
at kv tile 0 and flushed at the last kv tile.  Causal pruning is
two-sided: kv tiles past a row's valid ``length`` are dead for every q
tile, and kv tiles past q tile t's deepest query (absolute position
``length - q_len + min((t+1)*q_blk, q_len) - 1``) are dead for that q
tile — both are skipped with @pl.when (no MXU work), and the K/V
index_map *clamps* pruned tiles to the last live block so Pallas's
same-block revisiting elides their pipeline copies (no redundant HBM
traffic on the causal tail).

Ragged last tiles: ``q_len`` need not be a multiple of ``q_blk`` — the
wrapper zero-pads queries at the deep end and rows past ``q_len`` are
masked with ``kv_pos < length`` (their causal bound lies past the valid
range), producing finite garbage the wrapper drops.

Masks are parameterized by absolute position: query i sits at
``length - q_len + i`` where ``length`` (= cache_len) INCLUDES the
window, so a prefix-tail prefill that restarts mid-sequence at offset
``q_offset`` passes ``cache_len = q_offset + q_len`` and masks exactly
like ``chunked_attention(..., q_offset=q_offset)``.  ``q_len == 1``
takes a static branch with the original single-query mask
(``kv_pos < length``) so the decode path stays bit-identical to the
pre-q-tiling kernel.

VMEM budget per step (block_size=16, hd=128, rep=8, q_blk=64, bf16):
q/out 2x128 kB + k/v 2x4 kB + acc/l/m f32 ~260 kB — far under 16 MB, so
the pipeline double-buffers block fetches freely; per-step compute is
one [q_blk * rep, hd] x [hd, bs] and one [q_blk * rep, bs] x [bs, hd]
MXU pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


class PagedLayoutError(ValueError):
    """The pool's head dim cannot be tiled for the TPU: the kernel reads
    one kv head's ``[block_size, hd]`` slice of the ``[num_blocks,
    block_size, Hkv * hd]`` pool view, so ``hd`` must fill whole
    128-lane tiles."""


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, block_size: int, n_blocks: int, softcap: float,
            scale: float, q_len: int, q_blk: int, rep: int):
    b = pl.program_id(0)
    t = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    # deepest *real* query of q tile t (padded rows lie past q_len and
    # never extend the bound); at q_len == q_blk == 1 this reduces to
    # the original decode bound j * block_size < length
    hi = length - q_len + jnp.minimum((t + 1) * q_blk, q_len) - 1

    # ragged lengths / null-block tail / causal tail: kv tiles with no
    # position visible to this q tile are skipped entirely (no MXU work,
    # no softmax update; their pipeline fetch is elided by the clamped
    # index_map below).
    @pl.when(j * block_size <= hi)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [q_blk*rep, hd]
        k = k_ref[0].astype(jnp.float32)                   # [bs, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kv_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        if q_len == 1:
            # single-query decode: the original mask, kept on its own
            # static branch so this path stays bit-identical
            s = jnp.where(kv_pos < length, s, _NEG_INF)
        else:
            # q tile t, row r holds query i = t * q_blk + r // rep at
            # absolute position length - q_len + i; causal within the
            # window.  Padded rows (i >= q_len) have a causal bound past
            # the valid range, so they additionally need kv_pos < length
            # to stay off null-block garbage (a no-op for real rows,
            # whose q_pos < length already).
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            q_pos = length - q_len + t * q_blk + row // rep
            s = jnp.where((kv_pos <= q_pos) & (kv_pos < length), s,
                          _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_kernel(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           cache_len: jnp.ndarray, *, block_size: int,
                           softcap: float = 0.0, q_len: int = 1,
                           q_tile: Optional[int] = None,
                           rep: Optional[int] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, Hkv, q_pad * rep, hd] (query i, q-head r of kv head g at row
    ``i * rep + r``), where ``q_pad = ceil(q_len / q_tile) * q_tile`` —
    rows past ``q_len * rep`` are zero padding whose outputs the caller
    drops; k_pool/v_pool: [num_blocks, block_size, Hkv, hd]; block_table:
    [B, n_blocks] int32 (entries past a row's chain must point at a valid
    physical block — the pool's null-block convention); cache_len: [B]
    int32 valid lengths INCLUDING the q_len window (query i sits at
    absolute position ``cache_len - q_len + i``)
    -> [B, Hkv, q_pad * rep, hd].

    ``q_tile=None`` means one tile covering all q_len queries (the
    pre-q-tiling layout: no padding, QR == q_len * rep); ``rep`` is then
    derived from the shapes.

    The pool is read through the view ``[num_blocks, block_size, Hkv *
    hd]`` (a free merge of the two contiguous trailing dims): a K/V block
    is then ``(1, block_size, hd)`` at lane offset ``g * hd``, whose
    trailing dims the TPU can tile, where a ``(1, block_size, 1, hd)``
    block of the 4-D pool is refused (a second-minor block dim of 1
    against ``Hkv``).  Compiling for the TPU therefore needs
    ``hd % 128 == 0`` (or a single kv head); otherwise
    ``PagedLayoutError`` is raised.
    """
    B, Hkv, QR, hd = q.shape
    if q_tile is None:
        q_tile = q_len
    if rep is None:
        assert QR % q_len == 0, (QR, q_len)
        rep = QR // q_len
    tile_rows = q_tile * rep
    assert QR % tile_rows == 0, (QR, q_tile, rep)
    n_q_tiles = QR // tile_rows
    assert n_q_tiles * q_tile >= q_len, (n_q_tiles, q_tile, q_len)
    n_blocks = block_table.shape[1]
    assert k_pool.shape[1] == block_size and k_pool.shape[2] == Hkv
    if not interpret and Hkv > 1 and hd % 128:
        raise PagedLayoutError(
            f"paged attention on the TPU needs head_dim % 128 == 0 to read "
            f"one kv head's lanes of the [num_blocks, block_size, Hkv*hd] "
            f"pool view; got head_dim {hd} with {Hkv} kv heads")
    num_pool_blocks = k_pool.shape[0]
    k_view = k_pool.reshape(num_pool_blocks, block_size, Hkv * hd)
    v_view = v_pool.reshape(num_pool_blocks, block_size, Hkv * hd)
    scale = hd ** -0.5
    grid = (B, Hkv, n_q_tiles, n_blocks)

    def q_index(b, g, t, j, bt, cl):
        return (b, g, t, 0)

    def kv_index(b, g, t, j, bt, cl):
        # clamp dead kv tiles (past the row's length or past q tile t's
        # causal bound) to the last live tile: consecutive grid steps
        # then map to the same physical block and Pallas elides the copy
        hi = cl[b] - q_len + jnp.minimum((t + 1) * q_tile, q_len) - 1
        jj = jnp.clip(jnp.minimum(j, hi // block_size), 0, n_blocks - 1)
        return (bt[b, jj], 0, g)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile_rows, hd), q_index),
            pl.BlockSpec((1, block_size, hd), kv_index),
            pl.BlockSpec((1, block_size, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_rows, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((tile_rows, 1), jnp.float32),
            pltpu.VMEM((tile_rows, 1), jnp.float32),
            pltpu.VMEM((tile_rows, hd), jnp.float32),
        ])
    fn = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, n_blocks=n_blocks,
                          softcap=softcap, scale=scale, q_len=q_len,
                          q_blk=q_tile, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, QR, hd), q.dtype),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret)
    return fn(block_table.astype(jnp.int32), cache_len.astype(jnp.int32),
              q, k_view, v_view)
