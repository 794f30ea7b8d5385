"""Pallas TPU kernels: paged attention (fused block-table gather).

Two kernels read K/V through the serve engine's block table inside the
kernel, so the [B, L_max] logical index gather and the per-q-head K/V
repeat of the XLA reference (``models.attention.paged_decode_attention``)
never materialize.  Both read the pool through the view ``[num_blocks,
block_size, Hkv * hd]``: one page of every kv head is one contiguous run
of HBM, and kv head ``g`` is its lanes ``[g * hd, (g + 1) * hd)``.

**Decode** (``paged_decode_kernel``, one query per row): grid (batch,
page groups).  One grid step covers one row, all of its kv heads and
``P`` consecutive pages of its block table.  The pools stay in HBM
(``memory_space=pl.ANY``) and the kernel copies the pages itself: the
step for group ``i`` of row ``b`` waits for pages ``block_table[b, i*P :
i*P + live]`` (``live`` = the pages of the group the row has) in one
half of a double buffer, while the DMAs of the next live group, of this
row or the next, fill the other half.  A group past the row's length
issues no copy and does no work: its only cost is the grid step.  A
loop over the kv heads runs, per head, one ``[rep, hd] x [P*bs, hd]^T``
and one ``[rep, P*bs] x [P*bs, hd]`` MXU pass on the head's lane slice,
with the online softmax's (m, l, acc) in VMEM scratch, reset at group 0
and flushed at the row's last group.  The page copies and the heads are
loops, not unrolled, so the kernel lowers about as fast as the q-tiled
one (lowering runs at every process start, compile cache or not).  ``P``
comes from the shapes (``decode_pages_per_step``): the most pages whose K
and V fit ``DECODE_STEP_BYTES``.

**Q-tiled** (``paged_attention_kernel``, speculative verify and chunked /
prefix-tail prefill): grid (batch, kv_heads, q tiles, kv block tiles);
the [B, n_blocks] block table and the [B] valid lengths ride in as
scalar-prefetch operands, so kv tile j of row b fetches physical block
``block_table[b, j]`` straight out of the pool in the K/V BlockSpec
index_map.  Queries are split into tiles of ``q_blk`` positions x
``rep`` q-heads-per-kv-head rows; the kv-tile dimension is innermost, so
each (b, g, t) program walks the whole block chain with running (m, l,
acc) in VMEM scratch, reset at kv tile 0 and flushed at the last kv
tile.  Causal pruning is two-sided: kv tiles past a row's valid
``length`` are dead for every q tile, and kv tiles past q tile t's
deepest query (absolute position ``length - q_len + min((t+1)*q_blk,
q_len) - 1``) are dead for that q tile — both are skipped with @pl.when
(no MXU work), and the K/V index_map *clamps* pruned tiles to the last
live block so Pallas's same-block revisiting elides their pipeline
copies (no redundant HBM traffic on the causal tail).

Ragged last q tiles: ``q_len`` need not be a multiple of ``q_blk`` — the
wrapper zero-pads queries at the deep end and rows past ``q_len`` are
masked with ``kv_pos < length`` (their causal bound lies past the valid
range), producing finite garbage the wrapper drops.

Masks are parameterized by absolute position: query i sits at
``length - q_len + i`` where ``length`` (= cache_len) INCLUDES the
window, so a prefix-tail prefill that restarts mid-sequence at offset
``q_offset`` passes ``cache_len = q_offset + q_len`` and masks exactly
like ``chunked_attention(..., q_offset=q_offset)``.

VMEM budget per step.  Decode (Qwen1.5-MoE: 16 kv heads x 128, 16-token
pages, bf16, so 64 kB a page and ``P = 32``): K and V buffers 2 x 2 x
2 MB, q/out 2 x 16 x 4 kB (a [1, 128] row pads to an [8, 128] tile),
acc/l/m f32 3 x 64 kB: about 8.4 MB of the 16 MB scoped default.
Q-tiled (block_size=16, hd=128, rep=8, q_blk=64, bf16): q/out 2x128 kB +
k/v 2x4 kB + acc/l/m f32 ~260 kB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# bytes of K and V pages one decode grid step computes on (one half of
# its double buffer); sets the pages per step from the page size.  4 MB
# is P = 32 at Qwen1.5-MoE's 64 kB pages: the fastest of P = 4..32 on a
# v5e at the chat cell's decode shape (PERF.md, PR 14)
DECODE_STEP_BYTES = 4 * 1024 * 1024


class PagedLayoutError(ValueError):
    """The pool's head dim cannot be tiled for the TPU: the kernels read
    one kv head's ``[block_size, hd]`` lanes of the ``[num_blocks,
    block_size, Hkv * hd]`` pool view, so ``hd`` must fill whole
    128-lane tiles."""


def _check_layout(Hkv: int, hd: int, interpret: bool) -> None:
    if not interpret and Hkv > 1 and hd % 128:
        raise PagedLayoutError(
            f"paged attention on the TPU needs head_dim % 128 == 0 to read "
            f"one kv head's lanes of the [num_blocks, block_size, Hkv*hd] "
            f"pool view; got head_dim {hd} with {Hkv} kv heads")


def decode_pages_per_step(n_blocks: int, block_size: int,
                          row_bytes: int) -> int:
    """Pages per decode grid step: the most whose K and V (``block_size``
    rows of ``row_bytes`` each per page) fit ``DECODE_STEP_BYTES``, at
    least 1 and at most the table's ``n_blocks``."""
    page_bytes = block_size * row_bytes
    return max(1, min(n_blocks, DECODE_STEP_BYTES // (2 * page_bytes)))


def _decode_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                   v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
                   block_size: int, pages: int, n_blocks: int,
                   n_groups: int, softcap: float, scale: float):
    b, i = pl.program_id(0), pl.program_id(1)
    rows = pl.num_programs(0)
    _, Hkv, rep, hd = q_ref.shape
    span = pages * block_size

    def row_pages(row):
        # a length past the table reads no page beyond it
        return jnp.minimum((len_ref[row] + block_size - 1) // block_size,
                           n_blocks)

    def groups(row):
        # groups the DMA schedule visits: every row has group 0, so the
        # group after a row's last is always group 0 of the next row (a
        # row of length 0 copies nothing there, and its output is 0:
        # every score is masked and every V row zeroed)
        return jnp.maximum((row_pages(row) + pages - 1) // pages, 1)

    def each_copy(row, grp, slot, act):
        # one K and one V copy for each page of the group the row has
        def page_copies(p, carry):
            page = bt_ref[row, grp * pages + p]
            dst = pl.ds(pl.multiple_of(p * block_size, block_size),
                        block_size)
            for hbm, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                  (v_hbm, v_buf, sems.at[1, slot])):
                act(pltpu.make_async_copy(hbm.at[page], buf.at[slot, dst],
                                          sem))
            return carry
        live = jnp.clip(row_pages(row) - grp * pages, 0, pages)
        jax.lax.fori_loop(0, live, page_copies, 0)

    def start(row, grp, slot):
        each_copy(row, grp, slot, lambda c: c.start())

    def wait(row, grp, slot):
        each_copy(row, grp, slot, lambda c: c.wait())

    length = len_ref[b]
    n_live = groups(b)

    @pl.when((b == 0) & (i == 0))
    def _prime():
        slot_ref[0] = 0
        start(0, 0, 0)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(slot, tail: bool):
        kv_pos = i * span + jax.lax.broadcasted_iota(jnp.int32, (rep, span), 1)
        valid = kv_pos < length
        if tail:
            # pages past the row's end were not copied: their buffer rows
            # hold whatever was there, which p = 0 must not multiply
            v_valid = i * span + jax.lax.broadcasted_iota(
                jnp.int32, (span, hd), 0) < length

        def head(g, carry):
            lanes = pl.ds(pl.multiple_of(g * hd, hd), hd)
            k = k_buf[slot, :, lanes]                       # [span, hd]
            v = v_buf[slot, :, lanes]
            if tail:
                v = jnp.where(v_valid, v, jnp.zeros_like(v))
            q = q_ref[0, g].astype(k.dtype)                 # [rep, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, _NEG_INF)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_prev * corr + p.sum(axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new
            return carry
        jax.lax.fori_loop(0, Hkv, head, 0)

    @pl.when(i < n_live)
    def _group():
        slot = slot_ref[0]
        last = i + 1 == n_live
        next_b = jnp.where(last, b + 1, b)

        @pl.when(next_b < rows)
        def _prefetch():
            start(next_b, jnp.where(last, 0, i + 1), 1 - slot)
            slot_ref[0] = 1 - slot

        wait(b, i, slot)

        @pl.when(last)
        def _tail():
            attend(slot, tail=True)

        @pl.when(jnp.logical_not(last))
        def _body():
            attend(slot, tail=False)

    @pl.when(i == n_groups - 1)
    def _flush():
        for g in range(Hkv):
            o_ref[0, g] = (acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)
                           ).astype(o_ref.dtype)


def paged_decode_kernel(q: jnp.ndarray, k_pool: jnp.ndarray,
                        v_pool: jnp.ndarray, block_table: jnp.ndarray,
                        cache_len: jnp.ndarray, *, block_size: int,
                        softcap: float = 0.0,
                        pages_per_step: Optional[int] = None,
                        interpret: bool = False) -> jnp.ndarray:
    """One query per row.  q: [B, Hkv, rep, hd] (q head ``g * rep + r``
    at ``[g, r]``); k_pool/v_pool: [num_blocks, block_size, Hkv, hd];
    block_table: [B, n_blocks] int32 (entries past a row's chain must
    point at a valid physical block — the pool's null-block convention);
    cache_len: [B] int32 valid lengths INCLUDING the query's own position
    -> [B, Hkv, rep, hd].

    ``pages_per_step`` defaults to ``decode_pages_per_step`` of the
    shapes; a ragged last group (``n_blocks % P != 0``) is fine.
    Compiling for the TPU needs ``hd % 128 == 0`` (or one kv head), else
    ``PagedLayoutError``, and ``block_size`` a multiple of the pool
    dtype's sublane tile (16 rows in bf16) for the page copies.
    """
    B, Hkv, rep, hd = q.shape
    n_blocks = block_table.shape[1]
    assert k_pool.shape[1] == block_size and k_pool.shape[2] == Hkv
    _check_layout(Hkv, hd, interpret)
    num_pool_blocks = k_pool.shape[0]
    width = Hkv * hd
    k_view = k_pool.reshape(num_pool_blocks, block_size, width)
    v_view = v_pool.reshape(num_pool_blocks, block_size, width)
    pages = pages_per_step or decode_pages_per_step(
        n_blocks, block_size, width * k_pool.dtype.itemsize)
    n_groups = -(-n_blocks // pages)
    span = pages * block_size

    def row_index(b, i, bt, cl):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, n_groups),
        in_specs=[
            pl.BlockSpec((1, Hkv, rep, hd), row_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rep, hd), row_index),
        scratch_shapes=[
            pltpu.VMEM((2, span, width), k_pool.dtype),
            pltpu.VMEM((2, span, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hkv, rep, 1), jnp.float32),
            pltpu.VMEM((Hkv, rep, 1), jnp.float32),
            pltpu.VMEM((Hkv, rep, hd), jnp.float32),
        ])
    fn = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          pages=pages, n_blocks=n_blocks, n_groups=n_groups,
                          softcap=softcap, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        name="paged_attention",
        # a step prefetches the next step's pages: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret)
    return fn(block_table.astype(jnp.int32), cache_len.astype(jnp.int32),
              q, k_view, v_view)


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
    # never extend the bound)
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
        # q tile t, row r holds query i = t * q_blk + r // rep at
        # absolute position length - q_len + i; causal within the
        # window.  Padded rows (i >= q_len) have a causal bound past the
        # valid range, so they additionally need kv_pos < length to stay
        # off null-block garbage (a no-op for real rows, whose q_pos <
        # length already).
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = length - q_len + t * q_blk + row // rep
        s = jnp.where((kv_pos <= q_pos) & (kv_pos < length), s, _NEG_INF)
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

    ``q_tile=None`` means one tile covering all q_len queries (no
    padding, QR == q_len * rep); ``rep`` is then derived from the shapes.

    A K/V block is ``(1, block_size, hd)`` of the ``[num_blocks,
    block_size, Hkv * hd]`` pool view at lane offset ``g * hd``, whose
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
    _check_layout(Hkv, hd, interpret)
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
