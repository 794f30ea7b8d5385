"""Jitted wrapper used by models/attention.py (layout adaptation and the
choice of kernel: the decode kernel for one query per row, the q-tiled
kernel for verify windows and prefill chunks)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.kernels.paged_attention.paged_attention import (
    decode_pages_per_step, paged_attention_kernel, paged_decode_kernel)

# target rows (q positions x rep) per kernel q tile; the auto choice
# keeps small windows (verify k<=4) on a single tile
_Q_TILE_ROWS = 512


def largest_block_divisor(n: int, cap: int = 128) -> int:
    """Largest divisor of ``n`` that is <= cap (>= 1 always exists).

    Used to view a slab scratch cache [B, S_max] as a pool of
    ``S_max // bs`` contiguous blocks per row so the same paged kernel
    can serve prefill continuation (see models/attention.py).
    """
    for bs in range(min(cap, n), 0, -1):
        if n % bs == 0:
            return bs
    return 1


def _kv_shards(Hkv: int, mesh: Optional[jax.sharding.Mesh]) -> int:
    """Devices the kv heads split over: the mesh's ``model`` axis where
    it divides the kv head count, else 1 (every device runs all heads)."""
    if mesh is None or mesh.size == 1:
        return 1
    ep = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    return ep if Hkv % ep == 0 else 1


def decode_plan(k_pool: jnp.ndarray, *, block_size: int, n_blocks: int,
                mesh: Optional[jax.sharding.Mesh] = None) -> dict:
    """The decode kernel a one-query call of ``paged_attention`` runs on
    this pool, and its pages per grid step (from each device's local kv
    heads), for the attention dispatch log."""
    Hkv, hd = k_pool.shape[2], k_pool.shape[3]
    row_bytes = Hkv // _kv_shards(Hkv, mesh) * hd * k_pool.dtype.itemsize
    return {"kernel": "paged_decode",
            "pages_per_step": decode_pages_per_step(n_blocks, block_size,
                                                    row_bytes)}


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    block_table: jnp.ndarray, cache_len: jnp.ndarray, *,
                    block_size: int, softcap: float = 0.0,
                    q_tile: Optional[int] = None,
                    interpret: bool = False,
                    mesh: Optional[jax.sharding.Mesh] = None) -> jnp.ndarray:
    """Model-layout entry: q [B, S, H, hd] with S >= 1 query positions
    (S = 1 is plain decode; S = k + 1 is a speculative-verify window;
    S = chunk is a prefill chunk — always causal within the window);
    k_pool/v_pool [1, P, Hkv, hd] *physical* pools with
    P = num_blocks * block_size (the serve engine's paged cache leaves);
    block_table [B, n_blocks] int32; cache_len scalar or per-row [B] —
    the total valid length INCLUDING the S window positions (query i
    sits at absolute position ``cache_len - S + i``) -> [B, S, H, hd].

    The pool's KV axis is viewed as [num_blocks, block_size] (pure
    reshape, no copy) and q as [B, Hkv, S * rep, hd] (query i, q head
    h = g * rep + r at row i * rep + r — the ``_repeat_kv`` head order per
    query), so the kernel can index whole physical blocks and handle GQA
    and the query window in its index maps and mask.

    S = 1 runs the decode kernel (``paged_decode_kernel``: one grid step
    per row and page group over all kv heads, q viewed [B, Hkv, rep,
    hd]); S > 1 runs the q-tiled kernel.  ``q_tile`` applies to the
    latter: queries per kernel q tile, by default all of S when
    S * rep fits one ~512-row tile, else ~512 // rep; S is zero-padded at
    the deep end up to a tile multiple (ragged last tile) and the padded
    outputs are dropped here.

    On a multi-device ``mesh`` the kernel runs per device under
    ``shard_map`` (XLA cannot partition a Mosaic call): q heads and the
    pools' kv heads split over the ``model`` axis where the kv head
    count divides it — the layout the head-sharded projections and the
    engine's placed pool already have — else every device runs all
    heads on replicated operands.
    """
    if mesh is not None and mesh.size > 1:
        heads = PartitionSpec(
            None, None,
            "model" if _kv_shards(k_pool.shape[2], mesh) > 1 else None)
        local = functools.partial(paged_attention, block_size=block_size,
                                  softcap=softcap, q_tile=q_tile,
                                  interpret=interpret)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(heads, heads, heads, PartitionSpec(),
                                       PartitionSpec()),
                             out_specs=heads, check_vma=False)(
            q, k_pool, v_pool, block_table, cache_len)
    B, S, H, hd = q.shape
    P, Hkv = k_pool.shape[1], k_pool.shape[2]
    rep = H // Hkv
    num_blocks = P // block_size
    assert num_blocks * block_size == P, (P, block_size)
    kp = k_pool[0].reshape(num_blocks, block_size, Hkv, hd)
    vp = v_pool[0].reshape(num_blocks, block_size, Hkv, hd)
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                          (B,))
    bt = jnp.asarray(block_table, jnp.int32)
    if S == 1:
        out = paged_decode_kernel(q.reshape(B, Hkv, rep, hd), kp, vp, bt, cl,
                                  block_size=block_size, softcap=softcap,
                                  interpret=interpret)
        return out.reshape(B, 1, H, hd)
    if q_tile is None:
        q_tile = S if S * rep <= _Q_TILE_ROWS else max(1, _Q_TILE_ROWS // rep)
    q_tile = min(q_tile, S)
    q_pad = -(-S // q_tile) * q_tile
    # [B, S, Hkv, rep, hd] -> [B, Hkv, S, rep, hd] -> [B, Hkv, S*rep, hd]
    qk = q.reshape(B, S, Hkv, rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, S * rep, hd)
    if q_pad > S:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, (q_pad - S) * rep), (0, 0)))
    out = paged_attention_kernel(qk, kp, vp, bt, cl,
                                 block_size=block_size, softcap=softcap,
                                 q_len=S, q_tile=q_tile, rep=rep,
                                 interpret=interpret)
    return out[:, :, :S * rep].reshape(B, Hkv, S, rep, hd) \
        .transpose(0, 2, 1, 3, 4).reshape(B, S, H, hd)
