"""Jitted wrapper: grouped-FFN entry point used by core/grouped_ffn.py."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.moe_gmm.moe_gmm import moe_gmm


def tile_group_map(group_sizes_padded: jnp.ndarray, n_tiles: int,
                   block_m: int) -> jnp.ndarray:
    """tile index -> group id from block-aligned group extents.

    Tiles beyond the last group map to the final group (their rows are
    zeros, producing exact zeros)."""
    offsets = jnp.cumsum(group_sizes_padded)         # end offset per group
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_m
    tg = jnp.searchsorted(offsets, starts, side="right").astype(jnp.int32)
    return jnp.minimum(tg, group_sizes_padded.shape[0] - 1)


def fused_expert_ffn(x: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
                     group_sizes_padded: jnp.ndarray, *,
                     block_f: int, w_gate: Optional[jnp.ndarray] = None,
                     act: str = "gelu", block_m: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    M = x.shape[0]
    n_tiles = M // block_m
    tg = tile_group_map(group_sizes_padded, n_tiles, block_m)
    return moe_gmm(x, w_in, w_out, tg, w_gate=w_gate, act=act,
                   block_m=block_m, block_f=block_f, interpret=interpret)
