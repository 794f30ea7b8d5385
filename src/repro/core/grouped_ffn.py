"""Grouped expert FFN over the block-aligned dispatch buffer.

Reference (XLA) path: a lax.scan over block_m tiles, each tile dynamically
gathering its group's weight matrices — the XLA twin of the Pallas kernel's
grid loop. Exact compute (2*M*d*f per matmul, no per-group masked
overcompute: jax.lax.ragged_dot was rejected because its non-TPU lowering
materializes dense [G, M, f] masked intermediates — 8x compute and ~4 GB
buffers on mixtral prefill), differentiable, CPU-lowerable.

The Pallas path (kernels/moe_gmm) fuses the matmuls and double-buffers
weight tiles HBM->VMEM (the paper's async-fetch analogue one level down the
hierarchy); selected via ``use_pallas`` on TPU targets and validated in
interpret mode against this reference.

The grouped buffer rows beyond each group's real size are zeros; every
activation used here maps 0 -> 0, so padding contributes exact zeros.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _act(name: str, x: jnp.ndarray) -> jnp.ndarray:
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    if name == "silu":
        return jax.nn.silu(x)
    raise ValueError(name)


def tile_group_map(group_sizes_padded: jnp.ndarray, n_tiles: int,
                   block_m: int) -> jnp.ndarray:
    """tile index -> group id from block-aligned group extents. Tiles beyond
    the last group clamp to the final group (their rows are zeros)."""
    offsets = jnp.cumsum(group_sizes_padded)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_m
    tg = jnp.searchsorted(offsets, starts, side="right").astype(jnp.int32)
    return jnp.minimum(tg, group_sizes_padded.shape[0] - 1)


def grouped_ffn_ref(x: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
                    group_sizes_padded: jnp.ndarray, *,
                    w_gate: Optional[jnp.ndarray] = None,
                    act: str = "gelu", block_m: int = 128) -> jnp.ndarray:
    """x [M, d] (M % block_m == 0, groups block-aligned); w_in/w_gate
    [G, d, f]; w_out [G, f, d]."""
    M, d = x.shape
    n_tiles = M // block_m
    tg = tile_group_map(group_sizes_padded, n_tiles, block_m)
    xt = x.reshape(n_tiles, block_m, d)

    def step(_, inp):
        xi, g = inp
        h = xi @ w_in[g]
        if w_gate is not None:
            h = _act("silu", xi @ w_gate[g]) * h
        else:
            h = _act(act, h)
        return None, (h.astype(xi.dtype) @ w_out[g])

    _, yt = jax.lax.scan(jax.checkpoint(step), None, (xt, tg))
    return yt.reshape(M, d)


def grouped_ffn(x: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
                group_sizes_padded: jnp.ndarray, *,
                w_gate: Optional[jnp.ndarray] = None, act: str = "gelu",
                use_pallas: bool = False, interpret: bool = False,
                block_m: int = 128) -> jnp.ndarray:
    if not use_pallas:
        return grouped_ffn_ref(x, w_in, w_out, group_sizes_padded,
                               w_gate=w_gate, act=act, block_m=block_m)
    from repro.kernels.moe_gmm.moe_gmm import pick_block_f
    from repro.kernels.moe_gmm.ops import fused_expert_ffn
    block_f = pick_block_f(x.shape[1], w_in.shape[2], block_m=block_m,
                           itemsize=x.dtype.itemsize,
                           gated=w_gate is not None)
    return fused_expert_ffn(x, w_in, w_out, group_sizes_padded,
                            block_f=block_f, w_gate=w_gate, act=act,
                            block_m=block_m, interpret=interpret)
