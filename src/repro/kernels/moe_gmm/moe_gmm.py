"""Pallas TPU kernel: fused grouped expert FFN (the MoE compute hot-spot).

Computes, per block-aligned group g (one expert slot) over the dispatch
buffer:  out = act(x @ w_in[g]) [* silu(x @ w_gate[g])] @ w_out[g]

Design (TPU-native adaptation of the paper's async expert fetching, one level
down the memory hierarchy — DESIGN.md §2):

  * grid = (m_tiles, f_tiles): every m-tile belongs to EXACTLY one group
    because the dispatch buffer aligns group starts to ``block_m``
    (dispatch.py); the tile->group map rides in as a *scalar-prefetch*
    operand driving the weight BlockSpec index_map, so the Pallas pipeline
    streams each tile's expert-weight blocks HBM->VMEM with double buffering
    while the previous tile computes — the kernel-level analogue of
    "fetch the next expert while the current one runs" (paper §4.3).
  * the hidden dimension f is tiled by ``block_f`` and accumulated in an
    f32 VMEM scratch: elementwise activations commute with f-blocking, so
    the [m, f] intermediate is NEVER materialized in HBM (pure-XLA MoE
    implementations write it out — this is the kernel's memory-roofline win).
  * MXU alignment: block_m = 128, block_f a multiple of 128 (or all of
    f), d assumed 128-aligned (model configs pad).
  * VMEM: one grid step double-buffers the x/out tiles and the weight
    tiles and keeps an f32 accumulator; ``pick_block_f`` takes the largest
    legal block_f whose step fits ``VMEM_TILE_BUDGET``, and the kernel
    raises the compiler's scoped-VMEM limit to what that step needs.

Zero-padding rows inside a group produce exact zeros (act(0)=0 for
gelu/silu/relu and 0 * w = 0), so no masking is needed for correctness.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Bytes of VMEM one grid step may hold.  A v5e core has 128 MiB of VMEM;
# the compiler's default scoped limit is 16 MiB, which Qwen1.5-MoE's
# whole-f tile (d 2048, f 1408, gated, bf16: ~39 MB) and Mixtral's
# block_f 512 tile (d 4096) both exceed.  The rest of VMEM is left to the
# compiler's own scratch.
VMEM_TILE_BUDGET = 64 * 2 ** 20
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def tile_vmem_bytes(block_m: int, block_f: int, d: int, itemsize: int,
                    gated: bool) -> int:
    """VMEM of one grid step: x and out tiles plus the 2 or 3 weight
    tiles, each double-buffered, the f32 accumulator, and the f32
    [block_m, block_f] intermediates."""
    n_w = 3 if gated else 2
    buffered = 2 * (2 * block_m * d + n_w * d * block_f) * itemsize
    return buffered + 4 * block_m * d + 4 * n_w * block_m * block_f


def pick_block_f(d: int, f: int, *, block_m: int, itemsize: int,
                 gated: bool, budget: int = VMEM_TILE_BUDGET) -> int:
    """The largest block_f the TPU can tile — all of f, or a multiple of
    128 dividing f — whose grid step fits ``budget``."""
    legal = [f] + [bf for bf in range((f - 1) // 128 * 128, 0, -128)
                   if f % bf == 0]
    for bf in legal:
        if tile_vmem_bytes(block_m, bf, d, itemsize, gated) <= budget:
            return bf
    raise ValueError(
        f"moe_gmm: no block_f tiles f={f} (d={d}, block_m={block_m}) "
        f"within {budget} bytes of VMEM")


def _apply_act(act: str, h):
    if act == "gelu":
        return jax.nn.gelu(h)
    if act == "relu":
        return jax.nn.relu(h)
    if act == "silu":
        return jax.nn.silu(h)
    raise ValueError(act)


def _kernel_gated(tile_group_ref, x_ref, w_gate_ref, w_in_ref, w_out_ref,
                  o_ref, acc_ref, *, act: str, n_f_tiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    h_up = jnp.dot(x, w_in_ref[0], preferred_element_type=jnp.float32)
    h_gate = jnp.dot(x, w_gate_ref[0], preferred_element_type=jnp.float32)
    h = _apply_act("silu", h_gate) * h_up
    acc_ref[...] += jnp.dot(h.astype(x.dtype), w_out_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_f_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_plain(tile_group_ref, x_ref, w_in_ref, w_out_ref,
                  o_ref, acc_ref, *, act: str, n_f_tiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    h = jnp.dot(x, w_in_ref[0], preferred_element_type=jnp.float32)
    h = _apply_act(act, h)
    acc_ref[...] += jnp.dot(h.astype(x.dtype), w_out_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_f_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm(x: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
            tile_group: jnp.ndarray, *,
            w_gate: Optional[jnp.ndarray] = None, act: str = "gelu",
            block_f: int, block_m: int = 128,
            interpret: bool = False) -> jnp.ndarray:
    """x [M, d] (M % block_m == 0); w_in/w_gate [G, d, f]; w_out [G, f, d];
    tile_group [M // block_m] int32 in [0, G).  ``block_f`` must divide f
    (``pick_block_f`` chooses one)."""
    M, d = x.shape
    G, _, f = w_in.shape
    assert M % block_m == 0, (M, block_m)
    n_m = M // block_m
    block_f = min(block_f, f)
    assert f % block_f == 0, (f, block_f)
    n_f = f // block_f
    vmem = tile_vmem_bytes(block_m, block_f, d, x.dtype.itemsize,
                           w_gate is not None)

    grid = (n_m, n_f)
    x_spec = pl.BlockSpec((block_m, d), lambda i, j, tg: (i, 0))
    w_in_spec = pl.BlockSpec((1, d, block_f), lambda i, j, tg: (tg[i], 0, j))
    w_out_spec = pl.BlockSpec((1, block_f, d), lambda i, j, tg: (tg[i], j, 0))
    o_spec = pl.BlockSpec((block_m, d), lambda i, j, tg: (i, 0))
    scratch = [pltpu.VMEM((block_m, d), jnp.float32)]

    if w_gate is not None:
        w_gate_spec = pl.BlockSpec((1, d, block_f),
                                   lambda i, j, tg: (tg[i], 0, j))
        kernel = functools.partial(_kernel_gated, act=act, n_f_tiles=n_f)
        in_specs = [x_spec, w_gate_spec, w_in_spec, w_out_spec]
        operands = (x, w_gate, w_in, w_out)
    else:
        kernel = functools.partial(_kernel_plain, act=act, n_f_tiles=n_f)
        in_specs = [x_spec, w_in_spec, w_out_spec]
        operands = (x, w_in, w_out)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=o_spec, scratch_shapes=scratch)
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        name="moe_gmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(int(vmem * 1.25)
                              if vmem > _DEFAULT_SCOPED_VMEM else None)),
        interpret=interpret)
    return fn(tile_group.astype(jnp.int32), *operands)
