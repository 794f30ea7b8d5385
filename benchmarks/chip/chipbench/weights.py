"""Seeded weights of the benchmark's mixture-of-experts models.

The benchmark, not the program, decides what the weights are.  Every
weight is a function of the run's seed, a semantic name and its indices
(layer, expert), so the served model and the plain reference read the
same numbers without either taking anything from the other:

* ``fill_program_tree`` fills the program's parameter tree (its leaf
  paths and layout are the interface read here) in one jitted call on
  the device;
* ``layer`` and ``expert`` give the reference the same numbers, one layer
  or one expert at a time.

Matrices are drawn in float32, scaled, and rounded to the served type;
norm scales stay float32.  Norm scales are drawn too (the model applies
``1 + scale``), so the check exercises them.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1
EMBED_STD = 0.02
ROUTER_STD = 0.02


@dataclass(frozen=True)
class MoEDims:
    """Widths of a decoder-only MoE transformer, from a configuration."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    d_expert: int
    d_shared: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str = "bfloat16"          # the served weights' type

    @classmethod
    def from_config(cls, doc: Dict[str, Any]) -> "MoEDims":
        heads = doc["num_attention_heads"]
        return cls(layers=doc["num_hidden_layers"],
                   d_model=doc["hidden_size"], heads=heads,
                   kv_heads=doc["num_key_value_heads"],
                   head_dim=doc.get("head_dim",
                                    doc["hidden_size"] // heads),
                   experts=doc["num_experts"],
                   top_k=doc["num_experts_per_tok"],
                   d_expert=doc["moe_intermediate_size"],
                   d_shared=doc["shared_expert_intermediate_size"],
                   vocab=doc["vocab_size"],
                   rope_theta=float(doc["rope_theta"]),
                   norm_eps=float(doc["rms_norm_eps"]),
                   dtype=doc["torch_dtype"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF),
                              int(words[1]) & 0x7FFFFFFF)


def _key(key, name: str, *idx):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    for i in idx:
        k = jax.random.fold_in(k, i)
    return k


def normal(key, name: str, idx, shape, std: float, dtype=jnp.bfloat16):
    """One weight: N(0, std^2) drawn in float32, rounded to ``dtype``."""
    x = jax.random.normal(_key(key, name, *idx), shape, jnp.float32) * std
    return x.astype(dtype)


def _std(dims: MoEDims) -> Dict[str, float]:
    d, f, F = dims.d_model, dims.d_expert, dims.d_shared
    return {"wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
            "wo": (dims.heads * dims.head_dim) ** -0.5,
            "w_in": (2.0 / d) ** 0.5, "w_gate": (2.0 / d) ** 0.5,
            "w_out": (2.0 / f) ** 0.5,
            "shared_w_in": (2.0 / d) ** 0.5,
            "shared_w_gate": (2.0 / d) ** 0.5,
            "shared_w_out": (2.0 / F) ** 0.5}


def _shapes(dims: MoEDims) -> Dict[str, tuple]:
    d, H, Hkv, hd = dims.d_model, dims.heads, dims.kv_heads, dims.head_dim
    f, F = dims.d_expert, dims.d_shared
    return {"norm1": (d,), "norm2": (d,), "wq": (d, H, hd),
            "wk": (d, Hkv, hd), "wv": (d, Hkv, hd), "wo": (H, hd, d),
            "router": (d, dims.experts), "shared_w_in": (d, F),
            "shared_w_gate": (d, F), "shared_w_out": (F, d),
            "w_in": (d, f), "w_gate": (d, f), "w_out": (f, d)}


def table(key, name: str, dims: MoEDims):
    """The [vocab, d_model] embedding or output head."""
    return normal(key, name, (), (dims.vocab, dims.d_model), EMBED_STD,
                  dims.dtype)


def final_norm(key, dims: MoEDims):
    return normal(key, "final_norm", (), (dims.d_model,), NORM_STD,
                  jnp.float32)


def layer_leaf(key, name: str, l, dims: MoEDims):
    """One per-layer leaf (norms, attention, router, shared expert)."""
    shape = _shapes(dims)[name]
    if name in ("norm1", "norm2"):
        return normal(key, name, (l,), shape, NORM_STD, jnp.float32)
    if name == "router":
        return normal(key, name, (l,), shape, ROUTER_STD, jnp.float32)
    return normal(key, name, (l,), shape, _std(dims)[name], dims.dtype)


def expert_leaf(key, name: str, l, e, dims: MoEDims):
    """One expert's ``w_in`` / ``w_gate`` [d, f] or ``w_out`` [f, d]."""
    return normal(key, name, (l, e), _shapes(dims)[name], _std(dims)[name],
                  dims.dtype)


LAYER_LEAVES = ("norm1", "norm2", "wq", "wk", "wv", "wo", "router",
                "shared_w_in", "shared_w_gate", "shared_w_out")
EXPERT_LEAVES = ("w_in", "w_gate", "w_out")


def layer(key, l, dims: MoEDims) -> Dict[str, Any]:
    """Every non-expert leaf of layer ``l``, in float32 (as served)."""
    return {n: layer_leaf(key, n, l, dims).astype(jnp.float32)
            for n in LAYER_LEAVES}


def expert(key, l, e, dims: MoEDims) -> Dict[str, Any]:
    """Expert ``e`` of layer ``l``, in float32 (as served)."""
    return {n: expert_leaf(key, n, l, e, dims).astype(jnp.float32)
            for n in EXPERT_LEAVES}


# ----------------------------------------------------------------------
# the program's parameter tree
# ----------------------------------------------------------------------
# program leaf path (after "stack/blocks/sub0/") -> semantic name
_PROGRAM_LAYER = {
    "norm1/scale": "norm1", "norm2/scale": "norm2",
    "attn/wq": "wq", "attn/wk": "wk", "attn/wv": "wv", "attn/wo": "wo",
    "moe/router": "router",
    "shared_mlp/w_in": "shared_w_in", "shared_mlp/w_gate": "shared_w_gate",
    "shared_mlp/w_out": "shared_w_out",
}
_PROGRAM_EXPERT = {"moe/w_in": "w_in", "moe/w_gate": "w_gate",
                   "moe/w_out": "w_out"}


def expert_of_row(rows: int, ranks: int) -> np.ndarray:
    """Expert held in each row of the program's stacked expert leaves.

    The program stores expert rows rank-major, and places expert ``e`` on
    rank ``e % ranks`` in local slot ``e // ranks``: row ``g * per_rank +
    j`` holds expert ``j * ranks + g``."""
    per_rank = rows // ranks
    g, j = np.divmod(np.arange(rows), per_rank)
    return (j * ranks + g).astype(np.int32)


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def fill_program_tree(shapes, dims: MoEDims, ranks: int, key):
    """The program's parameter tree (``shapes`` from ``jax.eval_shape`` of
    its ``init``) filled with this module's weights.  Run it under
    ``jax.jit`` with the program's output shardings.  A leaf this module
    does not know raises, so a change of the program's layout shows."""
    L = dims.layers

    def fill(path, sds):
        p = _path(path)
        dtype = sds.dtype
        if p in ("embed", "lm_head"):
            t = table(key, p, dims)
            pad = sds.shape[0] - dims.vocab        # padded vocabulary rows
            return jnp.concatenate(
                [t, jnp.zeros((pad, dims.d_model), dtype)]) if pad else t
        if p == "final_norm/scale":
            return final_norm(key, dims)
        sub = p.removeprefix("stack/blocks/sub0/")
        if sub in _PROGRAM_LAYER:
            name = _PROGRAM_LAYER[sub]
            x = jax.vmap(lambda l: layer_leaf(key, name, l, dims))(
                jnp.arange(L))
            if name == "router" and sds.shape[-1] > dims.experts:
                # padded experts are masked by the router; their columns
                # are never read
                x = jnp.pad(x, ((0, 0), (0, 0),
                                (0, sds.shape[-1] - dims.experts)))
            return x.astype(dtype)
        if sub in _PROGRAM_EXPERT:
            name = _PROGRAM_EXPERT[sub]
            ids = jnp.asarray(expert_of_row(sds.shape[1], ranks))
            return jax.vmap(lambda l: jax.vmap(
                lambda e: expert_leaf(key, name, l, e, dims))(ids))(
                    jnp.arange(L))
        raise KeyError(f"no benchmark weight for program leaf {p!r} "
                       f"{sds.shape}")

    def checked(path, sds):
        x = fill(path, sds)
        if x.shape != sds.shape or x.dtype != sds.dtype:
            raise ValueError(f"benchmark weight for {_path(path)!r} is "
                             f"{x.shape} {x.dtype}; the program wants "
                             f"{sds.shape} {sds.dtype}")
        return x

    return jax.tree_util.tree_map_with_path(checked, shapes)
