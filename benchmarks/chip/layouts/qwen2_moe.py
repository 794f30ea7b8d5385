"""Layout of a Qwen1.5-MoE decoder (``model_type`` qwen2_moe): every layer
has multi-head attention with RoPE, a softmax top-k router over routed
SwiGLU experts and one shared SwiGLU expert; untied embedding and output
head.

It owns everything about the model's shape that the harness reads:

* ``Dims``, the widths, with the FLOPs of one token (``step_mfu``);
* ``from_config`` / ``from_program``: the widths as the configuration
  file states them (the published ``config.json`` keys) and as the
  program's ``ModelConfig`` states them; a run is refused where the two
  differ.  ``FROM_FILE`` lists what the program cannot state;
* ``program_config``: the program's configuration at what the file sets;
* ``fill_program_tree``: the program's parameter tree filled from the seed;
* ``layer``, ``expert``, ``table`` and ``final_norm``: the same draws,
  one layer or one expert at a time, for the reference.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.weights import (EMBED_STD, NORM_STD, ROUTER_STD,
                               expert_of_row, fill_tree, normal)


@dataclass(frozen=True)
class Dims:
    """Widths of a Qwen1.5-MoE decoder, from a configuration."""
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

    def token_flops(self, context: int, *, logits: bool) -> float:
        """Model FLOPs of one token through every layer at ``context``
        positions (itself included): the projections, the router, its
        top-k experts and the shared expert, causal attention over its
        context, and the output head where the token's logits are
        computed."""
        d, H, Hkv, hd = self.d_model, self.heads, self.kv_heads, self.head_dim
        per_layer = (d * H * hd * 2 + d * Hkv * hd * 2      # q, o; k, v
                     + d * self.experts                      # router
                     + self.top_k * 3 * d * self.d_expert    # experts
                     + 3 * d * self.d_shared)                # shared
        flops = 2.0 * per_layer * self.layers
        flops += 4.0 * H * hd * context * self.layers
        if logits:
            flops += 2.0 * d * self.vocab
        return flops


# fields of Dims that the program's ModelConfig does not state, taken from
# the configuration file, with the reason
FROM_FILE = {
    "norm_eps": "the program fixes RMSNorm's epsilon at 1e-6 "
                "(models/layers.py) and has no field for it",
}


def from_config(doc: Dict[str, Any]) -> Dims:
    heads = doc["num_attention_heads"]
    return Dims(layers=doc["num_hidden_layers"],
                d_model=doc["hidden_size"], heads=heads,
                kv_heads=doc["num_key_value_heads"],
                head_dim=doc.get("head_dim", doc["hidden_size"] // heads),
                experts=doc["num_experts"],
                top_k=doc["num_experts_per_tok"],
                d_expert=doc["moe_intermediate_size"],
                d_shared=doc["shared_expert_intermediate_size"],
                vocab=doc["vocab_size"],
                rope_theta=float(doc["rope_theta"]),
                norm_eps=float(doc["rms_norm_eps"]),
                dtype=doc["torch_dtype"])


def from_program(cfg) -> Dims:
    """The widths of the program's ``ModelConfig``; ``FROM_FILE`` fields
    are None."""
    moe = cfg.moe
    return Dims(layers=cfg.num_layers, d_model=cfg.d_model,
                heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim,
                experts=moe.num_experts, top_k=moe.num_experts_per_tok,
                d_expert=moe.d_ff_expert,
                d_shared=moe.num_shared_experts * moe.d_ff_expert,
                vocab=cfg.vocab_size, rope_theta=cfg.rope_theta,
                norm_eps=None, dtype=cfg.dtype)


def program_config(cfg, dims: Dims):
    """The program's configuration at the file's depth, RoPE base (the
    program's entry says 10,000; the published model 10^6) and type."""
    return cfg.replace(num_layers=dims.layers, rope_theta=dims.rope_theta,
                       dtype=dims.dtype)


def _std(dims: Dims) -> Dict[str, float]:
    d, f, F = dims.d_model, dims.d_expert, dims.d_shared
    return {"wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
            "wo": (dims.heads * dims.head_dim) ** -0.5,
            "w_in": (2.0 / d) ** 0.5, "w_gate": (2.0 / d) ** 0.5,
            "w_out": (2.0 / f) ** 0.5,
            "shared_w_in": (2.0 / d) ** 0.5,
            "shared_w_gate": (2.0 / d) ** 0.5,
            "shared_w_out": (2.0 / F) ** 0.5}


def _shapes(dims: Dims) -> Dict[str, tuple]:
    d, H, Hkv, hd = dims.d_model, dims.heads, dims.kv_heads, dims.head_dim
    f, F = dims.d_expert, dims.d_shared
    return {"norm1": (d,), "norm2": (d,), "wq": (d, H, hd),
            "wk": (d, Hkv, hd), "wv": (d, Hkv, hd), "wo": (H, hd, d),
            "router": (d, dims.experts), "shared_w_in": (d, F),
            "shared_w_gate": (d, F), "shared_w_out": (F, d),
            "w_in": (d, f), "w_gate": (d, f), "w_out": (f, d)}


def table(key, name: str, dims: Dims):
    """The [vocab, d_model] embedding or output head."""
    return normal(key, name, (), (dims.vocab, dims.d_model), EMBED_STD,
                  dims.dtype)


def final_norm(key, dims: Dims):
    return normal(key, "final_norm", (), (dims.d_model,), NORM_STD,
                  jnp.float32)


def layer_leaf(key, name: str, l, dims: Dims):
    """One per-layer leaf (norms, attention, router, shared expert)."""
    shape = _shapes(dims)[name]
    if name in ("norm1", "norm2"):
        return normal(key, name, (l,), shape, NORM_STD, jnp.float32)
    if name == "router":
        return normal(key, name, (l,), shape, ROUTER_STD, jnp.float32)
    return normal(key, name, (l,), shape, _std(dims)[name], dims.dtype)


def expert_leaf(key, name: str, l, e, dims: Dims):
    """One expert's ``w_in`` / ``w_gate`` [d, f] or ``w_out`` [f, d]."""
    return normal(key, name, (l, e), _shapes(dims)[name], _std(dims)[name],
                  dims.dtype)


LAYER_LEAVES = ("norm1", "norm2", "wq", "wk", "wv", "wo", "router",
                "shared_w_in", "shared_w_gate", "shared_w_out")
EXPERT_LEAVES = ("w_in", "w_gate", "w_out")


def layer(key, l, dims: Dims) -> Dict[str, Any]:
    """Every non-expert leaf of layer ``l``, in float32 (as served)."""
    return {n: layer_leaf(key, n, l, dims).astype(jnp.float32)
            for n in LAYER_LEAVES}


def expert(key, l, e, dims: Dims) -> Dict[str, Any]:
    """Expert ``e`` of layer ``l``, in float32 (as served)."""
    return {n: expert_leaf(key, n, l, e, dims).astype(jnp.float32)
            for n in EXPERT_LEAVES}


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


def fill_program_tree(shapes, dims: Dims, ranks: int, key):
    """The program's parameter tree (``shapes`` from ``jax.eval_shape`` of
    its ``init``, experts over ``ranks``) filled with this layout's
    weights; a leaf it does not know raises, naming its path."""
    L = dims.layers

    def fill(p, sds):
        if p in ("embed", "lm_head"):
            t = table(key, p, dims)
            pad = sds.shape[0] - dims.vocab        # padded vocabulary rows
            return jnp.concatenate(
                [t, jnp.zeros((pad, dims.d_model), sds.dtype)]) if pad else t
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
            return x.astype(sds.dtype)
        if sub in _PROGRAM_EXPERT:
            name = _PROGRAM_EXPERT[sub]
            ids = jnp.asarray(expert_of_row(sds.shape[1], ranks))
            return jax.vmap(lambda l: jax.vmap(
                lambda e: expert_leaf(key, name, l, e, dims))(ids))(
                    jnp.arange(L))
        return None

    return fill_tree(shapes, fill)
