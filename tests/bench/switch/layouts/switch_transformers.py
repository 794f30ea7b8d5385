"""Layout of the program's Switch Transformer decoder (``switch128``):
dense and MoE layers alternate, every ``sparse_step``-th layer (the last of
each period) routing each token to its top-1 expert; every FFN is an
ungated GELU MLP; no shared expert; the output head is the embedding.

The interface the harness reads: ``Dims`` (with ``token_flops``),
``FROM_FILE``, ``from_config``, ``from_program``, ``program_config`` and
``fill_program_tree``; ``layer``, ``expert``, ``table`` and
``final_norm`` are the draws its reference reads.  It builds on the
harness's layout-free draws alone.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.weights import (EMBED_STD, NORM_STD, ROUTER_STD,
                               expert_of_row, fill_tree, normal)


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int                 # the dense layers' FFN width
    experts: int
    top_k: int
    d_expert: int
    sparse_step: int          # layer l is MoE where l % step == step - 1
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str = "bfloat16"

    def is_moe(self, l: int) -> bool:
        return l % self.sparse_step == self.sparse_step - 1

    def token_flops(self, context: int, *, logits: bool) -> float:
        """Model FLOPs of one token through every layer at ``context``
        positions: the projections, the dense FFN or the router and the
        top-k experts, causal attention, and the tied head where the
        token's logits are computed."""
        d, H, Hkv, hd = self.d_model, self.heads, self.kv_heads, self.head_dim
        moe = sum(self.is_moe(l) for l in range(self.layers))
        per_token = self.layers * (d * H * hd * 2 + d * Hkv * hd * 2)
        per_token += (self.layers - moe) * 2 * d * self.d_ff
        per_token += moe * (d * self.experts
                            + self.top_k * 2 * d * self.d_expert)
        flops = 2.0 * per_token + 4.0 * H * hd * context * self.layers
        if logits:
            flops += 2.0 * d * self.vocab
        return flops


FROM_FILE = {
    "norm_eps": "the program fixes RMSNorm's epsilon at 1e-6 "
                "(models/layers.py) and has no field for it",
}


def from_config(doc: Dict[str, Any]) -> Dims:
    """Switch Transformers' own key names; the experts' width and the
    decoder's sparse step are this file's, the routing is top-1."""
    heads = doc["num_heads"]
    return Dims(layers=doc["num_layers"], d_model=doc["d_model"],
                heads=heads, kv_heads=heads, head_dim=doc["d_kv"],
                d_ff=doc["d_ff"], experts=doc["num_experts"], top_k=1,
                d_expert=doc["expert_d_ff"],
                sparse_step=doc["decoder_sparse_step"],
                vocab=doc["vocab_size"],
                rope_theta=float(doc["rope_theta"]),
                norm_eps=float(doc["layer_norm_epsilon"]),
                dtype=doc["torch_dtype"])


def from_program(cfg) -> Dims:
    moe = cfg.moe
    return Dims(layers=cfg.num_layers, d_model=cfg.d_model,
                heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                experts=moe.num_experts, top_k=moe.num_experts_per_tok,
                d_expert=moe.d_ff_expert, sparse_step=moe.moe_layer_period,
                vocab=cfg.vocab_size, rope_theta=cfg.rope_theta,
                norm_eps=None, dtype=cfg.dtype)


def program_config(cfg, dims: Dims):
    return cfg.replace(num_layers=dims.layers, dtype=dims.dtype)


def _std(dims: Dims) -> Dict[str, float]:
    d = dims.d_model
    return {"wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
            "wo": (dims.heads * dims.head_dim) ** -0.5,
            "mlp_w_in": (2.0 / d) ** 0.5,
            "mlp_w_out": (2.0 / dims.d_ff) ** 0.5,
            "w_in": (2.0 / d) ** 0.5, "w_out": (2.0 / dims.d_expert) ** 0.5}


def _shapes(dims: Dims) -> Dict[str, tuple]:
    d, H, Hkv, hd = dims.d_model, dims.heads, dims.kv_heads, dims.head_dim
    return {"norm1": (d,), "norm2": (d,), "wq": (d, H, hd),
            "wk": (d, Hkv, hd), "wv": (d, Hkv, hd), "wo": (H, hd, d),
            "mlp_w_in": (d, dims.d_ff), "mlp_w_out": (dims.d_ff, d),
            "router": (d, dims.experts),
            "w_in": (d, dims.d_expert), "w_out": (dims.d_expert, d)}


def table(key, dims: Dims):
    """The [vocab, d_model] embedding, which is also the output head."""
    return normal(key, "embed", (), (dims.vocab, dims.d_model), EMBED_STD,
                  dims.dtype)


def final_norm(key, dims: Dims):
    return normal(key, "final_norm", (), (dims.d_model,), NORM_STD,
                  jnp.float32)


def layer_leaf(key, name: str, l, dims: Dims):
    shape = _shapes(dims)[name]
    if name in ("norm1", "norm2"):
        return normal(key, name, (l,), shape, NORM_STD, jnp.float32)
    if name == "router":
        return normal(key, name, (l,), shape, ROUTER_STD, jnp.float32)
    return normal(key, name, (l,), shape, _std(dims)[name], dims.dtype)


def expert_leaf(key, name: str, l, e, dims: Dims):
    return normal(key, name, (l, e), _shapes(dims)[name], _std(dims)[name],
                  dims.dtype)


ATTN_LEAVES = ("norm1", "norm2", "wq", "wk", "wv", "wo")


def layer(key, l: int, dims: Dims) -> Dict[str, Any]:
    """Every non-expert leaf of layer ``l`` (its router where it is MoE,
    its FFN where it is dense), in float32 (as served)."""
    names = ATTN_LEAVES + (("router",) if dims.is_moe(l)
                           else ("mlp_w_in", "mlp_w_out"))
    return {n: layer_leaf(key, n, l, dims).astype(jnp.float32)
            for n in names}


def expert(key, l, e, dims: Dims) -> Dict[str, Any]:
    return {n: expert_leaf(key, n, l, e, dims).astype(jnp.float32)
            for n in ("w_in", "w_out")}


# program leaf path (after "stack/blocks/sub<j>/") -> semantic name, for
# every layer, the dense layers and the MoE layers
_ATTN = {"norm1/scale": "norm1", "norm2/scale": "norm2",
         "attn/wq": "wq", "attn/wk": "wk", "attn/wv": "wv", "attn/wo": "wo"}
_DENSE = {"mlp/w_in": "mlp_w_in", "mlp/w_out": "mlp_w_out"}
_MOE = {"moe/router": "router"}
_EXPERT = {"moe/w_in": "w_in", "moe/w_out": "w_out"}
_SUB = re.compile(r"stack/blocks/sub(\d+)/(.+)")


def fill_program_tree(shapes, dims: Dims, ranks: int, key):
    """The program's tree: ``stack/blocks/sub<j>`` stacks layers ``j``,
    ``j + step``, ...; sub ``step - 1`` holds the MoE layers."""
    step = dims.sparse_step

    def fill(p, sds):
        if p == "embed":
            t = table(key, dims)
            pad = sds.shape[0] - dims.vocab        # padded vocabulary rows
            return jnp.concatenate(
                [t, jnp.zeros((pad, dims.d_model), sds.dtype)]) if pad else t
        if p == "final_norm/scale":
            return final_norm(key, dims)
        m = _SUB.fullmatch(p)
        if m is None or int(m[1]) >= step:
            return None
        j, sub = int(m[1]), m[2]
        ls = jnp.arange(sds.shape[0]) * step + j
        known = {**_ATTN, **(_MOE if j == step - 1 else _DENSE)}
        if sub in known:
            name = known[sub]
            x = jax.vmap(lambda l: layer_leaf(key, name, l, dims))(ls)
            if name == "router" and sds.shape[-1] > dims.experts:
                x = jnp.pad(x, ((0, 0), (0, 0),
                                (0, sds.shape[-1] - dims.experts)))
            return x.astype(sds.dtype)
        if sub in _EXPERT and j == step - 1:
            name = _EXPERT[sub]
            ids = jnp.asarray(expert_of_row(sds.shape[1], ranks))
            return jax.vmap(lambda l: jax.vmap(
                lambda e: expert_leaf(key, name, l, e, dims))(ids))(ls)
        return None

    return fill_tree(shapes, fill)
