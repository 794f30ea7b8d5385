"""Layer stacks: dense/MoE decoder (scan over layer-pattern periods), the
zamba2 hybrid stack, and the whisper encoder-decoder.

All stacks scan over layers with stacked parameters so the HLO stays compact
(one layer body per pattern position) — essential for compiling 40+ cells of
the dry-run matrix quickly and the standard structure for PP-free deep
models. ``jax.checkpoint`` wraps the scan body when remat is requested.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.moe_layer import MoEBlockSpec, init_moe_params, moe_block
from repro.models import attention as A
from repro.models import mamba2 as M
from repro.models.layers import init_mlp, init_norm, mlp, norm


# ----------------------------------------------------------------------
# Layer pattern description
# ----------------------------------------------------------------------
def layer_pattern(cfg: ModelConfig) -> Tuple[List[str], int, int]:
    """Return (pattern, n_steps, n_lead_dense).

    pattern: layer kinds within one scan period, e.g. ["dense"],
    ["attn_local", "attn_global"], ["dense", "moe"]. The stack scans
    n_steps periods; ``n_lead_dense`` leading dense layers are unscanned
    (moonshot's first dense layer).
    """
    lead = cfg.moe.first_dense_layers if cfg.is_moe else 0
    L = cfg.num_layers - lead
    if cfg.family == "ssm":
        return ["mamba"], cfg.num_layers, 0
    if cfg.is_moe and cfg.moe.moe_layer_period > 1:
        p = cfg.moe.moe_layer_period
        assert L % p == 0
        pat = ["dense"] * p
        pat[cfg.moe.moe_layer_offset] = "moe"
        return pat, L // p, lead
    if cfg.is_moe:
        return ["moe"], L, lead
    if cfg.global_attn_every and cfg.global_attn_every > 1:
        p = cfg.global_attn_every
        assert L % p == 0
        pat = ["attn_local"] * (p - 1) + ["attn_global"]
        return pat, L // p, lead
    return ["dense"], L, lead


# ----------------------------------------------------------------------
# Per-layer init / apply
# ----------------------------------------------------------------------
def _init_one_layer(key: jax.Array, kind: str, cfg: ModelConfig,
                    moe_spec: Optional[MoEBlockSpec], dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    if kind == "mamba":
        p["mamba"] = M.init_mamba(ks[0], cfg, dtype)
        p["norm1"] = init_norm(cfg.d_model, cfg.norm)
        return p
    p["norm1"] = init_norm(cfg.d_model, cfg.norm)
    p["norm2"] = init_norm(cfg.d_model, cfg.norm)
    if cfg.post_norm:
        p["post_norm1"] = init_norm(cfg.d_model, cfg.norm)
        p["post_norm2"] = init_norm(cfg.d_model, cfg.norm)
    p["attn"] = A.init_attention(ks[0], cfg, dtype)
    if kind == "moe":
        p["moe"] = init_moe_params(ks[1], moe_spec, dtype)
        if cfg.moe.num_shared_experts:
            f_sh = cfg.moe.num_shared_experts * cfg.moe.d_ff_expert
            p["shared_mlp"] = init_mlp(ks[2], cfg.d_model, f_sh,
                                       "swiglu" if cfg.act == "swiglu"
                                       else cfg.act, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def _apply_one_layer(x: jnp.ndarray, p: Dict[str, Any], kind: str,
                     cfg: ModelConfig, pcfg: ParallelConfig, *,
                     mode: str, q_offset, cache, cache_len,
                     moe_spec: Optional[MoEBlockSpec], mesh, skew_key,
                     causal: bool = True, constrain=lambda x, mode="none": x,
                     continue_prefill: bool = False,
                     valid_mask=None, block_table=None, block_size: int = 0,
                     moe_replica_ids=None, moe_residency_ids=None,
                     interpret: bool = False,
                     ) -> Tuple[jnp.ndarray, Any, Dict[str, jnp.ndarray]]:
    """One layer of any kind. Returns (x, new_cache, diag).
    ``interpret`` runs the fused attention kernels in Pallas interpret
    mode (the model's ``pallas_interpret``)."""
    diag: Dict[str, jnp.ndarray] = {}
    x = constrain(x, mode)
    if kind == "mamba":
        # a prefix valid_mask (padded final prefill chunk) becomes a per-row
        # valid length so pad tokens don't fold into the recurrent state
        vlen = valid_mask.sum(axis=1) if valid_mask is not None else None
        h, new_state = M.mamba_block(norm(x, p["norm1"], cfg.norm), p["mamba"],
                                     cfg, state=cache, valid_len=vlen)
        return x + h, new_state, diag

    is_global = (kind != "attn_local")
    h = norm(x, p["norm1"], cfg.norm)
    h, new_cache = A.attention_block(
        h, p["attn"], cfg, causal=causal, is_global=is_global,
        q_offset=q_offset, cache=cache, cache_len=cache_len,
        attn_chunk=pcfg.attn_chunk, use_pallas=pcfg.use_pallas,
        interpret=interpret,
        continue_prefill=continue_prefill,
        block_table=block_table, block_size=block_size,
        strict_pallas=pcfg.pallas_strict, mesh=mesh)
    if cfg.post_norm:
        h = norm(h, p["post_norm1"], cfg.norm)
    x = x + h

    h = norm(x, p["norm2"], cfg.norm)
    if kind == "moe":
        y, mdiag = moe_block(h, p["moe"], spec=moe_spec, mesh=mesh,
                             skew_key=skew_key, valid_mask=valid_mask,
                             replica_ids=moe_replica_ids,
                             residency_ids=moe_residency_ids)
        if "shared_mlp" in p:
            with jax.named_scope("shared_expert"):
                y = y + mlp(h, p["shared_mlp"],
                            "swiglu" if cfg.act == "swiglu" else cfg.act)
        # collapse the leading batch-shard dim only: scalar diags -> scalars,
        # vector diags (rank_load/expert_load) keep their trailing axis
        diag = {k: v.mean(axis=0) for k, v in mdiag.items()}
        h = y
    else:
        h = mlp(h, p["mlp"], cfg.act)
    if cfg.post_norm:
        h = norm(h, p["post_norm2"], cfg.norm)
    return x + h, new_cache, diag


# ----------------------------------------------------------------------
# Decoder stack (dense / moe / ssm patterns)
# ----------------------------------------------------------------------
def init_stack(key: jax.Array, cfg: ModelConfig,
               moe_spec: Optional[MoEBlockSpec], dtype) -> Dict[str, Any]:
    pattern, n_steps, lead = layer_pattern(cfg)
    params: Dict[str, Any] = {}
    key, *lead_keys = jax.random.split(key, lead + 1)
    if lead:
        dense_cfg_kind = "dense"
        params["lead"] = [
            _init_one_layer(k, dense_cfg_kind, cfg, None, dtype)
            for k in lead_keys]
    step_keys = jax.random.split(key, n_steps)
    def init_step(k):
        sub_keys = jax.random.split(k, len(pattern))
        return {f"sub{j}": _init_one_layer(sub_keys[j], pattern[j], cfg,
                                           moe_spec, dtype)
                for j in range(len(pattern))}
    params["blocks"] = jax.vmap(init_step)(step_keys)
    return params


def _layer_cache_init(kind: str, cfg: ModelConfig, batch: int, s_max: int,
                      dtype, clamp_window: bool = True) -> Any:
    if kind == "mamba":
        return M.init_state(batch, cfg, dtype)
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    window = cfg.sliding_window
    if clamp_window and (kind == "attn_local"
                         or (window and not cfg.global_attn_every)):
        s_max = min(s_max, window)  # ring buffer for pure-SWA caches
    return A.AttnCache(jnp.zeros((batch, s_max, hkv, hd), dtype),
                       jnp.zeros((batch, s_max, hkv, hd), dtype))


def init_stack_cache(cfg: ModelConfig, batch: int, s_max: int, dtype,
                     clamp_window: bool = True) -> Dict[str, Any]:
    """``clamp_window=False`` keeps every attention leaf at full ``s_max``
    even for sliding-window layers (the serve engine's paged mode: windows
    are then enforced by ring-index arithmetic / masks, not by storage)."""
    pattern, n_steps, lead = layer_pattern(cfg)
    def one(kind):
        c = _layer_cache_init(kind, cfg, batch, s_max, dtype, clamp_window)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_steps,) + x.shape), c)
    cache: Dict[str, Any] = {
        "blocks": {f"sub{j}": one(pattern[j]) for j in range(len(pattern))}}
    if lead:
        cache["lead"] = [_layer_cache_init("dense", cfg, batch, s_max, dtype,
                                           clamp_window)
                         for _ in range(lead)]
    return cache


def run_stack(x: jnp.ndarray, params: Dict[str, Any], cfg: ModelConfig,
              pcfg: ParallelConfig, *, mode: str,
              cache: Optional[Dict[str, Any]] = None,
              cache_len=None, q_offset=0,
              moe_spec: Optional[MoEBlockSpec] = None, mesh=None,
              skew_key=None, causal: bool = True, constrain=lambda x, mode="none": x,
              continue_prefill: bool = False, valid_mask=None,
              block_table=None, block_size: int = 0,
              moe_replica_ids=None, moe_residency_ids=None,
              moe_layer_diags: bool = False, interpret: bool = False,
              ) -> Tuple[jnp.ndarray, Any, Dict[str, jnp.ndarray]]:
    """mode: train | prefill | decode | encode. Returns (x, new_cache, diags).

    ``moe_layer_diags`` (static) additionally emits ``expert_load_layers``
    [n_moe_steps, Ep] — the per-scan-step expert loads *before* the mean
    collapse — which the tiered-residency manager needs to predict each
    layer's working set separately (the per-layer signal from the PR-6
    follow-on)."""
    pattern, n_steps, lead = layer_pattern(cfg)

    new_lead_caches = []
    for i in range(lead):
        c = cache["lead"][i] if cache is not None else None
        x, nc, _ = _apply_one_layer(
            x, params["lead"][i], "dense", cfg, pcfg, mode=mode,
            q_offset=q_offset, cache=c, cache_len=cache_len,
            moe_spec=None, mesh=mesh, skew_key=skew_key, causal=causal,
            constrain=constrain, continue_prefill=continue_prefill,
            block_table=block_table, block_size=block_size,
            interpret=interpret)
        new_lead_caches.append(nc)

    def step(carry, inp):
        x, key = carry
        p_step, c_step = inp
        diags = {}
        new_caches = {}
        sub_key = key
        for j, kind in enumerate(pattern):
            if key is not None:
                sub_key = jax.random.fold_in(key, j)
            c = c_step[f"sub{j}"] if c_step is not None else None
            x, nc, d = _apply_one_layer(
                x, p_step[f"sub{j}"], kind, cfg, pcfg, mode=mode,
                q_offset=q_offset, cache=c, cache_len=cache_len,
                moe_spec=moe_spec, mesh=mesh, skew_key=sub_key, causal=causal,
                constrain=constrain, continue_prefill=continue_prefill,
                valid_mask=valid_mask, block_table=block_table,
                block_size=block_size, moe_replica_ids=moe_replica_ids,
                moe_residency_ids=moe_residency_ids, interpret=interpret)
            new_caches[f"sub{j}"] = nc
            diags.update({f"{k}": v for k, v in d.items()})
        new_key = (jax.random.fold_in(key, 997) if key is not None else None)
        return (x, new_key), (new_caches, diags)

    body = step
    if pcfg.remat != "none" and mode == "train":
        body = jax.checkpoint(step)

    xs_cache = cache["blocks"] if cache is not None else None
    if xs_cache is None:
        def wrapped(carry, p_step):
            return body(carry, (p_step, None))
        (x, _), (new_caches, diags) = jax.lax.scan(
            wrapped, (x, skew_key), params["blocks"])
    else:
        (x, _), (new_caches, diags) = jax.lax.scan(
            body, (x, skew_key), (params["blocks"], xs_cache))

    out_cache = None
    if cache is not None:
        out_cache = {"blocks": new_caches}
        if lead:
            out_cache["lead"] = new_lead_caches
    # scan stacks a leading n_steps axis; collapse it only, preserving the
    # trailing axis of vector diags (rank_load/expert_load)
    mean_diags = {k: v.mean(axis=0) for k, v in diags.items()}
    if moe_layer_diags and "expert_load" in diags:
        # the stacked pre-mean loads, one row per MoE scan step
        mean_diags["expert_load_layers"] = diags["expert_load"]
    return x, out_cache, mean_diags


# ----------------------------------------------------------------------
# Zamba2 hybrid stack: mamba backbone + shared attention blocks
# ----------------------------------------------------------------------
def init_hybrid(key: jax.Array, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    per = cfg.attn_every
    n_groups = cfg.num_layers // per
    rem = cfg.num_layers - n_groups * per
    k1, k2, k3 = jax.random.split(key, 3)

    def init_group(k):
        ks = jax.random.split(k, per)
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[
            {"mamba": M.init_mamba(kk, cfg, dtype),
             "norm1": init_norm(cfg.d_model, cfg.norm)} for kk in ks])

    params = {"groups": jax.vmap(lambda k: init_group(k))(
        jax.random.split(k1, n_groups))}
    if rem:
        ks = jax.random.split(jax.random.fold_in(k1, 7), rem)
        params["tail"] = [
            {"mamba": M.init_mamba(kk, cfg, dtype),
             "norm1": init_norm(cfg.d_model, cfg.norm)} for kk in ks]
    # one SHARED attention(+MLP) block applied after every group
    params["shared"] = {
        "norm1": init_norm(cfg.d_model, cfg.norm),
        "norm2": init_norm(cfg.d_model, cfg.norm),
        "attn": A.init_attention(k2, cfg, dtype),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }
    return params


def init_hybrid_cache(cfg: ModelConfig, batch: int, s_max: int, dtype):
    per = cfg.attn_every
    n_groups = cfg.num_layers // per
    rem = cfg.num_layers - n_groups * per
    ms = M.init_state(batch, cfg, dtype)
    cache = {
        "mamba": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups, per) + x.shape), ms),
        # each shared-attention application has its own KV cache
        "attn": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape),
            A.AttnCache(jnp.zeros((batch, s_max, cfg.num_kv_heads,
                                   cfg.resolved_head_dim), dtype),
                        jnp.zeros((batch, s_max, cfg.num_kv_heads,
                                   cfg.resolved_head_dim), dtype))),
    }
    if rem:
        cache["tail"] = [M.init_state(batch, cfg, dtype) for _ in range(rem)]
    return cache


def run_hybrid(x: jnp.ndarray, params, cfg: ModelConfig, pcfg: ParallelConfig,
               *, mode: str, cache=None, cache_len=None, q_offset=0,
               mesh=None, constrain=lambda x, mode="none": x,
               continue_prefill: bool = False,
               valid_mask=None) -> Tuple[jnp.ndarray, Any, Dict]:
    per = cfg.attn_every
    n_groups = cfg.num_layers // per
    rem = cfg.num_layers - n_groups * per
    shared = params["shared"]
    vlen = valid_mask.sum(axis=1) if valid_mask is not None else None

    def group_step(carry, inp):
        x = carry
        p_grp, c_grp = inp
        x = constrain(x, mode)
        new_m = []
        for i in range(per):
            p_i = jax.tree.map(lambda t: t[i], p_grp)
            c_i = (jax.tree.map(lambda t: t[i], c_grp["mamba"])
                   if c_grp is not None else None)
            h, nm = M.mamba_block(norm(x, p_i["norm1"], cfg.norm),
                                  p_i["mamba"], cfg, state=c_i,
                                  valid_len=vlen)
            x = x + h
            new_m.append(nm)
        # shared attention(+MLP) block — same weights every group
        c_a = c_grp["attn"] if c_grp is not None else None
        h = norm(x, shared["norm1"], cfg.norm)
        h, nc_a = A.attention_block(h, shared["attn"], cfg, causal=True,
                                    q_offset=q_offset, cache=c_a,
                                    cache_len=cache_len,
                                    attn_chunk=pcfg.attn_chunk,
                                    continue_prefill=continue_prefill)
        x = x + h
        x = x + mlp(norm(x, shared["norm2"], cfg.norm), shared["mlp"], cfg.act)
        new_cache = None
        if c_grp is not None:
            new_cache = {"mamba": jax.tree.map(lambda *t: jnp.stack(t), *new_m),
                         "attn": nc_a}
        return x, new_cache

    body = group_step
    if pcfg.remat != "none" and mode == "train":
        body = jax.checkpoint(group_step)

    if cache is None:
        x, _ = jax.lax.scan(lambda c, p: (body(c, (p, None))[0], None),
                            x, params["groups"])
        new_cache = None
    else:
        def wrapped(c, inp):
            return body(c, inp)
        x, stacked = jax.lax.scan(
            wrapped, x, (params["groups"],
                         {"mamba": cache["mamba"], "attn": cache["attn"]}))
        new_cache = {"mamba": stacked["mamba"], "attn": stacked["attn"]}

    new_tail = []
    for i in range(rem):
        c_i = cache["tail"][i] if cache is not None else None
        p_i = params["tail"][i]
        h, nt = M.mamba_block(norm(x, p_i["norm1"], cfg.norm), p_i["mamba"],
                              cfg, state=c_i, valid_len=vlen)
        x = x + h
        new_tail.append(nt)
    if cache is not None and rem:
        new_cache["tail"] = new_tail
    return x, new_cache, {}
