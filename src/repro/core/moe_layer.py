"""The HarMoEny MoE block — paper Algorithm 1 as a shard_map island.

Data flow per EP rank (the paper's six steps):
  1. token routing          -> route_topk / route_skewed (router.py)
  2. metadata exchange      -> all_gather of the [Ep] count histogram (~kB)
  3. token scheduling       -> replicated deterministic schedule (scheduler.py)
  4. scatter tokens         -> static-capacity all_to_all (dispatch.py)
  5. expert processing      -> grouped FFN + foreign-weight fetch off the
                               critical path (grouped_ffn.py, prefetch.py)
  6. gather tokens          -> reverse all_to_all + gate combine (dispatch.py)

The island takes x replicated over the EP ('model') axis and sharded over the
batch axes; each EP rank owns a contiguous token slice (the paper's per-GPU
minibatch). Every (pod, data) row runs an independent protocol instance.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig, round_up
from repro.core import dispatch as D
from repro.core import prefetch
from repro.core import router as R
from repro.core import scheduler as SCH
from repro.core.grouped_ffn import grouped_ffn
from repro.core.qthreshold import device_hardware, q_threshold
from repro.core.topology import EPTopology, make_topology


@dataclass(frozen=True)
class MoEBlockSpec:
    """Static plumbing for one MoE block on a given mesh.

    ``tp_mode``: when the expert count is below the EP degree (mixtral's 8
    experts on a 16-wide axis), expert parallelism is the wrong decomposition
    — hosting ratios force weight duplication and the paper's scheduling
    regime (E >= G) does not hold. In that case the block switches to
    tensor-parallel MoE: every rank holds a d_ff-slice of EVERY expert, the
    compute is perfectly balanced by construction (skew-insensitive), and the
    only collective is the row-parallel output psum (vLLM's Mixtral strategy;
    DESIGN.md §Arch-applicability).
    """
    moe: MoEConfig
    d_model: int
    ep_axis: str
    batch_axes: Tuple[str, ...]
    ep_degree: int
    tokens_local: int        # B_local * S_local (per batch-group)
    block_m: int = 128
    cf_pair: float = 2.0
    act: str = "silu"        # expert activation; "swiglu" handled via w_gate
    use_pallas: bool = False
    interpret: bool = False
    fetch_chunk: int = 2048
    tp_mode: bool = False
    # sequence parallelism: the island consumes x already seq-sharded over
    # the EP axis (each rank's shard IS its token slice — no dynamic_slice in,
    # no all_gather out). Requires seq_len % ep_degree == 0; decode uses the
    # replicated path.
    seq_sharded: bool = False

    @property
    def topo(self) -> EPTopology:
        assert not self.tp_mode
        return make_topology(self.ep_degree, self.moe.num_experts,
                             placement=self.moe.placement)

    @property
    def t_pad(self) -> int:
        return round_up(max(self.tokens_local, self.ep_degree), self.ep_degree)

    @property
    def t_slice(self) -> int:
        return self.t_pad // self.ep_degree

    @property
    def units_per_rank(self) -> int:
        return self.t_slice * self.moe.num_experts_per_tok

    @property
    def c_pair(self) -> int:
        per_dest = -(-self.units_per_rank // self.ep_degree)  # ceil
        return max(int(self.cf_pair * per_dest), 8)

    @property
    def n_groups(self) -> int:
        # compute-buffer group order: local | replica | foreign
        return (self.topo.experts_per_rank + self.moe.num_replica_slots
                + self.moe.num_foreign_slots)

    @property
    def c_total(self) -> int:
        cap = int(self.moe.capacity_factor * self.units_per_rank)
        return round_up(max(cap, self.block_m), self.block_m) \
            + self.n_groups * self.block_m

    @property
    def q(self) -> int:
        if self.moe.q_tokens:
            return self.moe.q_tokens
        return q_threshold(device_hardware(), ep_degree=self.ep_degree,
                           dense_fetch=True)


def init_moe_params(key: jax.Array, spec: MoEBlockSpec,
                    dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Global (pjit-view) parameters.

    EP mode: expert rows are rank-major slot rows — row g*epr + j holds
    expert topo.slot_map[g, j] (duplicated when E < G). TP mode: plain
    [E, d, f] (sharded over d_ff by the sharding rules)."""
    d, f = spec.d_model, spec.moe.d_ff_expert
    if spec.tp_mode:
        n_rows, n_router = spec.moe.num_experts, spec.moe.num_experts
    else:
        topo = spec.topo
        n_rows = topo.num_ranks * topo.experts_per_rank
        n_router = topo.padded_experts
    k_r, k_i, k_g, k_o = jax.random.split(key, 4)
    scale_in = (2.0 / d) ** 0.5
    scale_out = (2.0 / f) ** 0.5
    params = {
        "router": (jax.random.normal(k_r, (d, n_router)) * 0.02
                   ).astype(jnp.float32),
        "w_in": (jax.random.normal(k_i, (n_rows, d, f)) * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k_o, (n_rows, f, d)) * scale_out).astype(dtype),
    }
    if spec.act == "silu":  # swiglu experts carry a gate matrix
        params["w_gate"] = (jax.random.normal(k_g, (n_rows, d, f))
                            * scale_in).astype(dtype)
    R = spec.moe.num_replica_slots
    if R and not spec.tp_mode:
        # replica slots start empty (all replica_ids = -1, never scheduled);
        # serve/rebalance.py swaps hot experts' weight rows in between windows
        rep_rows = spec.ep_degree * R
        params["w_rep_in"] = jnp.zeros((rep_rows, d, f), dtype)
        params["w_rep_out"] = jnp.zeros((rep_rows, f, d), dtype)
        if spec.act == "silu":
            params["w_rep_gate"] = jnp.zeros((rep_rows, d, f), dtype)
    return params


def _moe_forward_local(x_rep: jnp.ndarray, params: Dict[str, jnp.ndarray],
                       spec: MoEBlockSpec, n_valid: int,
                       skew_key: Optional[jax.Array],
                       valid_rep: Optional[jnp.ndarray] = None,
                       replica_ids: Optional[jnp.ndarray] = None,
                       residency_ids: Optional[jnp.ndarray] = None
                       ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Per-rank body (inside shard_map). x_rep: [t_pad, d] replicated over EP.

    replica_ids: [G, R] replicated traced int32 — the expert id occupying each
    rank's replica slots (-1 = empty). Required (possibly all -1) whenever
    ``spec.moe.num_replica_slots > 0`` so buffer/weight shapes stay static.

    residency_ids: [G, W] replicated traced int32 — each rank's HBM-resident
    working set under tiered expert residency (serve/residency.py); experts
    statically placed on a rank but absent from its table are demoted to
    fetch-paying ``non_local`` destinations in the harmoeny schedule.
    """
    topo = spec.topo
    moe = spec.moe
    G, Ep = topo.num_ranks, topo.padded_experts
    k = moe.num_experts_per_tok
    R_slots = moe.num_replica_slots
    me = jax.lax.axis_index(spec.ep_axis)

    if spec.seq_sharded:
        x_slice = x_rep                     # already this rank's token slice
        t_slice = x_rep.shape[0]
    else:
        t_slice = x_rep.shape[0] // G
        x_slice = jax.lax.dynamic_slice_in_dim(x_rep, me * t_slice,
                                               t_slice, axis=0)

    # --- step 1: routing ------------------------------------------------
    with jax.named_scope("router"):
        if skew_key is not None and moe.router_skew > 0.0:
            key = jax.random.fold_in(skew_key, me)
            r_out = R.route_skewed(key, t_slice, top_k=k,
                                   num_experts=moe.num_experts,
                                   padded_experts=Ep, alpha=moe.router_skew,
                                   n_hot=moe.router_skew_experts)
        else:
            r_out = R.route_topk(x_slice, params["router"], top_k=k,
                                 num_real_experts=moe.num_experts)
        # mark padding tokens with the sentinel expert id Ep (never scheduled);
        # valid_rep additionally masks caller-declared dead tokens (inactive
        # decode slots, prompt-chunk padding) out of routing and capacity
        tok_idx = me * t_slice + jnp.arange(t_slice)
        valid_tok = tok_idx < n_valid
        if valid_rep is not None:
            v_slice = valid_rep if spec.seq_sharded else \
                jax.lax.dynamic_slice_in_dim(valid_rep, me * t_slice, t_slice,
                                             axis=0)
            valid_tok = valid_tok & v_slice
        assign = jnp.where(valid_tok[:, None], r_out.assign, Ep)
        counts = jnp.zeros((Ep,), jnp.int32).at[assign.reshape(-1)].add(
            1, mode="drop")

    # --- step 2: metadata exchange (~G*Ep*4 bytes on the wire) -----------
    with jax.named_scope("schedule"):
        m_all = jax.lax.all_gather(counts, spec.ep_axis, axis=0)  # [G, Ep]

        # --- step 3: replicated deterministic scheduling ------------------
        extra_local = None
        rep_ids_me = None
        if R_slots and replica_ids is not None:
            # ranks holding a replica of e count as local destinations for e
            extra_local = D.replica_slot_map(replica_ids, Ep) >= 0  # [G, Ep]
            rep_ids_me = jnp.take(replica_ids, me, axis=0)          # [R]
        non_local = None
        if residency_ids is not None:
            # tiered residency: statically-placed experts swapped out of HBM
            # stop counting as free destinations for the rebalancer
            non_local = prefetch.residency_non_local(residency_ids, topo)
        S, sdiag = SCH.schedule(m_all, topo, policy=moe.policy, q=spec.q,
                                c_pair=spec.c_pair,
                                num_foreign_slots=moe.num_foreign_slots,
                                extra_local=extra_local, non_local=non_local)

    # --- step 4: scatter ---------------------------------------------------
    with jax.named_scope("dispatch"):
        layout = D.build_layout(S, assign, me, topo, c_pair=spec.c_pair,
                                c_total=spec.c_total,
                                num_foreign_slots=moe.num_foreign_slots,
                                block_m=spec.block_m,
                                num_replica_slots=R_slots,
                                replica_ids_me=rep_ids_me)
        x_units = jnp.repeat(x_slice, k, axis=0)    # token-major, k-minor
        grouped = D.dispatch(x_units, layout, axis_name=spec.ep_axis,
                             num_ranks=G, c_pair=spec.c_pair,
                             c_total=spec.c_total)

    # --- step 5: expert processing + async weight fetch --------------------
    with jax.named_scope("expert_weights"):
        w_in, w_out = params["w_in"], params["w_out"]   # local [epr, ...]
        w_gate = params.get("w_gate")
        # replica-slot weight rows for this rank ([R, ...] shards, zeros
        # if empty)
        w_rep = {name: params.get(name) for name in
                 ("w_rep_in", "w_rep_out", "w_rep_gate")}

        def with_replicas(w, rep_name):
            wr = w_rep[rep_name]
            return w if wr is None else jnp.concatenate(
                [w, wr.astype(w.dtype)], axis=0)
        if moe.policy == "even_split":
            # full replication (paper's Even-Split): gather all experts;
            # the map covers every group row — local, replica, and foreign
            # alike
            def per_group(w):
                w_all = prefetch.gather_all_experts(w, axis_name=spec.ep_axis)
                rows = _expert_row_map(topo)
                ge = jnp.minimum(jnp.maximum(layout.group_expert, 0), Ep - 1)
                return w_all[jnp.asarray(rows)[ge]]
            w_in_full, w_out_full = per_group(w_in), per_group(w_out)
            w_gate_full = per_group(w_gate) if w_gate is not None else None
        elif moe.num_foreign_slots > 0:
            fids_all = prefetch.all_foreign_ids(S, topo, moe.num_foreign_slots,
                                                replica_ids=replica_ids
                                                if R_slots else None)

            def fetch(w, rep_name):
                wf = prefetch.fetch_foreign_weights(
                    w, fids_all, me, topo, axis_name=spec.ep_axis,
                    fetch_chunk=spec.fetch_chunk)
                return jnp.concatenate([with_replicas(w, rep_name),
                                        wf.astype(w.dtype)], axis=0)
            w_in_full = fetch(w_in, "w_rep_in")
            w_out_full = fetch(w_out, "w_rep_out")
            w_gate_full = fetch(w_gate, "w_rep_gate") \
                if w_gate is not None else None
        else:
            w_in_full = with_replicas(w_in, "w_rep_in")
            w_out_full = with_replicas(w_out, "w_rep_out")
            w_gate_full = (with_replicas(w_gate, "w_rep_gate")
                           if w_gate is not None else None)

    with jax.named_scope("expert_ffn"):
        sizes_padded = D.round_up_j(layout.group_sizes, spec.block_m)
        out_grouped = grouped_ffn(grouped, w_in_full, w_out_full, sizes_padded,
                                  w_gate=w_gate_full, act=spec.act,
                                  use_pallas=spec.use_pallas,
                                  interpret=spec.interpret,
                                  block_m=spec.block_m)

    # --- step 6: gather + combine ------------------------------------------
    with jax.named_scope("combine"):
        y_slice = D.combine(out_grouped, layout, axis_name=spec.ep_axis,
                            num_ranks=G, c_pair=spec.c_pair,
                            gates=r_out.gates, top_k=k)
        y_rep = (y_slice if spec.seq_sharded
                 else jax.lax.all_gather(y_slice, spec.ep_axis, axis=0,
                                         tiled=True))

    t_g = S.sum(axis=(0, 1)).astype(jnp.float32)
    # drops are per-rank quantities; sum them so the reported diagnostic is
    # the honest global count (out_specs otherwise surface one rank's shard)
    send_drops = jax.lax.psum(layout.send_drops, spec.ep_axis)
    dest_drops = jax.lax.psum(layout.dest_drops, spec.ep_axis)
    diag = {
        "aux_loss": r_out.aux_loss[None],
        "send_drops": send_drops[None].astype(jnp.float32),
        "dest_drops": dest_drops[None].astype(jnp.float32),
        "sched_iters": sdiag.iters[None].astype(jnp.float32),
        "moved_units": sdiag.moved[None].astype(jnp.float32),
        "max_load_before": sdiag.max_load_before[None].astype(jnp.float32),
        "max_load_after": sdiag.max_load_after[None].astype(jnp.float32),
        "mean_load": t_g.mean()[None],
        # vector diagnostics (paper §5 measurements): scheduled units per
        # rank and routed units per expert for this step
        "rank_load": t_g[None, :],                              # [1, G]
        "expert_load": m_all.sum(axis=0).astype(jnp.float32)[None, :],  # [1, Ep]
    }
    return y_rep, diag


# diagnostic keys emitted by every MoE block variant; scalars are [1]-shaped
# inside the shard_map body, vectors are [1, N] (N = ranks / experts)
SCALAR_DIAGS = ("aux_loss", "send_drops", "dest_drops", "sched_iters",
                "moved_units", "max_load_before", "max_load_after",
                "mean_load")
VECTOR_DIAGS = ("rank_load", "expert_load")


def _diag_out_specs(batch_spec):
    P = jax.sharding.PartitionSpec
    specs = {key: P(batch_spec) for key in SCALAR_DIAGS}
    specs.update({key: P(batch_spec, None) for key in VECTOR_DIAGS})
    return specs


def _expert_row_map(topo: EPTopology):
    """expert id -> its first global slot row (static)."""
    import numpy as np
    rows = np.zeros((topo.padded_experts,), np.int32)
    for g in range(topo.num_ranks):
        for j in range(topo.experts_per_rank):
            e = topo.slot_map[g, j]
            rows[e] = g * topo.experts_per_rank + j
    return rows


def tp_moe_block(x: jnp.ndarray, params: Dict[str, jnp.ndarray], *,
                 spec: MoEBlockSpec, mesh: jax.sharding.Mesh,
                 skew_key: Optional[jax.Array] = None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Tensor-parallel MoE (E < EP degree). Every rank holds a d_ff slice of
    every expert: local sort-by-expert + exact ragged matmuls + output psum.
    Perfectly balanced by construction; zero drops (no capacity bounds)."""
    P = jax.sharding.PartitionSpec
    B, S_len, d = x.shape
    E = spec.moe.num_experts
    k = spec.moe.num_experts_per_tok
    batch_spec = spec.batch_axes if spec.batch_axes else None

    bm = spec.block_m

    def body(xb, p_router, w_in, w_out, w_gate, key):
        B_loc = xb.shape[0]
        T = B_loc * S_len
        flat = xb.reshape(T, d)
        with jax.named_scope("router"):
            if key is not None and spec.moe.router_skew > 0.0:
                r = R.route_skewed(key, T, top_k=k, num_experts=E,
                                   padded_experts=E,
                                   alpha=spec.moe.router_skew,
                                   n_hot=spec.moe.router_skew_experts)
            else:
                r = R.route_topk(flat, p_router, top_k=k,
                                 num_real_experts=E)
        ue = r.assign.reshape(-1)
        U = ue.shape[0]
        with jax.named_scope("dispatch"):
            # block-aligned grouped buffer (exact: capacity covers all
            # units)
            sizes = jnp.zeros((E,), jnp.int32).at[ue].add(1)
            padded = D.round_up_j(sizes, bm)
            offsets = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]])
            order = jnp.argsort(ue, stable=True)
            start = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)[:-1]])
            r_sorted = jnp.arange(U, dtype=jnp.int32) - start[ue[order]]
            rank_in_e = jnp.zeros((U,), jnp.int32).at[order].set(r_sorted)
            row = offsets[ue] + rank_in_e
            m_pad = round_up(U, bm) + E * bm
            x_units = jnp.repeat(flat, k, axis=0)
            x_buf = jnp.zeros((m_pad, d), flat.dtype).at[row].set(x_units)
        with jax.named_scope("expert_ffn"):
            y_buf = grouped_ffn(x_buf, w_in, w_out, padded, w_gate=w_gate,
                                act=spec.act, use_pallas=spec.use_pallas,
                                interpret=spec.interpret, block_m=bm)
        with jax.named_scope("combine"):
            y_buf = jax.lax.psum(y_buf, spec.ep_axis)      # row-parallel
            y_units = y_buf[row]
            y = (y_units.reshape(T, k, d)
                 * r.gates.reshape(T, k, 1).astype(y_units.dtype)
                 ).sum(axis=1)
        zero = jnp.zeros((1,), jnp.float32)
        diag = {"aux_loss": r.aux_loss[None], "send_drops": zero,
                "dest_drops": zero, "sched_iters": zero, "moved_units": zero,
                "max_load_before": zero, "max_load_after": zero,
                "mean_load": zero,
                # TP-MoE is compute-balanced by construction: every rank
                # holds a d_ff slice of every unit's expert
                "rank_load": jnp.full((1, spec.ep_degree),
                                      U / spec.ep_degree, jnp.float32),
                "expert_load": sizes.astype(jnp.float32)[None, :]}
        return y.reshape(B_loc, S_len, d).astype(xb.dtype), diag

    in_specs = (
        P(batch_spec, None, None),
        P(None, None),
        P(None, None, spec.ep_axis),                # w_in: f-sliced
        P(None, spec.ep_axis, None),                # w_out: f-sliced
        (P(None, None, spec.ep_axis) if "w_gate" in params else None),
        (P() if skew_key is not None else None),
    )
    out_specs = (P(batch_spec, None, None), _diag_out_specs(batch_spec))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, params["router"], params["w_in"], params["w_out"],
              params.get("w_gate"), skew_key)


def moe_block(x: jnp.ndarray, params: Dict[str, jnp.ndarray], *,
              spec: MoEBlockSpec, mesh: jax.sharding.Mesh,
              skew_key: Optional[jax.Array] = None,
              valid_mask: Optional[jnp.ndarray] = None,
              replica_ids: Optional[jnp.ndarray] = None,
              residency_ids: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Global-view MoE block. x: [B, S, d] -> [B, S, d], diagnostics.

    Batch is sharded over ``spec.batch_axes``; experts over ``spec.ep_axis``
    (or d_ff over ``spec.ep_axis`` in TP mode — see MoEBlockSpec).
    ``valid_mask`` [B, S] (bool) excludes dead tokens — inactive serving
    slots, prompt-chunk padding — from routing, capacity, and the schedule
    diagnostics; their outputs are still produced (garbage) and must be
    discarded by the caller.
    ``replica_ids`` [G, R] int32 (traced; -1 = empty) names the expert whose
    weights currently occupy each rank's replica slots; defaults to all
    empty when ``spec.moe.num_replica_slots > 0``.
    ``residency_ids`` [G, W] int32 (traced; -1 = pad) names each rank's
    HBM-resident working set under tiered expert residency; None means
    everything is resident (no demotion).
    """
    if spec.tp_mode:
        # TP-MoE is capacity-free and compute-balanced; dead tokens cannot
        # drop real ones, so the mask (and replication) is unnecessary there.
        return tp_moe_block(x, params, spec=spec, mesh=mesh,
                            skew_key=skew_key)
    P = jax.sharding.PartitionSpec
    B, S_len, d = x.shape
    batch_spec = spec.batch_axes if spec.batch_axes else None

    R_slots = spec.moe.num_replica_slots
    if R_slots:
        assert "w_rep_in" in params, \
            "num_replica_slots > 0 requires w_rep_* params (init_moe_params)"
        if replica_ids is None:
            replica_ids = jnp.full((spec.ep_degree, R_slots), -1, jnp.int32)
    else:
        replica_ids = None

    def body(xb, p_router, p_in, p_out, p_gate, p_reps, rep_ids, res_ids,
             key, vmask):
        B_loc, S_loc = xb.shape[0], xb.shape[1]
        flat = xb.reshape(B_loc * S_loc, d)
        prm = {"router": p_router, "w_in": p_in, "w_out": p_out}
        if p_gate is not None:
            prm["w_gate"] = p_gate
        if p_reps is not None:
            prm.update(p_reps)
        if spec.seq_sharded:
            # xb (and vmask) are already this rank's token slice
            y, diag = _moe_forward_local(
                flat, prm, spec, flat.shape[0] * spec.ep_degree, key,
                valid_rep=None if vmask is None else vmask.reshape(-1),
                replica_ids=rep_ids, residency_ids=res_ids)
            y = y.reshape(B_loc, S_loc, d)
        else:
            n_valid = flat.shape[0]
            t_pad = round_up(max(n_valid, spec.ep_degree), spec.ep_degree)
            x_rep = jnp.pad(flat, ((0, t_pad - n_valid), (0, 0)))
            v_rep = None
            if vmask is not None:
                v_rep = jnp.pad(vmask.reshape(-1),
                                (0, t_pad - n_valid))   # pads are invalid
            y, diag = _moe_forward_local(x_rep, prm, spec, n_valid, key,
                                         valid_rep=v_rep,
                                         replica_ids=rep_ids,
                                         residency_ids=res_ids)
            y = y[:n_valid].reshape(B_loc, S_loc, d)
        return y, diag

    rep_params = None
    rep_param_specs = None
    if R_slots:
        rep_params = {name: params[name] for name in
                      ("w_rep_in", "w_rep_out", "w_rep_gate")
                      if name in params}
        rep_param_specs = {name: P(spec.ep_axis, None, None)
                           for name in rep_params}
    x_seq_spec = spec.ep_axis if spec.seq_sharded else None
    in_specs = (
        P(batch_spec, x_seq_spec, None),           # x: batch (+seq) sharded
        P(None, None),                             # router replicated
        P(spec.ep_axis, None, None),               # expert rows over EP axis
        P(spec.ep_axis, None, None),
        (P(spec.ep_axis, None, None) if "w_gate" in params else None),
        rep_param_specs,                           # replica rows over EP axis
        (P(None, None) if replica_ids is not None else None),
        (P(None, None) if residency_ids is not None else None),
        (P() if skew_key is not None else None),
        (P(batch_spec, x_seq_spec) if valid_mask is not None else None),
    )
    out_specs = (P(batch_spec, x_seq_spec, None), _diag_out_specs(batch_spec))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, params["router"], params["w_in"], params["w_out"],
              params.get("w_gate"), rep_params, replica_ids, residency_ids,
              skew_key, valid_mask)
