"""Serving load benchmark: arrival rate × router skew × policy sweep, plus
a paged-vs-slab KV capacity comparison and a shared-prefix trace, both at
equal memory.

Runs the repro.serve continuous-batching engine on a reduced Mixtral-family
MoE over 2 CPU-emulated devices (model/expert-parallel) and emits a
machine-readable ``BENCH_serve.json``:

* ``results`` — per-cell TTFT/TPOT percentiles, decode tokens/s, occupancy,
  KV utilization / effective concurrency, and HarMoEny schedule
  diagnostics, for the paged engine across rate × skew × policy;
* ``capacity`` — slab vs paged engines given the SAME physical KV token
  budget on a mixed-prompt-length workload: the paged pool's block-level
  allocation sustains strictly more concurrent decodes than the slab
  pool's worst-case slots;
* ``prefix`` — shared-prefix traces (common system prompt; also identical
  full prompts) through the paged engine with prefix sharing on vs off at
  the same block budget: sharing serves the common prefix out of the
  copy-on-write block cache, cutting prefill chunks and TTFT p50, with
  ``prefix_hit_rate``/``cow_copies`` reported per cell;
* ``speculative`` — self-drafting speculative decode (n-gram prompt
  lookup + static-shape ``[B, k+1]`` verify) on a repetitive-text
  workload vs the plain-decode baseline, plus an incompressible-random
  contrast cell: acceptance rate, committed tokens per slot-step, and
  decode steps per committed token (< 1.0 = the speculative win);
* ``skew`` — serving-time MoE load balancing under heavy router skew:
  harmoeny + hot-expert replication vs harmoeny / round_robin /
  even_split / static_opt at an equal capacity budget
  (capacity_factor 1.25).  Real-engine cells carry wall TTFT/tok_s, the
  measured max/mean rank-load ratio, the straggler-wait GPU-idle proxy,
  and drop counts; modeled cells cost each step's real schedule over a
  live drifting stream with the calibrated v5e time model, where the
  headline is harmoeny+replication beating the next-best baseline on
  decode throughput;
* ``residency`` — tiered expert residency (host↔HBM streaming) at a
  bounded working-set budget: real-engine cells carry the live
  ``residency`` report (hit rate, swaps, prefetches, staged bytes,
  modeled PCIe stall) across prefetch policies at half the expert
  footprint, and modeled cells cost a paper-scale drifting stream with
  the real scheduler under ``non_local`` demotion — the headline is
  predictive prefetch stalling strictly less than on-demand staging at
  the same budget while recovering ~all fully-resident throughput;
* ``fleet`` — multi-replica serving through the ``FleetRouter``:
  prefix-affinity routing vs load-only / round-robin on a two-group
  shared-prefix trace (affinity pins each prefix group to the replica
  whose radix cache holds it — fewer cold prefill chunks, lower TTFT
  p50), and prefill/decode disaggregation vs two unified replicas under
  a steady-decode + long-prompt-burst mix (the decode-role replica never
  runs prompt prefills, so burst prefill chunks cannot stall in-flight
  decodes — lower TPOT p99 at equal device count);
* ``state_pool`` — sequence-state stores under long-context + bursty
  pressure through the ``SequenceStateStore`` surface: a pure-SSM
  (mamba2) engine on the slotted recurrent-state pool under smooth vs
  bursty arrivals at the same mean rate (bursts oversubscribe the fixed
  slot pool, visible in TTFT p99, while ``state_bytes_per_slot`` stays
  length-independent), a hybrid (zamba2-style) long/short prompt mix,
  and the paged engine serving prompts far beyond its sliding window as
  fixed-size ring-buffer chains (O(window) KV per slot, chains never
  grow);
* ``decode_attention`` — microbench of the per-step decode-attention
  primitive, reference block-table gather vs the fused Pallas kernel,
  sweeping the active sequence length against ``L_max``: the reference
  materializes every row's full ``[L_max]`` logical K/V view regardless
  of actual length (constant bytes), the fused kernel touches only the
  valid blocks (bytes scale with the active length);
* ``phases`` — per-phase serving breakdown (prefill / prefix-tail /
  decode / verify tokens and tokens-per-second) with the unified fused
  path on vs the reference gather, at equal config: a long-context
  (2k-prompt, window disabled) prefix-sharing cell and a
  speculative-verify cell.  The fused cells assert that no hot phase
  dispatches the logical gather (``attention_dispatch`` is fused on
  every traced branch, ``attention_fallbacks`` empty).  Off-TPU the
  fused kernels run in interpret mode, so the wall columns time the
  interpreter, not the kernels.

  PYTHONPATH=src python benchmarks/serve_load.py [--out BENCH_serve.json]
"""
import argparse
import functools
import json
import os
import platform
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses                                            # noqa: E402

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs import get_config                          # noqa: E402
from repro.configs.base import ParallelConfig                 # noqa: E402
from repro.kernels import pallas_interpret                    # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh                  # noqa: E402
from repro.models.model import MeshShape, build_model         # noqa: E402
from repro.serve import (FleetRouter, ServeEngine, WallClock,  # noqa: E402
                         bursty_requests, engine_config_for,
                         long_context_requests, merge_requests,
                         poisson_requests)

ARCH = "mixtral-8x7b"
MODEL_PAR = 2
PROMPT_LEN, GEN, SLOTS, N_REQ = 32, 8, 4, 12
PREFILL_CHUNK = 16
KV_BLOCK = 8
# req/s; 0 = closed batch, 5 ~ inter-arrival on the order of the service
# time (true open-loop interleaving), 50 = overload (arrivals finish in
# ~0.24s, so slot packing converges back to the closed-batch schedule)
RATES = [0.0, 5.0, 50.0]
SKEWS = [0.0, 0.9]
POLICIES = ["harmoeny", "round_robin"]


def build_engine(skew: float, policy: str, skew_seed: int, *,
                 slots: int = SLOTS, paged: bool = True,
                 num_kv_blocks: int = 0, prefix_sharing: bool = False,
                 gen: int = GEN, prompt_len: int = PROMPT_LEN,
                 speculative_k: int = 0, q_tokens: int = 0,
                 replica_slots: int = 0, rebalance_interval: int = 0,
                 resident_experts: int = 0,
                 prefetch_policy: str = "predictive",
                 placement=None):
    cfg = get_config(ARCH).reduced()
    moe = dataclasses.replace(cfg.moe, policy=policy)
    if skew > 0:
        moe = dataclasses.replace(moe, router_skew=skew)
    if q_tokens:
        moe = dataclasses.replace(moe, q_tokens=q_tokens)
    if replica_slots:
        moe = dataclasses.replace(moe, num_replica_slots=replica_slots)
    if placement is not None:
        moe = dataclasses.replace(moe, placement=tuple(int(e)
                                                       for e in placement))
    cfg = cfg.replace(moe=moe)
    mesh = make_host_mesh(data=1, model=MODEL_PAR)
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(cfg, ParallelConfig(attn_chunk=PROMPT_LEN),
                        batch=slots, seq_len=PROMPT_LEN,
                        mesh_shape=ms, mesh=mesh)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(
        model, params,
        engine_config_for(cfg, max_slots=slots, prompt_len=prompt_len,
                          max_new_tokens=gen, prefill_chunk=PREFILL_CHUNK,
                          skew_seed=skew_seed, paged=paged,
                          kv_block_size=KV_BLOCK,
                          num_kv_blocks=num_kv_blocks,
                          prefix_sharing=prefix_sharing,
                          speculative_k=speculative_k,
                          replica_slots=replica_slots,
                          rebalance_interval=rebalance_interval,
                          resident_experts=resident_experts,
                          prefetch_policy=prefetch_policy),
        mesh=mesh)
    engine.warmup()
    return cfg, engine


def _cell(rep, **extra):
    moe = rep.get("moe", {})
    return {
        **extra,
        "n_requests": rep["n_requests"],
        "ttft_p50_ms": rep["ttft"]["p50"] * 1e3,
        "ttft_p99_ms": rep["ttft"]["p99"] * 1e3,
        "tpot_p50_ms": rep["tpot"]["p50"] * 1e3,
        "tpot_p99_ms": rep["tpot"]["p99"] * 1e3,
        "e2e_p50_ms": rep["e2e"]["p50"] * 1e3,
        "tok_s": rep["throughput_tok_s"],
        "mean_occupancy": rep["mean_occupancy"],
        "max_concurrency": rep["max_occupancy"],
        "kv_utilization": rep.get("kv_utilization"),
        "prefix_hit_rate": rep.get("prefix_hit_rate"),
        "cow_copies": rep.get("cow_copies", 0),
        "evictions": rep.get("evictions", 0),
        "preemptions": rep["preemptions"],
        "decode_steps": rep["decode_steps"],
        "prefill_chunks": rep["prefill_chunks"],
        "recompiled_after_warmup": rep.get("recompiled_after_warmup"),
        "moved_units": moe.get("prefill/moved_units", 0.0),
        "drops": (moe.get("prefill/send_drops", 0.0)
                  + moe.get("prefill/dest_drops", 0.0)),
        "max_load_before": moe.get("prefill/max_load_before", 0.0),
        "max_load_after": moe.get("prefill/max_load_after", 0.0),
    }


def sweep():
    results = []
    for skew in SKEWS:
        for policy in POLICIES:
            cfg, engine = build_engine(skew, policy, skew_seed=1)
            for rate in RATES:
                engine.reset_metrics()
                reqs = poisson_requests(
                    N_REQ, rate=rate, vocab_size=cfg.vocab_size,
                    prompt_len=PROMPT_LEN, max_new_tokens=GEN, seed=0)
                rep = engine.run(reqs)
                cell = _cell(rep, rate=rate, skew=skew, policy=policy)
                results.append(cell)
                print(f"[bench] skew={skew} policy={policy:11s} "
                      f"rate={rate:5.0f} "
                      f"ttft_p50={cell['ttft_p50_ms']:8.1f}ms "
                      f"tpot_p50={cell['tpot_p50_ms']:6.2f}ms "
                      f"tok/s={cell['tok_s']:6.1f} "
                      f"kv_util={cell['kv_utilization']:.2f}")
    return results


def capacity_compare():
    """Slab vs paged at the same KV token budget, mixed prompt lengths.

    The slab pool reserves ``max_seq_len`` per slot, capping concurrency at
    SLOTS; the paged pool spends the identical token budget block-by-block
    and decodes more requests at once.
    """
    cells = []
    n_req = 16
    # slab budget: SLOTS x max_seq_len tokens per layer; the paged pool
    # spends one block of it on the reserved null block, so its USABLE
    # budget is one block smaller — physical memory is truly equal
    cfg, slab = build_engine(0.9, "harmoeny", skew_seed=1, paged=False)
    budget = SLOTS * slab.ecfg.max_seq_len
    _, paged = build_engine(0.9, "harmoeny", skew_seed=1, slots=2 * SLOTS,
                            paged=True,
                            num_kv_blocks=budget // KV_BLOCK - 1)
    for rate in (0.0, 50.0):
        for name, engine in (("slab", slab), ("paged", paged)):
            engine.reset_metrics()
            reqs = poisson_requests(
                n_req, rate=rate, vocab_size=cfg.vocab_size,
                prompt_len=PROMPT_LEN, max_new_tokens=GEN, seed=3,
                prompt_len_range=(8, PROMPT_LEN))
            rep = engine.run(reqs)
            cell = _cell(rep, pool=name, rate=rate, skew=0.9,
                         policy="harmoeny",
                         kv_budget_tokens=budget,
                         slots=engine.ecfg.max_slots)
            cells.append(cell)
            print(f"[bench] capacity pool={name:5s} rate={rate:5.0f} "
                  f"max_conc={cell['max_concurrency']} "
                  f"mean_occ={cell['mean_occupancy']:.2f} "
                  f"decode_steps={cell['decode_steps']} "
                  f"tok/s={cell['tok_s']:6.1f}")
    by = {(c["pool"], c["rate"]): c for c in cells}
    gains = {f"rate_{int(r)}":
             by[("paged", r)]["max_concurrency"]
             - by[("slab", r)]["max_concurrency"] for r in (0.0, 50.0)}
    more = all(g > 0 for g in gains.values())
    print(f"[bench] paged concurrency gain at equal memory: {gains} "
          f"(strictly more: {more})")
    return cells, gains, more


def prefix_compare():
    """Shared-prefix traces: prefix sharing on vs off at the same block
    budget.

    Two workloads — a common 24-token system prompt with per-request tails,
    and identical full prompts (the full-hit copy-on-write path).  Each
    cell runs on a fresh engine, then one warming request puts the shared
    prefix in residence before the measured window — the steady-state
    regime prefix caching targets (a system prompt is resident from the
    first seconds of serving; a cold closed batch admits every slot before
    anything is committed and mostly measures scheduler noise).  Sharing
    then serves the common prefix out of the block cache, so every request
    prefills only its tail: fewer prefill chunks, lower TTFT.  The
    no-sharing engine gets the identical warming run (state-symmetric, but
    it has no cache to warm).
    """
    budget = SLOTS * 8          # blocks; same physical pool either way
    cells = []
    for workload, prefix_len in (("system_prompt", 24), ("identical", 32)):
        for share in (False, True):
            cfg, engine = build_engine(0.9, "harmoeny", skew_seed=1,
                                       paged=True, num_kv_blocks=budget,
                                       prefix_sharing=share)
            reqs = poisson_requests(
                N_REQ, rate=0.0, vocab_size=cfg.vocab_size,
                prompt_len=PROMPT_LEN, max_new_tokens=GEN, seed=4,
                shared_prefix_len=prefix_len)
            # same seed => the warming request carries the same shared
            # prefix the measured batch does
            warm = poisson_requests(
                1, rate=0.0, vocab_size=cfg.vocab_size,
                prompt_len=PROMPT_LEN, max_new_tokens=GEN, seed=4,
                shared_prefix_len=prefix_len)
            engine.run(warm)
            engine.reset_metrics()
            rep = engine.run(reqs)
            cell = _cell(rep, workload=workload, sharing=share,
                         shared_prefix_len=prefix_len, skew=0.9,
                         policy="harmoeny", kv_budget_blocks=budget)
            cells.append(cell)
            print(f"[bench] prefix workload={workload:13s} "
                  f"sharing={str(share):5s} "
                  f"ttft_p50={cell['ttft_p50_ms']:8.1f}ms "
                  f"prefill_chunks={cell['prefill_chunks']:3d} "
                  f"hit={cell['prefix_hit_rate']} "
                  f"cow={cell['cow_copies']}")
    by = {(c["workload"], c["sharing"]): c for c in cells}
    reductions = {
        w: by[(w, False)]["ttft_p50_ms"] - by[(w, True)]["ttft_p50_ms"]
        for w in ("system_prompt", "identical")}
    faster = all(r > 0 for r in reductions.values())
    print(f"[bench] prefix sharing TTFT p50 reduction (ms): {reductions} "
          f"(all faster: {faster})")
    return cells, reductions, faster


def speculative_compare():
    """Self-drafting speculative decode on a repetitive-text workload.

    Prompts are a tiled 4-token motif, so the greedy continuation loops
    and the n-gram prompt-lookup proposer keeps finding its suffix — the
    regime speculative decoding targets (code, quoting, templated
    answers).  Each cell decodes the same closed batch with
    ``speculative_k`` in {0, 2, 4}: k = 0 is the plain-decode baseline
    (exactly one slot-step per committed token); k > 0 must report
    acceptance > 0 and per-slot decode steps per committed token < 1.0,
    with greedy streams token-identical across cells (asserted by
    ``tests/test_serve_speculative.py``; here the committed token COUNTS
    are cross-checked).  A contrast cell decodes incompressible random
    prompts at k = 4 — acceptance collapses and steps/token returns to
    ~1.0, the honest bound on when speculation pays off.
    """
    from repro.serve import Request

    # short prompts + a long decode phase (the speculative regime), sized
    # so the padded pool (+k) still fits the reduced model's 64-token
    # sliding window: round_up(16 + 30, 16) + 4 -> 52, block-rounded 56
    n_req, plen, gen = 8, 16, 30
    cells = []

    def requests(workload):
        rng = np.random.default_rng(7)
        reqs = []
        for i in range(n_req):
            if workload == "repetitive":
                motif = rng.integers(0, 64, (4,)).astype(np.int32)
                toks = np.tile(motif, -(-plen // 4))[:plen]
            else:               # incompressible: i.i.d. random prompt
                toks = rng.integers(0, 64, (plen,)).astype(np.int32)
            reqs.append(Request(rid=i, tokens=toks, max_new_tokens=gen))
        return reqs

    for workload, ks in (("repetitive", (0, 2, 4)), ("random", (4,))):
        for k in ks:
            cfg, engine = build_engine(0.9, "harmoeny", skew_seed=1,
                                       gen=gen, prompt_len=plen,
                                       speculative_k=k)
            rep = engine.run(requests(workload))
            sp = rep.get("speculative", {})
            cell = _cell(rep, workload=workload, speculative_k=k,
                         skew=0.9, policy="harmoeny",
                         total_new_tokens=rep["total_new_tokens"],
                         acceptance_rate=sp.get("acceptance_rate"),
                         drafted=sp.get("drafted", 0),
                         accepted=sp.get("accepted", 0),
                         spec_tokens_per_step=sp.get("tokens_per_step"),
                         steps_per_committed_token=sp.get(
                             "steps_per_committed_token"))
            cells.append(cell)
            print(f"[bench] speculative workload={workload:10s} k={k} "
                  f"acc={cell['acceptance_rate']} "
                  f"steps/token={cell['steps_per_committed_token']} "
                  f"decode_steps={cell['decode_steps']:3d} "
                  f"tpot_p50={cell['tpot_p50_ms']:6.2f}ms")
    by = {(c["workload"], c["speculative_k"]): c for c in cells}
    # same workload => same greedy stream => identical committed counts
    tokens_equal = len({by[("repetitive", k)]["total_new_tokens"]
                        for k in (0, 2, 4)}) == 1
    steps_per_token = {
        f"k{k}": by[("repetitive", k)]["steps_per_committed_token"]
        for k in (2, 4)}
    wins = all(v is not None and v < 1.0 for v in steps_per_token.values())
    print(f"[bench] speculative steps/committed token (repetitive): "
          f"{steps_per_token} (< 1.0: {wins}; token counts equal across "
          f"k: {tokens_equal})")
    return cells, steps_per_token, wins, tokens_equal


def skew_compare():
    """Serving under heavy skew: harmoeny + hot-expert replication vs the
    baselines, at an equal per-rank capacity budget (capacity_factor 1.25).

    Two instruments per policy (the split the simulator docstring
    mandates — wall-clock on CPU-emulated devices cannot see imbalance,
    because every rank executes the same static-shape program):

    * **engine cells** — the real serving engine at router_skew 0.9:
      wall TTFT/tok_s, the measured per-rank load vectors (max/mean
      ratio, straggler-wait GPU-idle proxy), scheduler drop counts, and
      replica swap counts.  Greedy streams are token-identical across
      policies (asserted in tests), so every cell decodes the same
      tokens.
    * **modeled cells** — the calibrated v5e time model over a live
      drifting stream at paper scale (G=8, E=64, a large fused batch per
      step, movement granularity at the Eq. 4 q-threshold): phase 1
      draws from the 4-hot-expert profile ``static_opt`` was placed for,
      phase 2 drifts to one scorching previously-cold expert.  Each
      step's REAL schedule (core/scheduler.py, same code the engine
      jits) is capacity-clamped at 1.25x the mean per-rank load (the
      dispatch drop path: an imbalanced policy drops the excess AND
      still waits on its clamped hottest rank) and costed with
      ``simulate_layer``; throughput counts delivered units only.  The
      replication cell feeds the live ``expert_load`` stream through the
      same ``ExpertRebalancer`` the engine uses and credits
      replica-resident experts as fetch-free.

    Headline: modeled delivered throughput of harmoeny + replication
    beats the next-best baseline under skew >= 0.8, while its
    capacity-budget overflow (the dispatch drop proxy) stays ~0.
    """
    engine_cells = skew_engine_cells()
    modeled = skew_modeled_cells()

    by = {c["policy"]: c for c in modeled}
    ours = by["harmoeny+replication"]
    best_baseline = max((c for c in modeled
                         if c["policy"] != "harmoeny+replication"),
                        key=lambda c: c["tok_s_modeled"])
    headline = {
        "ours_tok_s": ours["tok_s_modeled"],
        "next_best_policy": best_baseline["policy"],
        "next_best_tok_s": best_baseline["tok_s_modeled"],
        "speedup_vs_next_best":
            ours["tok_s_modeled"] / best_baseline["tok_s_modeled"],
        "beats_next_best":
            ours["tok_s_modeled"] > best_baseline["tok_s_modeled"],
        "ours_overflow_units": ours["overflow_units_total"],
        "ours_overflow_steady_units": ours["overflow_units_steady"],
        "engine_drops_zero": all(
            c["send_drops"] + c["dest_drops"] == 0 for c in engine_cells
            if c["policy"] != "even_split"),
    }
    print(f"[bench] skew headline: ours={headline['ours_tok_s']:.0f} tok/s "
          f"vs {headline['next_best_policy']}="
          f"{headline['next_best_tok_s']:.0f} "
          f"({headline['speedup_vs_next_best']:.2f}x, beats: "
          f"{headline['beats_next_best']}); overflow="
          f"{headline['ours_overflow_units']:.0f} "
          f"(steady={headline['ours_overflow_steady_units']:.0f})")
    return {"engine_cells": engine_cells, "modeled_cells": modeled,
            "headline": headline}


SKEW = 0.9
CF = 1.25


def skew_engine_cells():
    """Real-engine skew cells (see ``skew_compare``)."""
    from repro.core.topology import static_opt_placement

    engine_cells = []
    prof = None
    for name in ("harmoeny+replication", "harmoeny", "round_robin",
                 "even_split", "static_opt"):
        policy = name.split("+")[0]
        kw = {}
        if name == "harmoeny+replication":
            kw = dict(replica_slots=1, rebalance_interval=4)
        if policy == "static_opt":
            # profile-then-place against the synthetic skew distribution
            cfg0 = get_config(ARCH).reduced()
            E, H = cfg0.moe.num_experts, cfg0.moe.router_skew_experts
            prof = np.full((E,), (1.0 - SKEW) / max(E - H, 1))
            prof[:H] = SKEW / max(H, 1)
            kw = dict(placement=static_opt_placement(
                (prof * 10_000).astype(np.int64), MODEL_PAR))
        cfg, engine = build_engine(SKEW, policy, skew_seed=1, q_tokens=2,
                                   **kw)
        reqs = poisson_requests(N_REQ, rate=0.0, vocab_size=cfg.vocab_size,
                                prompt_len=PROMPT_LEN, max_new_tokens=GEN,
                                seed=5)
        rep = engine.run(reqs)
        lb = rep.get("load_balance", {}).get("decode", {})
        cell = {
            "policy": name, "skew": SKEW, "capacity_factor": CF,
            "ttft_p50_ms": rep["ttft"]["p50"] * 1e3,
            "tok_s_wall": rep["throughput_tok_s"],
            "max_mean_ratio": lb.get("max_mean_ratio"),
            "straggler_wait_units": lb.get("straggler_wait_units"),
            "send_drops": lb.get("send_drops_total", 0.0),
            "dest_drops": lb.get("dest_drops_total", 0.0),
            "replica_swaps": rep["engine"].get("replica_swaps", 0),
            "hot_experts": rep["engine"].get("hot_experts", []),
            "recompiled_after_warmup": rep.get("recompiled_after_warmup"),
        }
        engine_cells.append(cell)
        print(f"[bench] skew-engine {name:21s} "
              f"ttft_p50={cell['ttft_p50_ms']:7.1f}ms "
              f"tok/s={cell['tok_s_wall']:6.1f} "
              f"ratio={cell['max_mean_ratio']:.2f} "
              f"straggler={cell['straggler_wait_units']:.1f} "
              f"drops={cell['send_drops']:.0f}/{cell['dest_drops']:.0f} "
              f"swaps={cell['replica_swaps']}")
    return engine_cells


def skew_modeled_cells():
    """v5e-modeled drifting-stream skew cells (see ``skew_compare``)."""
    import jax.numpy as jnp
    from repro.core.scheduler import schedule
    from repro.core.simulator import SimCosts, simulate_layer
    from repro.core.topology import make_topology, static_opt_placement
    from repro.serve.rebalance import ExpertRebalancer

    # ---------------- modeled cells (drifting stream, v5e time model) --
    # Paper-scale operating point: U token units per step (a large fused
    # decode/verify batch over many concurrent requests) and movement
    # granularity Q set to the Eq. 4 q-threshold under the sim's own cost
    # model — the smallest chunk whose compute masks one expert fetch
    # (fetch_s / comp_per_unit_s).  Below this scale redistribution can
    # never pay (fetch dominates), which is precisely the paper's point.
    G, E, K_SLOTS, R_SLOTS = 8, 64, 4, 4
    U, T = 65536, 120
    N_HOT = 4
    costs = SimCosts()
    comp_unit_s = costs.unit_flops / (costs.hw.peak_flops * costs.mfu)
    fetch_s = costs.expert_bytes * costs.fetch_penalty / costs.hw.ici_bw
    Q = int(np.ceil(fetch_s / comp_unit_s))
    rng = np.random.default_rng(11)

    def probs(phase):
        p = np.full((E,), 0.0)
        if phase == 0:                  # matches static_opt's profile
            p[:] = (1.0 - SKEW) / (E - N_HOT)
            p[:N_HOT] = SKEW / N_HOT
        else:                           # drift: one scorching cold expert
            p[:] = (1.0 - SKEW) / (E - 1)
            p[E // 2] = SKEW
        return p

    place = static_opt_placement(
        (probs(0) * 10_000).astype(np.int64), G)
    topos = {"static_opt": make_topology(G, E, placement=place)}
    base_topo = make_topology(G, E)
    cap = CF * U / G
    modeled = []
    for name in ("harmoeny+replication", "harmoeny", "round_robin",
                 "even_split", "static_opt"):
        policy = name.split("+")[0]
        topo = topos.get(name, base_topo)
        rb = (ExpertRebalancer(topo, R_SLOTS)
              if name == "harmoeny+replication" else None)
        extra = None
        layer_s = np.zeros(2)
        units = np.zeros(2)
        idle = []
        overflow = 0.0
        overflow_steady = 0.0
        # adaptation windows: the EMA rebalancer cannot react before its
        # next proposal, so overflow inside 2 proposal periods after t=0
        # and after the phase flip is inherent drift lag, not steady-state
        # behaviour — both numbers are reported
        P = 10
        warmup = set(range(0, 2 * P)) | set(range(T // 2, T // 2 + 2 * P))
        ratios = []
        for t in range(T):
            phase = 0 if t < T // 2 else 1
            counts = rng.multinomial(U // G, probs(phase), size=G)
            S, diag = schedule(jnp.asarray(counts, jnp.int32), topo,
                               policy=policy, q=Q, c_pair=10 ** 6,
                               num_foreign_slots=K_SLOTS,
                               extra_local=(None if extra is None
                                            else jnp.asarray(extra)))
            # Equal capacity budget: every destination computes at most
            # ``cap`` units; the rest is dropped at dispatch (the engine's
            # dest_drops path).  Throughput counts delivered units only,
            # and layer time is costed on the clamped schedule — an
            # imbalanced policy both drops tokens AND still waits on its
            # (capacity-clamped) hottest rank.
            S_np = np.asarray(S, np.float64)
            load = S_np.sum(axis=(0, 1))
            over = float(np.maximum(load - cap, 0.0).sum())
            overflow += over
            if t not in warmup:
                overflow_steady += over
            scale = np.where(load > cap, cap / np.maximum(load, 1e-9), 1.0)
            S_del = S_np * scale[None, None, :]
            sim = simulate_layer(S_del, topo, costs,
                                 sched_iters=int(diag.iters),
                                 drops=over, extra_local=extra)
            layer_s[phase] += sim["layer_s"]
            units[phase] += float(S_del.sum())
            idle.append(sim["idle_frac_mean"])
            ratios.append(float(load.max() / max(load.mean(), 1e-9)))
            if rb is not None:
                rb.observe(S_np.sum(axis=(0, 2)))
                if (t + 1) % P == 0:
                    dec = rb.propose()
                    if dec.changed:
                        ids = dec.replica_ids
                        extra = np.zeros((G, topo.padded_experts), bool)
                        for g in range(G):
                            for e in ids[g]:
                                if e >= 0:
                                    extra[g, e] = True
        cell = {
            "policy": name, "skew": SKEW, "capacity_factor": CF,
            "ranks": G, "experts": E, "units_per_step": U,
            "q_units": Q,
            "delivered_frac": float(units.sum() / (U * T)),
            "tok_s_modeled": float(units.sum() / layer_s.sum()),
            "tok_s_modeled_phase1": float(units[0] / layer_s[0]),
            "tok_s_modeled_phase2": float(units[1] / layer_s[1]),
            "layer_us_mean": float(layer_s.sum() / T * 1e6),
            "idle_frac_mean": float(np.mean(idle)),
            "imbalance_mean": float(np.mean(ratios)),
            "overflow_units_total": overflow,
            "overflow_units_steady": overflow_steady,
        }
        modeled.append(cell)
        print(f"[bench] skew-model  {name:21s} "
              f"tok/s={cell['tok_s_modeled']:12.0f} "
              f"(p1 {cell['tok_s_modeled_phase1']:12.0f} / "
              f"p2 {cell['tok_s_modeled_phase2']:12.0f}) "
              f"idle={cell['idle_frac_mean']:.2f} "
              f"imb={cell['imbalance_mean']:.2f} "
              f"overflow={cell['overflow_units_total']:.0f}"
              f"/steady {cell['overflow_units_steady']:.0f}")
    return modeled


def residency_compare():
    """Tiered expert residency: host↔HBM streaming at a bounded HBM budget.

    Two instruments, same split as ``skew_compare``:

    * **engine cells** — the real serving engine under router skew with a
      tight working-set budget (``resident_experts`` = half the expert
      rows, W = epr/2 per rank) across the three prefetch policies plus
      the fully-resident baseline.  Greedy streams are token-identical
      across budgets by construction (device params stay authoritative —
      asserted in tests); the cells carry the live ``residency`` report:
      hit rate, swap/prefetch counts, staged bytes, and the
      TierCostModel-priced stall seconds of the emulated PCIe tier.

    * **modeled cells** — paper-scale (G=8, E=64) layer costing over a
      drifting two-MoE-layer stream.  Each step schedules with the REAL
      HarMoEny scheduler under the ``non_local`` demotion mask derived
      from the previous step's residency table (double-buffered, exactly
      the engine's discipline), and is costed with ``simulate_layer``;
      host-tier stalls are charged from the ``ExpertResidencyManager``
      replay itself — the only party that knows which misses the
      predictive policy staged *ahead* of first touch (hidden behind the
      previous layer's compute window) versus paid for on demand.  All
      demoted pairs are passed as ``hidden_stages`` so the simulator does
      not double-charge the tier on top of the manager's accounting.

      The stream: layer 0 routes to one stable expert per rank; layer 1
      routes to a second expert per rank that *drifts* to a cold third
      mid-run.  ``predictive`` prefetches the incoming expert during
      layer 0's window of the very first post-drift step (the per-layer
      EMA folds the step's own loads before the replay), ``on_demand``
      stalls once per rank on first touch, and ``none`` stalls on every
      single post-drift use of the never-admitted expert — whose demotion
      also reroutes its tokens as fetch-paying foreign work in the
      schedule.

    Headline: at half the HBM footprint, predictive stalls strictly less
    than on_demand and recovers ~all of the fully-resident modeled
    throughput, while ``none`` (no streaming) pays a persistent tier
    penalty.
    """
    engine_cells = residency_engine_cells()
    modeled = residency_modeled_cells()

    by = {c["cell"]: c for c in modeled}
    pred, odem = by["predictive"], by["on_demand"]
    headline = {
        "budget_experts": pred["resident_experts"],
        "footprint_frac": pred["footprint_frac"],
        "predictive_stall_s": pred["host_stall_s"],
        "on_demand_stall_s": odem["host_stall_s"],
        "predictive_beats_on_demand_on_stall":
            pred["host_stall_s"] < odem["host_stall_s"],
        "recovered_throughput_frac":
            pred["tok_s_modeled"] / by["fully_resident"]["tok_s_modeled"],
        "none_throughput_frac":
            by["none"]["tok_s_modeled"]
            / by["fully_resident"]["tok_s_modeled"],
        "engine_predictive_hit_rate": next(
            (c["hit_rate"] for c in engine_cells
             if c["cell"] == "predictive"), None),
    }
    print(f"[bench] residency headline: budget={headline['budget_experts']} "
          f"({headline['footprint_frac']:.0%} footprint) "
          f"stall pred={headline['predictive_stall_s'] * 1e3:.2f}ms vs "
          f"odem={headline['on_demand_stall_s'] * 1e3:.2f}ms "
          f"(beats: {headline['predictive_beats_on_demand_on_stall']}); "
          f"recovered={headline['recovered_throughput_frac']:.3f} "
          f"none={headline['none_throughput_frac']:.3f}")
    return {"engine_cells": engine_cells, "modeled_cells": modeled,
            "headline": headline}


def residency_engine_cells():
    """Real-engine residency cells (see ``residency_compare``)."""
    cfg0 = get_config(ARCH).reduced()
    E = cfg0.moe.num_experts                      # pod expert rows (epr*G)
    cells = []
    for name, budget, policy in (
            ("fully_resident", E, "predictive"),
            ("predictive", E // 2, "predictive"),
            ("on_demand", E // 2, "on_demand"),
            ("none", E // 2, "none")):
        cfg, engine = build_engine(SKEW, "harmoeny", skew_seed=1,
                                   resident_experts=budget,
                                   prefetch_policy=policy)
        reqs = poisson_requests(N_REQ, rate=0.0, vocab_size=cfg.vocab_size,
                                prompt_len=PROMPT_LEN, max_new_tokens=GEN,
                                seed=5)
        rep = engine.run(reqs)
        res = rep["residency"]
        cell = {
            "cell": name, "policy": policy, "skew": SKEW,
            "resident_experts": budget,
            "footprint_frac": budget / E,
            "tok_s_wall": rep["throughput_tok_s"],
            "hit_rate": res["hit_rate"],
            "swaps": res["swaps"],
            "prefetches": res["prefetches"],
            "stall_s": res["stall_units"],
            "bytes_staged": res["bytes_staged"],
            "residency_stages": rep["engine"]["residency_stages"],
            "recompiled_after_warmup": rep.get("recompiled_after_warmup"),
        }
        cells.append(cell)
        print(f"[bench] residency-engine {name:14s} budget={budget} "
              f"hit={cell['hit_rate']:.3f} swaps={cell['swaps']:4d} "
              f"prefetch={cell['prefetches']:4d} "
              f"stall={cell['stall_s'] * 1e3:7.2f}ms "
              f"staged={cell['bytes_staged'] / 2 ** 20:7.1f}MB "
              f"tok/s={cell['tok_s_wall']:6.1f}")
    return cells


def residency_modeled_cells():
    """v5e-modeled drifting-stream residency cells (see
    ``residency_compare``)."""
    import gc

    import jax
    import jax.numpy as jnp

    # By this point every earlier section has compiled its own engines and
    # the process carries thousands of cached CPU executables; the LLVM JIT
    # can hit mmap exhaustion (ENOMEM → segfault) on the next burst of
    # compilations. Drop the compile caches before the modeled loop — the
    # remaining sections build fresh engines and recompile regardless.
    jax.clear_caches()
    gc.collect()
    from repro.core.scheduler import schedule
    from repro.core.simulator import SimCosts, simulate_layer
    from repro.core.topology import local_slot_of, make_topology
    from repro.serve.residency import ExpertResidencyManager, TierCostModel

    G, E, L = 8, 64, 2
    U, T = 65536, 80
    K_SLOTS = 4
    W = 4                                # budget: half of epr=8 per rank
    costs = SimCosts()
    comp_unit_s = costs.unit_flops / (costs.hw.peak_flops * costs.mfu)
    fetch_s = costs.expert_bytes * costs.fetch_penalty / costs.hw.ici_bw
    Q = int(np.ceil(fetch_s / comp_unit_s))
    topo = make_topology(G, E)
    Ep = topo.padded_experts
    lsl = local_slot_of(topo)

    # per-layer active experts, ONE local slot per rank per layer: layer 0
    # stays on slot 0; layer 1 uses slot 1 and drifts to the cold slot 5
    # at T/2 (outside the seeded working set {slots 0..W-1}, so only
    # streaming can admit it)
    def active_slots(layer, phase):
        return {(0, 0): 0, (0, 1): 0, (1, 0): 1, (1, 1): 5}[(layer, phase)]

    def layer_counts(rng, layer, phase):
        j = active_slots(layer, phase)
        p = np.zeros(Ep)
        for g in range(G):
            p[int(topo.slot_map[g, j])] = 1.0 / G
        return rng.multinomial(U // G, p, size=G)            # [G, Ep]

    cells = []
    for name, budget, policy in (
            ("fully_resident", G * topo.experts_per_rank, "predictive"),
            ("predictive", G * W, "predictive"),
            ("on_demand", G * W, "on_demand"),
            ("none", G * W, "none")):
        mgr = ExpertResidencyManager(
            topo, budget, policy=policy,
            cost=TierCostModel(expert_bytes=costs.expert_bytes,
                               pcie_bw=costs.host_bw))
        rng = np.random.default_rng(13)      # same stream in every cell
        compute_s = 0.0
        stall_s = 0.0
        units = 0.0
        for t in range(T):
            phase = 0 if t < T // 2 else 1
            # double-buffered: step t schedules under the table published
            # at the end of step t-1, exactly like the engine
            ids = mgr._last_ids
            res = np.zeros((G, Ep), bool)
            for g in range(G):
                for e in ids[g]:
                    if e >= 0:
                        res[g, int(e)] = True
            non_local = (lsl >= 0) & ~res
            loads = np.zeros((L, Ep))
            for layer in range(L):
                counts = layer_counts(rng, layer, phase)
                loads[layer] = counts.sum(axis=0)
                S, diag = schedule(jnp.asarray(counts, jnp.int32), topo,
                                   policy="harmoeny", q=Q, c_pair=10 ** 6,
                                   num_foreign_slots=K_SLOTS,
                                   non_local=jnp.asarray(non_local))
                S_np = np.asarray(S, np.float64)
                sim = simulate_layer(S_np, topo, costs,
                                     sched_iters=int(diag.iters),
                                     non_local=non_local,
                                     hidden_stages=non_local)
                compute_s += sim["layer_s"]
                units += float(S_np.sum())
            dec = mgr.step(loads)
            stall_s += dec.stall_units
        w = mgr.counters()
        total_s = compute_s + stall_s
        cell = {
            "cell": name, "policy": policy,
            "ranks": G, "experts": E, "units_per_step": U,
            "moe_layers": L, "steps": T, "q_units": Q,
            "resident_experts": budget,
            "footprint_frac": budget / (G * topo.experts_per_rank),
            "tok_s_modeled": float(units / total_s),
            "layer_us_mean": float(compute_s / (T * L) * 1e6),
            "host_stall_s": float(stall_s),
            "stall_frac": float(stall_s / total_s),
            "hit_rate": w["hit_rate"],
            "swaps": w["swaps"],
            "prefetches": w["prefetches"],
            "bytes_staged": w["bytes_staged"],
        }
        cells.append(cell)
        print(f"[bench] residency-model  {name:14s} budget={budget:2d} "
              f"({cell['footprint_frac']:.0%}) "
              f"tok/s={cell['tok_s_modeled']:12.0f} "
              f"stall={cell['host_stall_s'] * 1e3:8.2f}ms "
              f"({cell['stall_frac']:.1%}) hit={cell['hit_rate']:.3f} "
              f"swaps={cell['swaps']:3d} prefetch={cell['prefetches']:3d}")
    return cells


def decode_attention_microbench():
    """Reference gather vs fused kernel, active length swept against L_max.

    The reference (``paged_decode_attention``) gathers each row's full
    ``[L_max, Hkv, hd]`` logical K/V view and repeats KV heads per q head
    every decode step, so its memory traffic is constant in the actual
    sequence length; the fused kernel walks the block table inside the
    kernel and reads only ``ceil(active / block_size)`` blocks per row.
    Off-TPU the kernel runs in interpret mode, so its absolute wall time
    is not meaningful there — the theoretical bytes columns (and the
    reference timings) carry the comparison; on TPU both time columns are
    real.  Every cell also cross-checks parity (``max_abs_err``).
    """
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.models.attention import paged_decode_attention

    B, Hkv, rep, hd, bs = 4, 2, 4, 64, 16
    l_max = 512
    n_logical = l_max // bs
    num_blocks = 1 + B * n_logical
    P = num_blocks * bs
    key = jax.random.PRNGKey(0)
    k_pool = jax.random.normal(jax.random.fold_in(key, 1), (1, P, Hkv, hd))
    v_pool = jax.random.normal(jax.random.fold_in(key, 2), (1, P, Hkv, hd))
    q = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, Hkv * rep, hd))
    interpret = pallas_interpret()
    ref_fn = jax.jit(functools.partial(paged_decode_attention,
                                       block_size=bs))
    fused_fn = jax.jit(functools.partial(paged_attention, block_size=bs,
                                         interpret=interpret))
    perm = np.random.default_rng(0).permutation(np.arange(1, num_blocks))

    def timed(fn, bt, cl, iters):
        out = fn(q, k_pool, v_pool, bt, cl)
        jax.block_until_ready(out)                    # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k_pool, v_pool, bt, cl)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3, np.asarray(
            out, np.float32)

    cells = []
    for active in (32, 128, 512):
        bt = np.zeros((B, n_logical), np.int32)
        i = 0
        for b in range(B):                            # rest stay null
            nv = active // bs
            bt[b, :nv] = perm[i:i + nv]
            i += nv
        btj = jnp.asarray(bt)
        cl = jnp.full((B,), active, jnp.int32)
        ref_ms, ref_out = timed(ref_fn, btj, cl, iters=30)
        fused_ms, fused_out = timed(fused_fn, btj, cl, iters=5)
        leaf_bytes = 2 * Hkv * hd * 4                 # K+V, f32
        cell = {
            "active_len": active, "l_max": l_max,
            "gather_ref_ms": ref_ms, "fused_ms": fused_ms,
            "gather_ref_bytes": B * l_max * leaf_bytes,
            "fused_bytes": B * active * leaf_bytes,
            "max_abs_err": float(np.abs(ref_out - fused_out).max()),
        }
        cells.append(cell)
        print(f"[bench] decode_attn active={active:4d}/{l_max} "
              f"gather_ref={ref_ms:7.3f}ms ({cell['gather_ref_bytes']:>9d} B)"
              f"  fused={fused_ms:7.3f}ms ({cell['fused_bytes']:>9d} B)  "
              f"err={cell['max_abs_err']:.2e}")
    return {
        "shape": {"batch": B, "kv_heads": Hkv, "gqa_rep": rep, "head_dim": hd,
                  "block_size": bs, "l_max": l_max},
        "fused_interpret_mode": interpret,
        "cells": cells,
    }


def _phase_engine(*, fused: bool, prompt_len: int, gen: int, chunk: int,
                  prefix_sharing: bool = False, speculative_k: int = 0,
                  slots: int = 2, kv_block: int = KV_BLOCK):
    """Engine for the phase-breakdown cells: window disabled (long-context
    paged pools exceed the reduced arch's 64-token window), unified fused
    path (q-tiled prefill attention + paged decode/verify + grouped-GEMM
    MoE) on or off as one switch."""
    # window disabled for long paged pools; 2 layers keep the interpret-
    # mode (off-TPU) fused cells inside a sane wall budget — the fused /
    # gather contrast is per-layer, so the layer count cancels out
    cfg = get_config(ARCH).reduced().replace(sliding_window=0, num_layers=2)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, policy="harmoeny"))
    mesh = make_host_mesh(data=1, model=MODEL_PAR)
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(cfg, ParallelConfig(attn_chunk=min(512, prompt_len)),
                        batch=slots, seq_len=prompt_len,
                        mesh_shape=ms, mesh=mesh)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(
        model, params,
        engine_config_for(cfg, max_slots=slots, prompt_len=prompt_len,
                          max_new_tokens=gen, prefill_chunk=chunk,
                          skew_seed=1, paged=True, kv_block_size=kv_block,
                          prefix_sharing=prefix_sharing,
                          speculative_k=speculative_k,
                          fused_paged_attention=fused,
                          fused_moe_gmm=fused),
        mesh=mesh)
    engine.warmup()
    return cfg, engine


def phases_breakdown():
    """Per-phase tokens and tok/s, fused vs gather (docstring top).

    Cell pairs (identical workload, greedy, token counts cross-checked):

    * ``long_prefill`` — 2048-token prompts sharing a 1024-token prefix,
      prefix sharing on: exercises prefill, prefix-tail resume, and plain
      decode.
    * ``spec_verify`` — short repetitive prompts, speculative k=4:
      exercises the [B, k+1] verify phase.
    """
    interpret = pallas_interpret()
    cells = []

    def run_cell(workload, *, fused, prompt_len, gen, chunk, sharing,
                 k, n_req, shared_prefix=0, kv_block=KV_BLOCK):
        cfg, engine = _phase_engine(fused=fused, prompt_len=prompt_len,
                                    gen=gen, chunk=chunk,
                                    prefix_sharing=sharing,
                                    speculative_k=k, kv_block=kv_block)
        if workload == "spec_verify":
            # tiled motif prompts so the n-gram proposer drafts well and
            # the verify phase commits multi-token windows
            from repro.serve import Request
            rng = np.random.default_rng(7)
            reqs = []
            for i in range(n_req):
                motif = rng.integers(0, 64, (4,)).astype(np.int32)
                reqs.append(Request(
                    rid=i, tokens=np.tile(motif, -(-prompt_len // 4))
                    [:prompt_len], max_new_tokens=gen))
        else:
            reqs = poisson_requests(
                n_req, rate=0.0, vocab_size=cfg.vocab_size,
                prompt_len=prompt_len, max_new_tokens=gen, seed=6,
                shared_prefix_len=shared_prefix)
        t0 = time.perf_counter()
        if sharing:
            # a cold first request populates the prefix cache INSIDE the
            # measured window: its chunks are the plain-prefill phase, the
            # same-seed followers resume off its cached prefix and land in
            # the prefix-tail phase
            warm = poisson_requests(
                1, rate=0.0, vocab_size=cfg.vocab_size,
                prompt_len=prompt_len, max_new_tokens=gen, seed=6,
                shared_prefix_len=shared_prefix)
            engine.run(warm)
        rep = engine.run(reqs)
        wall_s = time.perf_counter() - t0
        phases = {name: dict(ph)
                  for name, ph in rep.get("phases", {}).items()}
        cell = {
            "workload": workload, "fused": fused,
            "prompt_len": prompt_len, "gen": gen,
            "prefill_chunk": chunk, "speculative_k": k,
            "prefix_sharing": sharing, "n_requests": n_req,
            "total_new_tokens": rep["total_new_tokens"],
            "e2e_wall_s": wall_s,
            "e2e_tok_s_wall": rep["total_new_tokens"] / wall_s,
            "phases": phases,
            "attention_dispatch": rep.get("attention_dispatch", {}),
            "attention_fallbacks": rep.get("attention_fallbacks", {}),
        }
        if fused:
            # acceptance: with use_pallas on, no hot phase may dispatch
            # the [B, L_max] logical gather or silently fall back
            assert cell["attention_fallbacks"] == {}, \
                f"silent fused fallbacks: {cell['attention_fallbacks']}"
            for branch, d in cell["attention_dispatch"].items():
                assert d["fused"], f"branch {branch} fell back: {d}"
        cells.append(cell)
        for name, ph in sorted(phases.items()):
            print(f"[bench] phases {workload:12s} fused={str(fused):5s} "
                  f"{name:11s} tokens={ph['tokens']:6d} "
                  f"tok/s={ph['tokens_per_s']:9.1f}")
        return cell

    # long-context: 2048-token prompts; s_pad = 2048 + 256 (round-up) +
    # 256 (prefix-sharing chunk) = 2560 = 20 x 128-token slab tiles
    for fused in (False, True):
        # 64-token KV blocks: the default 8-token blocks make the
        # interpret-mode decode grid 8x deeper on the 2.5k-token pool
        # for no extra information
        run_cell("long_prefill", fused=fused, prompt_len=2048, gen=8,
                 chunk=256, sharing=True, k=0, n_req=2,
                 shared_prefix=1024, kv_block=64)
    for fused in (False, True):
        run_cell("spec_verify", fused=fused, prompt_len=16, gen=30,
                 chunk=16, sharing=False, k=4, n_req=4)

    by = {(c["workload"], c["fused"]): c for c in cells}
    summary = {}
    for w in ("long_prefill", "spec_verify"):
        g, f = by[(w, False)], by[(w, True)]
        assert g["total_new_tokens"] == f["total_new_tokens"], \
            "fused and gather cells decoded different streams"
        hot = "prefix_tail" if w == "long_prefill" else "verify"
        summary[w] = {
            "tokens_identical": True,
            "hot_phase": hot,
            "hot_phase_tok_s_gather": g["phases"][hot]["tokens_per_s"],
            "hot_phase_tok_s_fused": f["phases"][hot]["tokens_per_s"],
            "e2e_tok_s_wall_gather": g["e2e_tok_s_wall"],
            "e2e_tok_s_wall_fused": f["e2e_tok_s_wall"],
        }
        print(f"[bench] phases headline {w}: {hot} tok/s "
              f"{summary[w]['hot_phase_tok_s_gather']:.1f} (gather) vs "
              f"{summary[w]['hot_phase_tok_s_fused']:.1f} (fused)")
    return {"fused_interpret_mode": interpret, "cells": cells,
            "summary": summary}


def build_fleet(roles, *, routing="load", affinity_weight=1.0,
                prompt_len=PROMPT_LEN, gen=GEN, slots=SLOTS,
                num_kv_blocks=0, prefix_sharing=False,
                prefill_chunk=PREFILL_CHUNK):
    """N virtual replicas on the 2-device group: one model + one set of
    weights, one engine (and KV pool) per role entry, one shared wall
    clock, a ``FleetRouter`` on top."""
    # window disabled: the fleet cells run 64-token prompts plus decode,
    # and reduced() clamps the arch to a 64-token window that would
    # reject the block-rounded paged pool
    cfg = get_config(ARCH).reduced().replace(sliding_window=0)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, policy="harmoeny"))
    mesh = make_host_mesh(data=1, model=MODEL_PAR)
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(cfg, ParallelConfig(attn_chunk=min(512, prompt_len)),
                        batch=slots, seq_len=prompt_len,
                        mesh_shape=ms, mesh=mesh)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    clock = WallClock()
    engines = [ServeEngine(
        model, params,
        engine_config_for(cfg, max_slots=slots, prompt_len=prompt_len,
                          max_new_tokens=gen, prefill_chunk=prefill_chunk,
                          skew_seed=1, paged=True, kv_block_size=KV_BLOCK,
                          num_kv_blocks=num_kv_blocks,
                          prefix_sharing=prefix_sharing, role=role),
        mesh=mesh, clock=clock) for role in roles]
    fleet = FleetRouter(engines, policy=routing,
                        affinity_weight=affinity_weight)
    fleet.warmup()
    return cfg, fleet


def _fleet_cell(rep, **extra):
    fl = rep["fleet"]
    agg, routing = fl["aggregate"], fl["routing"]
    return {
        **extra,
        "n_requests": agg["n_requests"],
        "ttft_p50_ms": agg["ttft"]["p50"] * 1e3,
        "ttft_p99_ms": agg["ttft"]["p99"] * 1e3,
        "tpot_p50_ms": agg["tpot"]["p50"] * 1e3,
        "tpot_p99_ms": agg["tpot"]["p99"] * 1e3,
        "e2e_p50_ms": agg["e2e"]["p50"] * 1e3,
        "queue_delay_p50_ms": agg["queue_delay"]["p50"] * 1e3,
        "tok_s": agg["throughput_tok_s"],
        "goodput_req_s": agg["goodput_req_s"],
        "prefix_hit_rate": agg["prefix_hit_rate"],
        "routed_per_replica": routing["per_replica"],
        "affinity_hit_rate": routing["affinity_hit_rate"],
        "affinity_hit_tokens": routing["affinity_hit_tokens"],
        "prefill_chunks_total": sum(r["prefill_chunks"]
                                    for r in rep["replica_reports"]),
        "preemptions": agg["preemptions"],
        "handoffs_moved": fl["handoffs"]["moved"],
        "handoff_mib": fl["handoffs"]["bytes"] / 2 ** 20,
        "recompiled_after_warmup": [
            bool(r.get("recompiled_after_warmup"))
            for r in rep["replica_reports"]],
    }


def fleet_compare():
    """Fleet serving: prefix-affinity routing and prefill/decode
    disaggregation vs their single-policy baselines.

    * **routing cells** — 2 unified replicas with prefix sharing on a
      THREE-group shared-prefix trace (three 56-token system prompts,
      8-token tails) under a block budget that fits about two cached
      prefixes plus active chains per replica.  ``load`` routing balances
      instantaneous queued+KV tokens and ignores cache state, so all
      three groups keep landing on both replicas and the LRU prefix cache
      thrashes — repeated cold 4-chunk prefills; ``prefix_affinity``
      probes each replica's radix index (LRU-neutral) and pins each group
      where its prefix is already resident, so each replica serves a
      stable subset of prefixes from cache and followers prefill only
      their 8-token tail.  Fewer prefill chunks is the deterministic
      work-saved signal; lower TTFT p50 is the headline.
    * **disaggregation cells** — a steady short-prompt/long-decode stream
      plus a mid-run burst of long-prompt prefill-only requests
      (``max_new_tokens=1``: the first token finishes them, so they never
      hand off), on 2 unified replicas vs 1 prefill-role + 1 decode-role
      replica (KV handoff).  Unified replicas interleave the burst's
      prefill chunks with in-flight decode steps, stalling every decode
      slot they share an engine with; the disaggregated decode replica
      never runs prompt prefills, so the burst cannot touch its decode
      cadence — lower TPOT p99 at equal device count is the headline.
    """
    cells = {"routing": [], "disaggregation": []}

    # -------------- routing: three prefix groups, 2 unified replicas ---
    # budget: a 56-token prefix caches as 7 blocks; 30 blocks hold two
    # prefixes plus active chains, NOT all three — cache-blind routing
    # thrashes, affinity partitions the groups across the replicas
    plen, tail_gen, prefix_len, n_per_group = 64, 8, 56, 8
    for routing in ("load", "round_robin", "prefix_affinity"):
        # weight 3: a 56-token cached prefix offsets ~168 tokens of load
        # (≈ 2.5 queued prompts), so a warm replica keeps its group even
        # while briefly busier — weight 1 lets one queued prompt push the
        # group onto a cold replica and duplicate its cache footprint
        cfg, fleet = build_fleet(["unified"] * 2, routing=routing,
                                 affinity_weight=3.0,
                                 prompt_len=plen, gen=tail_gen, slots=3,
                                 num_kv_blocks=30, prefix_sharing=True)
        groups = [poisson_requests(
            n_per_group, rate=6.0, vocab_size=cfg.vocab_size,
            prompt_len=plen, max_new_tokens=tail_gen, seed=20 + g,
            shared_prefix_len=prefix_len, rid_base=100 * g)
            for g in range(3)]
        rep = fleet.run(merge_requests(*groups))
        cell = _fleet_cell(rep, routing=routing, replicas=2,
                           prefix_groups=3, shared_prefix_len=prefix_len,
                           prompt_len=plen)
        cells["routing"].append(cell)
        print(f"[bench] fleet-routing {routing:15s} "
              f"ttft_p50={cell['ttft_p50_ms']:7.1f}ms "
              f"p99={cell['ttft_p99_ms']:7.1f}ms "
              f"prefill_chunks={cell['prefill_chunks_total']:3d} "
              f"hit={cell['prefix_hit_rate']} "
              f"affinity={cell['affinity_hit_rate']}")

    # ------------- disaggregation: steady decode + long-prompt burst ---
    plen, gen_steady = 64, 24
    def workload(cfg):
        steady = poisson_requests(
            8, rate=6.0, vocab_size=cfg.vocab_size, prompt_len=16,
            max_new_tokens=gen_steady, seed=30)
        # prefill-only burst: max_new_tokens=1 means the sampled first
        # token finishes each request on whatever engine prefilled it
        burst = [dataclasses.replace(r, arrival_time=0.25)
                 for r in poisson_requests(
                     8, rate=0.0, vocab_size=cfg.vocab_size,
                     prompt_len=plen, max_new_tokens=1, seed=31,
                     rid_base=500)]
        return merge_requests(steady, burst)

    for name, roles in (("unified", ["unified"] * 2),
                        ("disaggregated", ["prefill", "decode"])):
        cfg, fleet = build_fleet(roles, prompt_len=plen, gen=gen_steady,
                                 slots=6)
        rep = fleet.run(workload(cfg))
        cell = _fleet_cell(rep, mode=name, replicas=2,
                           steady_requests=8, burst_requests=8,
                           burst_prompt_len=plen)
        cells["disaggregation"].append(cell)
        print(f"[bench] fleet-disagg {name:14s} "
              f"tpot_p50={cell['tpot_p50_ms']:6.2f}ms "
              f"p99={cell['tpot_p99_ms']:7.2f}ms "
              f"ttft_p50={cell['ttft_p50_ms']:7.1f}ms "
              f"handoffs={cell['handoffs_moved']}")

    by_r = {c["routing"]: c for c in cells["routing"]}
    by_d = {c["mode"]: c for c in cells["disaggregation"]}
    headline = {
        "affinity_ttft_p50_ms": by_r["prefix_affinity"]["ttft_p50_ms"],
        "load_ttft_p50_ms": by_r["load"]["ttft_p50_ms"],
        "affinity_beats_load_ttft":
            by_r["prefix_affinity"]["ttft_p50_ms"]
            < by_r["load"]["ttft_p50_ms"],
        "affinity_prefill_chunks_saved":
            by_r["load"]["prefill_chunks_total"]
            - by_r["prefix_affinity"]["prefill_chunks_total"],
        "disagg_tpot_p99_ms": by_d["disaggregated"]["tpot_p99_ms"],
        "unified_tpot_p99_ms": by_d["unified"]["tpot_p99_ms"],
        "disagg_beats_unified_tpot_p99":
            by_d["disaggregated"]["tpot_p99_ms"]
            < by_d["unified"]["tpot_p99_ms"],
        "no_replica_recompiled": not any(
            any(c["recompiled_after_warmup"])
            for sec in cells.values() for c in sec),
    }
    print(f"[bench] fleet headline: affinity ttft_p50 "
          f"{headline['affinity_ttft_p50_ms']:.1f}ms vs load "
          f"{headline['load_ttft_p50_ms']:.1f}ms "
          f"(beats: {headline['affinity_beats_load_ttft']}, "
          f"chunks saved: {headline['affinity_prefill_chunks_saved']}); "
          f"disagg tpot_p99 {headline['disagg_tpot_p99_ms']:.2f}ms vs "
          f"unified {headline['unified_tpot_p99_ms']:.2f}ms "
          f"(beats: {headline['disagg_beats_unified_tpot_p99']})")
    return {"cells": cells, "headline": headline}


def _state_pool_cell(rep, **labels):
    cell = dict(labels)
    cell.update({
        "n_requests": rep["n_requests"],
        "ttft_p50_ms": rep["ttft"]["p50"] * 1e3,
        "ttft_p99_ms": rep["ttft"]["p99"] * 1e3,
        "tpot_p50_ms": rep["tpot"]["p50"] * 1e3,
        "throughput_tok_s": rep["throughput_tok_s"],
        "mean_occupancy": rep["mean_occupancy"],
        "preemptions": rep["preemptions"],
        "state_pool": rep["state_pool"],
        "recompiled_after_warmup": rep.get("recompiled_after_warmup"),
    })
    return cell


def state_pool_compare():
    """Sequence-state stores under long-context + bursty pressure.

    Four cells through the ``SequenceStateStore`` surface:

    * ``ssm_smooth`` / ``ssm_bursty`` — a pure-SSM (mamba2) engine on the
      slotted recurrent-state pool, the same request mix arriving as a
      smooth Poisson stream vs bursts at the same mean rate: bursts
      oversubscribe the fixed slot pool at one instant, so queueing shows
      up in TTFT p99 while the state pool itself stays fixed-size
      (``state_bytes_per_slot`` is length-independent — the SSM serving
      argument);
    * ``hybrid_long_context`` — a zamba2-style hybrid engine serving a
      long/short prompt mix near the pool ceiling: SSM leaves + attention
      slabs compose in one slot store;
    * ``ring_long_bursty`` — the paged transformer engine with prompts
      far beyond its sliding window, bursty arrivals: window-clamped
      layers serve as fixed-size ring-buffer chains (allocated whole at
      admission, never grown), so long contexts cost O(window) KV, not
      O(length).
    """
    cells = []

    def run(arch, label, reqs_fn, *, slots, prompt_len, gen, chunk,
            paged=False, **labels):
        cfg = get_config(arch).reduced()
        pcfg = ParallelConfig(attn_chunk=min(64, prompt_len))
        if arch == ARCH:
            # the MoE arch runs expert/model-parallel like every other cell
            mesh = make_host_mesh(data=1, model=MODEL_PAR)
            ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
            model = build_model(cfg, pcfg, batch=slots, seq_len=prompt_len,
                                mesh_shape=ms, mesh=mesh)
            with mesh:
                params = model.init(jax.random.PRNGKey(0))
        else:
            mesh = None
            model = build_model(cfg, pcfg, batch=slots, seq_len=prompt_len)
            params = model.init(jax.random.PRNGKey(0))
        ecfg = engine_config_for(cfg, max_slots=slots,
                                 prompt_len=prompt_len,
                                 max_new_tokens=gen, prefill_chunk=chunk,
                                 paged=paged, kv_block_size=KV_BLOCK)
        eng = ServeEngine(model, params, ecfg, mesh=mesh)
        eng.warmup()
        rep = eng.run(reqs_fn(cfg, ecfg))
        cell = _state_pool_cell(rep, arch=arch, workload=label,
                                paged=paged, **labels)
        cells.append(cell)
        sp = cell["state_pool"]
        print(f"[bench] state-pool {label:20s} kind={sp['kind']:5s} "
              f"ttft_p50={cell['ttft_p50_ms']:7.1f}ms "
              f"p99={cell['ttft_p99_ms']:7.1f}ms "
              f"occ={cell['mean_occupancy']:.2f} "
              f"preempt={cell['preemptions']}")
        return cell

    # --- SSM: smooth Poisson vs bursty at the same 8 req/s mean rate ---
    n, plen, gen = 12, 64, 8
    run("mamba2-2.7b", "ssm_smooth",
        lambda cfg, ecfg: poisson_requests(
            n, rate=8.0, vocab_size=cfg.vocab_size, prompt_len=plen,
            max_new_tokens=gen, seed=40),
        slots=3, prompt_len=plen, gen=gen, chunk=16, arrivals="poisson")
    run("mamba2-2.7b", "ssm_bursty",
        lambda cfg, ecfg: bursty_requests(
            n, vocab_size=cfg.vocab_size, prompt_len=plen,
            max_new_tokens=gen, burst_size=6, burst_gap=0.75, seed=40),
        slots=3, prompt_len=plen, gen=gen, chunk=16, arrivals="bursty")

    # --- hybrid: long/short prompt mix near the pool ceiling ---
    run("zamba2-7b", "hybrid_long_context",
        lambda cfg, ecfg: long_context_requests(
            8, vocab_size=cfg.vocab_size, max_seq_len=ecfg.max_seq_len,
            max_new_tokens=gen, rate=8.0, long_frac=0.5, short_len=16,
            seed=41),
        slots=3, prompt_len=96, gen=gen, chunk=16, arrivals="poisson")

    # --- paged ring: prompts ~2x beyond the 64-token sliding window ---
    ring_cell = run(ARCH, "ring_long_bursty",
                    lambda cfg, ecfg: bursty_requests(
                        8, vocab_size=cfg.vocab_size, prompt_len=120,
                        max_new_tokens=gen, burst_size=4, burst_gap=0.75,
                        seed=42,
                        prompt_len_range=(72, 120)),
                    slots=3, prompt_len=120, gen=gen, chunk=16,
                    paged=True, arrivals="bursty")

    by_label = {c["workload"]: c for c in cells}
    headline = {
        "ssm_smooth_ttft_p99_ms": by_label["ssm_smooth"]["ttft_p99_ms"],
        "ssm_bursty_ttft_p99_ms": by_label["ssm_bursty"]["ttft_p99_ms"],
        "bursty_pressure_visible":
            by_label["ssm_bursty"]["ttft_p99_ms"]
            > by_label["ssm_smooth"]["ttft_p99_ms"],
        "ssm_state_bytes_per_slot":
            by_label["ssm_smooth"]["state_pool"]["state_bytes_per_slot"],
        "ring_engaged": bool(ring_cell["state_pool"].get("window_ring")),
        "ring_tokens": ring_cell["state_pool"].get("ring_tokens"),
        "no_cell_recompiled": not any(c["recompiled_after_warmup"]
                                      for c in cells),
    }
    print(f"[bench] state-pool headline: bursty ttft_p99 "
          f"{headline['ssm_bursty_ttft_p99_ms']:.1f}ms vs smooth "
          f"{headline['ssm_smooth_ttft_p99_ms']:.1f}ms "
          f"(pressure: {headline['bursty_pressure_visible']}); "
          f"ring engaged: {headline['ring_engaged']} "
          f"(M={headline['ring_tokens']}); "
          f"recompiles: {not headline['no_cell_recompiled']}")
    return {"cells": cells, "headline": headline}


ONLY_SECTIONS = {"fleet": ("fleet", lambda: fleet_compare()),
                 "state_pool": ("state_pool",
                                lambda: state_pool_compare())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    ap.add_argument("--only", default="",
                    choices=["", *ONLY_SECTIONS],
                    help="run a single section and merge it into an "
                         "existing --out file (fresh runs leave this "
                         "empty and produce the full file)")
    args = ap.parse_args()
    print(f"[bench] compile cache {enable_compile_cache()}")

    if args.only:
        key, fn = ONLY_SECTIONS[args.only]
        section = fn()
        out = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                out = json.load(f)
        out[key] = section
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"[bench] merged {key} section -> "
              f"{os.path.abspath(args.out)}")
        return

    results = sweep()
    capacity, gains, more = capacity_compare()
    prefix_cells, reductions, faster = prefix_compare()
    spec_cells, spec_spt, spec_wins, spec_tokens_equal = \
        speculative_compare()
    skew = skew_compare()
    residency = residency_compare()
    fleet = fleet_compare()
    state_pool = state_pool_compare()
    decode_attn = decode_attention_microbench()
    phases = phases_breakdown()

    out = {
        "meta": {
            "bench": "serve_load", "arch": ARCH, "reduced": True,
            "devices": len(jax.devices()), "model_par": MODEL_PAR,
            "slots": SLOTS, "n_requests": N_REQ,
            "prompt_len": PROMPT_LEN, "gen": GEN,
            "prefill_chunk": PREFILL_CHUNK,
            "kv_block_size": KV_BLOCK,
            "pool": "paged",
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "jax": jax.__version__,
        },
        "results": results,
        "capacity": {
            "cells": capacity,
            "concurrency_gain": gains,
            "paged_more_concurrent": more,
        },
        "prefix": {
            "cells": prefix_cells,
            "ttft_p50_reduction_ms": reductions,
            "sharing_faster": faster,
        },
        "speculative": {
            "cells": spec_cells,
            "steps_per_committed_token": spec_spt,
            "speculation_wins": spec_wins,
            "token_counts_equal_across_k": spec_tokens_equal,
        },
        "skew": skew,
        "residency": residency,
        "fleet": fleet,
        "state_pool": state_pool,
        "decode_attention": decode_attn,
        "phases": phases,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[bench] wrote {os.path.abspath(args.out)} "
          f"({len(results)} sweep + {len(capacity)} capacity + "
          f"{len(prefix_cells)} prefix + {len(spec_cells)} speculative + "
          f"{len(skew['engine_cells'])}+{len(skew['modeled_cells'])} skew + "
          f"{len(residency['engine_cells'])}+"
          f"{len(residency['modeled_cells'])} residency + "
          f"{len(fleet['cells']['routing'])}+"
          f"{len(fleet['cells']['disaggregation'])} fleet + "
          f"{len(state_pool['cells'])} state-pool + "
          f"{len(decode_attn['cells'])} decode-attention + "
          f"{len(phases['cells'])} phase-breakdown cells)")


if __name__ == "__main__":
    main()
