"""Serving CLI: a thin driver over the ``repro.serve`` continuous-batching
engine (HarMoEny load balancing under request streams).

Example (CPU, small MoE, heavy synthetic skew):
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
      --batch 4 --prompt-len 64 --gen 16 --skew 0.9 --model-par 4

The old one-shot semantics (one closed batch of ``--batch`` prompts,
lockstep greedy decode) are the default: ``--requests N --rate R`` opens the
loop with N Poisson arrivals at R req/s, admitted into freed decode slots as
earlier requests finish. ``--paged`` swaps the slab KV pool for the paged
block-table pool (block-aware admission, preemption-by-recompute);
``--paged --prefix-sharing`` additionally serves repeated prompt prefixes
out of a copy-on-write block cache (``--shared-prefix-len`` makes the
synthetic prompts actually share one); ``--paged --fused-attention`` swaps
the reference block-table gather for the fused Pallas decode-attention
kernel; ``--temperature``/``--top-k``/``--top-p`` switch greedy decode to
truncated sampling.
Reports per-request TTFT/TPOT percentiles, decode tokens/s, and the
HarMoEny schedule diagnostics (moved units, drops, load balance) — the
paper's §5 metrics.

``--replicas N`` scales out to a fleet of N engine replicas behind a
``FleetRouter`` (virtual replicas: one set of weights on one device
group, one engine + KV pool each, one shared clock) with
``--routing-policy`` load / prefix_affinity / round_robin;
``--disaggregate`` splits the fleet into prefill-role and decode-role
engines connected by the KV handoff path. Fleet runs report aggregate
and per-replica metrics plus routing / handoff diagnostics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.core.topology import static_opt_placement
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.shardings import param_shardings
from repro.models.model import MeshShape, build_model
from repro.serve import (EngineConfig, FleetRouter, ROUTING_POLICIES,
                         ServeEngine, WallClock, engine_config_for,
                         load_trace, poisson_requests)

# ----------------------------------------------------------------------
# EngineConfig-derived flag plumbing.  Every engine knob used to be wired
# three times — argparse declaration, args attribute, engine_config_for
# kwarg — and each new knob repeated the dance.  Now one table row names
# the flag and the ``EngineConfig`` field it sets: the argparse type and
# default come from the dataclass field itself (``add_engine_flags``),
# and the kwargs for ``engine_config_for`` are extracted generically
# (``engine_overrides``).  A ``default`` override in the row marks
# CLI-level "0 = auto" semantics that ``engine_config_for`` resolves
# before ``EngineConfig`` validation sees them.
# ----------------------------------------------------------------------
ENGINE_FLAGS = [
    ("--prefill-chunk", "prefill_chunk",
     dict(default=0, help="prompt tokens per prefill chunk (0 = auto)")),
    ("--paged", "paged",
     dict(help="paged KV pool: block-table attention, block-aware "
               "admission, preemption-by-recompute")),
    ("--kv-block-size", "kv_block_size",
     dict(help="tokens per physical KV block (paged mode)")),
    ("--kv-blocks", "num_kv_blocks",
     dict(help="usable KV blocks (0 = worst case: slab parity)")),
    ("--prefix-sharing", "prefix_sharing",
     dict(help="prefix-sharing KV cache: copy-on-write blocks, radix "
               "prefix index, LRU eviction (needs --paged)")),
    ("--fused-attention", "fused_paged_attention",
     dict(help="fused Pallas attention on every phase: q-tiled paged "
               "attention for prefill / prefix-tail / verify and "
               "block-table decode attention (needs --paged for decode; "
               "interpret mode off-TPU). Strict: raises instead of "
               "silently falling back")),
    ("--fused-moe", "fused_moe_gmm",
     dict(help="grouped-GEMM Pallas expert FFN on prefill/decode/verify "
               "token batches (MoE archs only; interpret mode off-TPU)")),
    ("--speculative-k", "speculative_k",
     dict(help="speculative decoding: verify up to k self-drafted tokens "
               "per decode step in one static [B, k+1] forward (needs "
               "--paged; greedy streams stay token-identical)")),
    ("--speculative-policy", "speculative_policy",
     dict(help="draft proposer (ngram = prompt-lookup self-drafting)")),
    ("--temperature", "temperature",
     dict(help="sampling temperature (0 = greedy)")),
    ("--top-k", "top_k",
     dict(help="truncate sampling to the top-k logits (0 = full)")),
    ("--top-p", "top_p",
     dict(help="nucleus sampling: keep the smallest token set with "
               "cumulative probability >= top-p (1 = off)")),
    ("--replica-slots", "replica_slots",
     dict(help="static hot-expert replica slots per rank (0 = "
               "replication off); swaps never recompile")),
    ("--rebalance-interval", "rebalance_interval",
     dict(help="engine steps between hot-expert weight swaps (0 = "
               "never; needs --replica-slots)")),
    ("--resident-experts", "resident_experts",
     dict(help="tiered expert residency: pod-total HBM working-set "
               "budget in experts (0 = off; must be a multiple of the "
               "EP degree)")),
    ("--prefetch-policy", "prefetch_policy",
     dict(choices=["predictive", "on_demand", "none"],
          help="residency staging policy: predictive = EMA-driven "
               "next-layer prefetch (stalls hidden), on_demand = stage "
               "on first touch, none = frozen initial working set")),
]


def add_engine_flags(ap: argparse.ArgumentParser) -> None:
    """Declare one CLI flag per ``ENGINE_FLAGS`` row, typed and defaulted
    from the ``EngineConfig`` field it maps to (bool fields become
    ``store_true`` switches).  ``dest`` is the field name, so the parsed
    namespace mirrors the config and ``engine_overrides`` needs no
    per-flag mapping."""
    fields = {f.name: f for f in dataclasses.fields(EngineConfig)}
    for flag, name, extra in ENGINE_FLAGS:
        extra = dict(extra)
        default = extra.pop("default", fields[name].default)
        if isinstance(default, bool):
            ap.add_argument(flag, dest=name, action="store_true", **extra)
        else:
            ap.add_argument(flag, dest=name, type=type(default),
                            default=default, **extra)


def engine_overrides(args) -> dict:
    """The parsed values of every ``ENGINE_FLAGS`` knob, keyed by
    ``EngineConfig`` field name — splat into ``engine_config_for``."""
    return {name: getattr(args, name) for _, name, _ in ENGINE_FLAGS}


def skew_profile(moe, skew: float) -> np.ndarray:
    """Offline per-expert load profile under the synthetic skew router
    (core/router.py route_skewed): the first ``router_skew_experts``
    experts share ``skew`` of the mass, the rest split the remainder.
    Feeds ``static_opt_placement`` — the paper's profile-then-place
    baseline, which a live stream whose skew drifts then defeats."""
    E, H = moe.num_experts, moe.router_skew_experts
    p = np.full((E,), (1.0 - skew) / max(E - H, 1))
    p[:H] = skew / max(H, 1)
    return (p * 10_000).astype(np.int64)


def config_from_args(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if getattr(args, "sliding_window", -1) >= 0:
        # long-context cells: reduced() clamps archs like mixtral to a
        # 64-token window, which rejects paged pools longer than the
        # window; 0 disables the window so >=2k prompts can run paged
        cfg = cfg.replace(sliding_window=args.sliding_window)
    if cfg.moe is None:
        return cfg
    moe = dataclasses.replace(cfg.moe, policy=args.policy)
    if args.skew > 0:
        moe = dataclasses.replace(moe, router_skew=args.skew)
    if args.replica_slots > 0:
        moe = dataclasses.replace(moe, num_replica_slots=args.replica_slots)
    if args.q_tokens > 0:
        moe = dataclasses.replace(moe, q_tokens=args.q_tokens)
    if args.policy == "static_opt" and moe.num_experts >= args.model_par:
        # profile-then-place: bin-pack the offline skew profile once
        placement = static_opt_placement(
            skew_profile(moe, moe.router_skew), args.model_par)
        moe = dataclasses.replace(moe, placement=tuple(int(e)
                                                       for e in placement))
    return cfg.replace(moe=moe)


def _mesh_and_model(args, cfg, prompt_len):
    pcfg = ParallelConfig(attn_chunk=min(512, prompt_len))
    if args.data_par > 1:
        raise NotImplementedError(
            "the serving engine shards the model/expert axis only; "
            "--data-par must be 1 (data-parallel serving is an open item)")
    mesh = make_host_mesh(data=1, model=args.model_par)
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(cfg, pcfg, batch=args.batch, seq_len=prompt_len,
                        mesh_shape=ms, mesh=mesh)
    # initialise every leaf in place on its own shard: a model that does
    # not fit one device never lands whole on the first
    key = jax.random.PRNGKey(0)
    shardings = param_shardings(jax.eval_shape(model.init, key), cfg, pcfg,
                                mesh)
    params = jax.jit(model.init, out_shardings=shardings)(key)
    return mesh, model, params


def _engine_cfg(args, cfg, prompt_len, gen, role="unified"):
    return engine_config_for(
        cfg, max_slots=args.batch, prompt_len=prompt_len,
        max_new_tokens=gen, skew_seed=args.seed + 1,
        moe_policy=args.moe_policy or None, role=role,
        **engine_overrides(args))


def build_serving_engine(args, cfg=None, *, prompt_len=None, gen=None):
    """Config + model + engine from CLI args (shared with examples).

    ``prompt_len``/``gen`` override the CLI shapes (trace-driven runs size
    the engine from the trace, not the defaults)."""
    cfg = cfg if cfg is not None else config_from_args(args)
    prompt_len = prompt_len or args.prompt_len
    gen = gen or args.gen
    mesh, model, params = _mesh_and_model(args, cfg, prompt_len)
    ecfg = _engine_cfg(args, cfg, prompt_len, gen)
    engine = ServeEngine(model, params, ecfg, mesh=mesh)
    return cfg, engine


def build_fleet(args, cfg=None, *, prompt_len=None, gen=None):
    """N virtual replicas behind a ``FleetRouter``: one set of weights on
    one device group, one engine (and KV pool) each, one shared wall
    clock. ``--disaggregate`` makes the first ``replicas // 2`` engines
    prefill-role and the rest decode-role (requires ``--paged``)."""
    cfg = cfg if cfg is not None else config_from_args(args)
    prompt_len = prompt_len or args.prompt_len
    gen = gen or args.gen
    mesh, model, params = _mesh_and_model(args, cfg, prompt_len)
    if args.disaggregate:
        if args.replicas < 2:
            raise ValueError("--disaggregate needs --replicas >= 2 "
                             "(at least one prefill + one decode engine)")
        if not args.paged:
            raise ValueError("--disaggregate hands KV off through the "
                             "paged block machinery; add --paged")
        n_pf = max(1, args.replicas // 2)
        roles = ["prefill"] * n_pf + ["decode"] * (args.replicas - n_pf)
    else:
        roles = ["unified"] * args.replicas
    clock = WallClock()
    engines = [ServeEngine(model, params,
                           _engine_cfg(args, cfg, prompt_len, gen, role),
                           mesh=mesh, clock=clock)
               for role in roles]
    fleet = FleetRouter(engines, policy=args.routing_policy,
                        affinity_weight=args.affinity_weight)
    return cfg, fleet


def serve(args):
    cfg = config_from_args(args)
    if args.trace:
        requests = load_trace(args.trace, vocab_size=cfg.vocab_size)
        prompt_len = max(r.prompt_len for r in requests)
        gen = max(r.max_new_tokens for r in requests)
    else:
        n = args.requests or args.batch
        requests = poisson_requests(
            n, rate=args.rate, vocab_size=cfg.vocab_size,
            prompt_len=args.prompt_len, max_new_tokens=args.gen,
            seed=args.seed, shared_prefix_len=args.shared_prefix_len)
        prompt_len, gen = args.prompt_len, args.gen
    if args.replicas > 1 or args.disaggregate:
        return serve_fleet(args, cfg, requests, prompt_len, gen)
    cfg, engine = build_serving_engine(args, cfg, prompt_len=prompt_len,
                                       gen=gen)
    engine.warmup()                      # compile outside the TTFT window
    rep = engine.run(requests)

    ttft, tpot = rep["ttft"], rep["tpot"]
    print(f"[serve] arch={args.arch} policy={args.policy} skew={args.skew} "
          f"slots={args.batch} requests={rep['n_requests']} rate={args.rate}")
    print(f"[serve] TTFT p50 {ttft['p50'] * 1e3:.1f} ms  "
          f"p99 {ttft['p99'] * 1e3:.1f} ms   "
          f"TPOT p50 {tpot['p50'] * 1e3:.2f} ms   "
          f"decode {rep['throughput_tok_s']:.1f} tok/s "
          f"(occupancy {rep['mean_occupancy']:.2f}/{args.batch})")
    moe = rep.get("moe", {})
    if any(k.endswith("moved_units") for k in moe):
        for phase in ("prefill", "decode"):
            if f"{phase}/moved_units" not in moe:
                continue
            drops = moe.get(f"{phase}/send_drops", 0.0) \
                + moe.get(f"{phase}/dest_drops", 0.0)
            print(f"[serve] {phase} schedule: "
                  f"moved={moe[f'{phase}/moved_units']:.0f} "
                  f"drops={drops:.0f} "
                  f"max_load {moe.get(f'{phase}/max_load_before', 0):.0f}"
                  f"->{moe.get(f'{phase}/max_load_after', 0):.0f}")
    lb = rep.get("load_balance", {})
    for phase, sec in lb.items():
        if "max_mean_ratio" not in sec:
            continue
        print(f"[serve] {phase} load: max/mean ratio "
              f"{sec['max_mean_ratio']:.2f}  "
              f"straggler_wait {sec['straggler_wait_units']:.1f} units  "
              f"drops {sec.get('send_drops_total', 0):.0f}/"
              f"{sec.get('dest_drops_total', 0):.0f}")
    eng_rep = rep["engine"]
    if args.replica_slots:
        print(f"[serve] replication: slots={eng_rep['replica_slots']} "
              f"interval={eng_rep.get('rebalance_interval', 0)} "
              f"swaps={eng_rep.get('replica_swaps', 0)} "
              f"hot={eng_rep.get('hot_experts', [])}")
    if args.paged:
        util = rep.get("kv_utilization")
        print(f"[serve] paged KV: blocks={rep['engine']['num_kv_blocks']} "
              f"x{rep['engine']['kv_block_size']} tokens  "
              f"utilization={util if util is None else f'{util:.2f}'}  "
              f"preemptions={rep['preemptions']}  "
              f"max_concurrency={rep['max_occupancy']}  "
              f"fused_attention={rep['engine']['fused_paged_attention']}")
    if args.prefix_sharing:
        hit = rep.get("prefix_hit_rate")
        print(f"[serve] prefix cache: "
              f"hit_rate={hit if hit is None else f'{hit:.2f}'}  "
              f"cow_copies={rep['cow_copies']}  "
              f"evictions={rep['evictions']}  "
              f"resume_cached_tokens={rep['resume_cached_tokens']}")
    if getattr(args, "resident_experts", 0) and "residency" in rep:
        res = rep["residency"]
        hr = res.get("hit_rate")
        print(f"[serve] residency: budget={eng_rep['resident_experts']} "
              f"policy={eng_rep.get('prefetch_policy')}  "
              f"hit_rate={hr if hr is None else f'{hr:.2f}'}  "
              f"swaps={res['swaps']} prefetches={res['prefetches']}  "
              f"stall={res['stall_units']:.4f}s  "
              f"staged={res['bytes_staged'] / 1e6:.1f} MB")
    if args.speculative_k and "speculative" in rep:
        sp = rep["speculative"]
        acc = sp["acceptance_rate"]
        print(f"[serve] speculative k={args.speculative_k} "
              f"policy={args.speculative_policy}: "
              f"acceptance={acc if acc is None else f'{acc:.2f}'}  "
              f"tokens/step={sp['tokens_per_step']:.2f}  "
              f"steps/token={sp['steps_per_committed_token']:.2f}")
    print(f"[serve] jit entries {rep['jit_entries']} "
          f"recompiled_after_warmup={rep.get('recompiled_after_warmup')}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2)
        print(f"[serve] report -> {args.out}")
    return rep


def serve_fleet(args, cfg, requests, prompt_len, gen):
    cfg, fleet = build_fleet(args, cfg, prompt_len=prompt_len, gen=gen)
    fleet.warmup()                       # compile outside the TTFT window
    rep = fleet.run(requests)
    fl = rep["fleet"]
    agg, routing, hand = fl["aggregate"], fl["routing"], fl["handoffs"]
    ttft, tpot = agg["ttft"], agg["tpot"]
    print(f"[fleet] arch={args.arch} replicas={fl['n_replicas']} "
          f"policy={routing['policy']} "
          f"disaggregated={fl['disaggregated']} "
          f"requests={agg['n_requests']} rate={args.rate}")
    print(f"[fleet] TTFT p50 {ttft['p50'] * 1e3:.1f} ms  "
          f"p99 {ttft['p99'] * 1e3:.1f} ms   "
          f"TPOT p50 {tpot['p50'] * 1e3:.2f} ms   "
          f"decode {agg['throughput_tok_s']:.1f} tok/s   "
          f"goodput {agg['goodput_req_s']:.2f} req/s")
    hit = routing["affinity_hit_rate"]
    print(f"[fleet] routing: per_replica={routing['per_replica']}  "
          f"affinity_hits={routing['affinity_hits']} "
          f"(rate={hit if hit is None else f'{hit:.2f}'}, "
          f"{routing['affinity_hit_tokens']} cached tokens)")
    if fl["disaggregated"]:
        print(f"[fleet] handoffs: moved={hand['moved']} "
              f"bytes={hand['bytes'] / 2 ** 20:.2f} MiB "
              f"pending={hand['pending']}")
    for r in fl["replicas"]:
        rt = r["ttft"]["p50"]
        print(f"[fleet]   replica {r['index']} role={r['role']:8s} "
              f"routed={r['routed']:3d} finished={r['n_requests']:3d} "
              f"steps={r['steps']:4d} "
              f"ttft_p50={'-' if rt is None else f'{rt * 1e3:.1f}ms'}")
    recompiled = [bool(rr.get("recompiled_after_warmup"))
                  for rr in rep["replica_reports"]]
    print(f"[fleet] recompiled_after_warmup={recompiled}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2)
        print(f"[fleet] report -> {args.out}")
    return rep


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's flags (``chip_smoke.py`` drives the same
    parser, so its runs are CLI runs)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (concurrent requests)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--policy", default="harmoeny",
                    choices=["harmoeny", "round_robin", "even_split",
                             "static_opt"])
    ap.add_argument("--moe-policy", default="",
                    choices=["", "harmoeny", "round_robin", "even_split",
                             "static_opt"],
                    help="decode-time scheduling policy override (default: "
                         "--policy everywhere); lets one set of weights "
                         "serve prefill and decode under different policies")
    ap.add_argument("--q-tokens", type=int, default=0,
                    help="scheduler token-unit granularity override (0 = "
                         "auto threshold; small values let tiny decode "
                         "batches redistribute)")
    ap.add_argument("--data-par", type=int, default=0)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # --- serving-engine knobs ---
    # every EngineConfig knob comes from the ENGINE_FLAGS table (one
    # declaration per knob, typed/defaulted from the dataclass field)
    add_engine_flags(ap)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: one closed batch)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate req/s (0 = all at t=0)")
    ap.add_argument("--sliding-window", type=int, default=-1,
                    help="override the arch's sliding window (-1 = keep; "
                         "0 = full attention — needed for long-context "
                         "paged cells on reduced window archs)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="synthetic prompts share their first K tokens "
                         "(the system-prompt regime prefix caching targets)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the fleet router (>1 "
                         "enables fleet mode; virtual replicas share one "
                         "set of weights on one device group)")
    ap.add_argument("--routing-policy", default="load",
                    choices=list(ROUTING_POLICIES),
                    help="fleet routing: load = least queued+KV tokens, "
                         "prefix_affinity = load minus cached-prefix "
                         "match (needs --prefix-sharing to matter), "
                         "round_robin = baseline")
    ap.add_argument("--affinity-weight", type=float, default=1.0,
                    help="tokens of load one cached prefix token offsets "
                         "under prefix_affinity routing")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split the fleet into prefill-role and decode-"
                         "role engines linked by KV handoff (needs "
                         "--paged and --replicas >= 2)")
    ap.add_argument("--trace", default="",
                    help="JSON trace file of arrival records")
    ap.add_argument("--out", default="", help="write the report JSON here")
    return ap


def main():
    args = build_parser().parse_args()
    print(f"[serve] compile cache {enable_compile_cache()}")
    serve(args)


if __name__ == "__main__":
    main()
