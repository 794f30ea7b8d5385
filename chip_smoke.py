#!/usr/bin/env python3
"""Bring-up smoke of the serving path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the expert-parallel phase, four chips

One chip: Qwen1.5-MoE (``qwen15-moe-a27b``) at every published width in
bf16, with the depth cut to what one v5e holds (``ONE_CHIP_DEPTH``; depth
is the only cut) and random weights from a seed.  It runs the paged
attention and grouped-GEMM kernels at the run's shapes against their
``ref.py`` oracles, then serves 8 requests of 512-token prompts for 32
new tokens each through the serving CLI's own entry points
(``config_from_args`` -> ``build_serving_engine`` -> ``warmup`` / ``run``)
with the paged pool, fused paged attention and fused ``moe_gmm`` in strict
mode, under the HarMoEny schedule at router skew 0.9.

Four chips: the full 24-layer model, experts and heads split over
``--model-par 4``, parameters initialised in place on their shards.  The
same skew-0.9 stream is served with HarMoEny's decode schedule and with
round-robin (the paper's baseline), both at ``--q-tokens 1``.

Every check raises, so any failed phase makes the exit code non-zero.
The last line of standard output is the JSON result, printed only when
every check held.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``;
run twice to see the compile seconds drop.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "qwen15-moe-a27b"
# Layers one 16 GB v5e holds at full width: ~1.14 GB of bf16 weights per
# layer plus ~1.25 GB of embedding and untied head; at 6 layers the
# compiled decode step needs ~10.2 GB (8.4 GB arguments, 1.5 GB
# temporaries), leaving room for the KV pool, the scratch and the
# kernel-parity buffers.
ONE_CHIP_DEPTH = 6
N_REQUESTS, PROMPT_LEN, GEN = 8, 512, 32
PREFILL_CHUNK, KV_BLOCK = 256, 16
SKEW = 0.9
SEED = 0
# bf16 parity tolerance, as a share of the reference's largest magnitude:
# bf16 carries 8 bits of mantissa (2^-8 ~ 0.4% per rounding) and both
# kernels round their f32 accumulators and intermediates to bf16 once
# or twice; a wrong block, head or expert is off by O(100%)
PARITY_TOL = 2e-2
# after initialisation every chip holds its share within this margin
BALANCE_TOL = 0.25


def require_devices(n: int):
    """The TPU devices, or exit non-zero naming what JAX found."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found; JAX's default backend is "
                 f"{devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"chip_smoke: this phase needs {n} TPU chips; found "
                 f"{len(devices)}")
    return devices


def serve_args(model_par: int, *extra: str):
    from repro.launch.serve import build_parser
    return build_parser().parse_args([
        "--arch", ARCH, "--batch", str(N_REQUESTS),
        "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN),
        "--requests", str(N_REQUESTS), "--skew", str(SKEW),
        "--policy", "harmoeny", "--model-par", str(model_par),
        "--paged", "--kv-block-size", str(KV_BLOCK),
        "--prefill-chunk", str(PREFILL_CHUNK),
        "--fused-attention", "--fused-moe", "--seed", str(SEED), *extra])


def stream(cfg):
    from repro.serve import poisson_requests
    return poisson_requests(N_REQUESTS, rate=0.0, vocab_size=cfg.vocab_size,
                            prompt_len=PROMPT_LEN, max_new_tokens=GEN,
                            seed=SEED)


def serve(engine, requests, tag: str):
    """Warm up (compile), serve ``requests``, check the stream; returns
    (report, {rid: generated tokens})."""
    outputs = {}
    finish = engine._finish

    def capture(st, now):
        outputs[st.req.rid] = list(st.output)
        finish(st, now)
    engine._finish = capture
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    rep = engine.run(requests)
    vocab = engine.cfg.vocab_size
    print(f"[{tag}] compile (warmup) {compile_s:.1f} s; TTFT p50 "
          f"{rep['ttft']['p50'] * 1e3:.1f} ms, TPOT p50 "
          f"{rep['tpot']['p50'] * 1e3:.2f} ms")
    for phase, sec in rep["phases"].items():
        # host clock from dispatch to the synced result: device time plus
        # dispatch, without the engine's host bookkeeping around it
        print(f"[{tag}] {phase}: {sec['steps']} jitted steps, "
              f"{sec['seconds'] / sec['steps'] * 1e3:.2f} ms each from "
              f"dispatch to synced result")
    print(f"[{tag}] attention dispatch {json.dumps(rep['attention_dispatch'])}")
    if sorted(outputs) != sorted(r.rid for r in requests):
        raise RuntimeError(f"{tag}: finished {sorted(outputs)} of "
                           f"{len(requests)} requests")
    for rid, toks in outputs.items():
        if len(toks) != GEN or not all(0 <= t < vocab for t in toks):
            raise RuntimeError(f"{tag}: request {rid} returned {toks}")
    checks = {
        "attention_fallbacks": rep["attention_fallbacks"] == {},
        "recompiled_after_warmup": rep["recompiled_after_warmup"] is False,
        "pallas_interpret": rep["engine"]["pallas_interpret"] is False,
    }
    if not all(checks.values()):
        raise RuntimeError(f"{tag}: failed {checks}: fallbacks "
                           f"{rep['attention_fallbacks']}, jit "
                           f"{rep['jit_entries']}")
    return rep, outputs


def _max_rel_err(got, want) -> float:
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def kernel_parity(engine) -> None:
    """Run the paged-attention kernel at the engine's decode and prefill
    shapes, and ``moe_gmm`` at its expert shapes, against ``ref.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.moe_gmm.moe_gmm import pick_block_f
    from repro.kernels.moe_gmm.ops import fused_expert_ffn, tile_group_map
    from repro.kernels.moe_gmm.ref import moe_gmm_ref
    from repro.kernels.paged_attention.ops import (largest_block_divisor,
                                                   paged_attention)
    from repro.kernels.paged_attention.ref import paged_attention_ref

    cfg, kv = engine.cfg, engine.kv
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(SEED)
    key = jax.random.PRNGKey(SEED)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.bfloat16)

    B, bps, bs = engine.ecfg.max_slots, kv.blocks_per_slot, KV_BLOCK
    n_pool = kv.alloc.num_blocks
    # decode: every row walks its own chain of physical blocks (block 0 is
    # the null block) up to a ragged length; prefill: a chunk deep in the
    # slab scratch viewed as contiguous 128-token blocks
    chains = rng.permutation(np.arange(1, n_pool))[:B * bps].reshape(B, bps)
    lens = rng.integers(1, bps * bs + 1, B)
    s_pad = kv.s_pad
    bs_slab = largest_block_divisor(s_pad)
    cases = {
        "decode": (normal(1, (B, 1, H, hd)), normal(2, (1, n_pool * bs, Hkv, hd)),
                   normal(3, (1, n_pool * bs, Hkv, hd)), chains, lens, bs),
        "prefill": (normal(4, (1, PREFILL_CHUNK, H, hd)),
                    normal(5, (1, s_pad, Hkv, hd)),
                    normal(6, (1, s_pad, Hkv, hd)),
                    np.arange(s_pad // bs_slab)[None],
                    np.array([PROMPT_LEN]), bs_slab),
    }
    interpret = engine.model.pallas_interpret
    for name, (q, k, v, bt, cl, bsz) in cases.items():
        fused = jax.jit(lambda *a, bsz=bsz: paged_attention(
            *a, block_size=bsz, interpret=interpret))(q, k, v, bt, cl)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a, bsz=bsz: paged_attention_ref(
                *a, block_size=bsz))(q, k, v, bt, cl)
        err = _max_rel_err(fused, ref)
        print(f"[parity] paged_attention {name} q{tuple(q.shape)} pool "
              f"{tuple(k.shape)} block {bsz}: max rel err {err:.2e} "
              f"(tol {PARITY_TOL:.0e})")
        if not err <= PARITY_TOL:
            raise RuntimeError(f"paged_attention {name} parity {err:.2e}")

    moe = cfg.moe
    G, d, f, bm = moe.num_experts, cfg.d_model, moe.d_ff_expert, 128
    sizes = jnp.asarray(rng.integers(0, 3, G) * bm, jnp.int32)
    M = G * 2 * bm
    x = normal(7, (M, d))
    w_in = normal(8, (G, d, f)) * (2.0 / d) ** 0.5
    w_gate = normal(9, (G, d, f)) * (2.0 / d) ** 0.5
    w_out = normal(10, (G, f, d)) * (2.0 / f) ** 0.5
    block_f = pick_block_f(d, f, block_m=bm, itemsize=2, gated=True)
    fused = jax.jit(lambda *a: fused_expert_ffn(
        *a[:4], w_gate=a[4], act="silu", block_m=bm, block_f=block_f,
        interpret=interpret))(x, w_in, w_out, sizes, w_gate)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: moe_gmm_ref(
            *a[:3], tile_group_map(a[3], M // bm, bm), w_gate=a[4],
            act="silu", block_m=bm))(x, w_in, w_out, sizes, w_gate)
    err = _max_rel_err(fused, ref)
    print(f"[parity] moe_gmm G={G} d={d} f={f} M={M} block_f={block_f}: "
          f"max rel err {err:.2e} (tol {PARITY_TOL:.0e})")
    if not err <= PARITY_TOL:
        raise RuntimeError(f"moe_gmm parity {err:.2e}")


def one_chip(devices) -> None:
    from repro.launch.serve import build_serving_engine, config_from_args
    args = serve_args(1)
    cfg = config_from_args(args)
    print(f"[one-chip] {ARCH}: {ONE_CHIP_DEPTH} of {cfg.num_layers} layers; "
          f"depth is the only cut (d_model {cfg.d_model}, {cfg.num_heads} "
          f"heads x {cfg.resolved_head_dim}, {cfg.moe.num_experts} experts x "
          f"d_ff {cfg.moe.d_ff_expert}, top-{cfg.moe.num_experts_per_tok}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype})")
    cfg = cfg.replace(num_layers=ONE_CHIP_DEPTH)
    _, engine = build_serving_engine(args, cfg)
    kernel_parity(engine)
    serve(engine, stream(cfg), "one-chip")
    peak = devices[0].memory_stats()["peak_bytes_in_use"]
    print(f"[one-chip] peak_bytes_in_use {peak} ({peak / 2 ** 30:.2f} GiB)")


def four_chips(devices) -> None:
    import dataclasses
    import gc

    import numpy as np

    from repro.launch.serve import build_serving_engine
    from repro.serve import ServeEngine
    args = serve_args(4, "--q-tokens", "1")
    cfg, engine = build_serving_engine(args)
    used = np.array([d.memory_stats()["bytes_in_use"] for d in devices[:4]])
    spread = float(np.max(np.abs(used - used.mean())) / used.mean())
    print(f"[four-chips] {ARCH}: all {cfg.num_layers} layers, model-par 4; "
          f"bytes_in_use after init {used.tolist()} (max deviation from "
          f"the mean {spread:.1%})")
    if not spread <= BALANCE_TOL:
        raise RuntimeError(f"per-chip bytes after init deviate {spread:.1%}")
    requests = stream(cfg)
    reports, outputs = {}, {}
    reports["harmoeny"], outputs["harmoeny"] = serve(
        engine, requests, "four-chips harmoeny")
    model, params, mesh, ecfg = (engine.model, engine.params, engine.mesh,
                                 engine.ecfg)
    del engine                                  # free its KV pool
    gc.collect()
    rr = ServeEngine(model, params,
                     dataclasses.replace(ecfg, moe_policy="round_robin"),
                     mesh=mesh)
    reports["round_robin"], outputs["round_robin"] = serve(
        rr, stream(cfg), "four-chips round_robin")
    ratios = {}
    for policy, rep in reports.items():
        for phase, sec in rep["load_balance"].items():
            drops = (sec.get("send_drops_total", 0),
                     sec.get("dest_drops_total", 0))
            print(f"[four-chips] {policy} {phase}: max/mean rank load "
                  f"{sec['max_mean_ratio']:.3f}, drops {drops[0]:.0f}/"
                  f"{drops[1]:.0f}")
            if drops != (0, 0):
                raise RuntimeError(f"{policy} {phase} dropped {drops}")
        ratios[policy] = rep["load_balance"]["decode"]["max_mean_ratio"]
    agree = np.mean([a == b for rid in outputs["harmoeny"]
                     for a, b in zip(outputs["harmoeny"][rid],
                                     outputs["round_robin"][rid])])
    print(f"[four-chips] greedy tokens equal across the two schedules: "
          f"{agree:.1%}")
    if not ratios["harmoeny"] <= ratios["round_robin"]:
        raise RuntimeError(f"harmoeny decode max/mean {ratios['harmoeny']} "
                           f"> round_robin {ratios['round_robin']}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices[:4]]
    print(f"[four-chips] peak_bytes_in_use per chip {peaks}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip expert-parallel phase")
    opts = ap.parse_args()
    devices = require_devices(4 if opts.four_chips else 1)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}; "
          f"compile cache {enable_compile_cache()}")
    (four_chips if opts.four_chips else one_chip)(devices)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
