#!/usr/bin/env python3
"""Run one cell of the chip benchmark (``BENCHMARK.json`` at the root).

    python3 benchmarks/chip/run.py --workload qwen15-L6.chat --seed 7 \\
        --seconds 45 --trace 0

Set-up builds the program's serving engine for the cell's configuration
with weights made from the seed, warms up every shape the cell uses and
makes the cell's traffic from the seed.  The window then drives
``ServeEngine.step()`` for ``--seconds``; requests still in flight are
drained (at most the mix's ``drain_seconds``).  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces part of the window with
the JAX profiler and reports the per-layer metrics.

Once the window is over, the peak device memory is read, the engine is
freed, and the configuration's float32 reference checks a seeded sample
of the served tokens.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
optionally ``breakdown``, and last ``checks``: each number compared with
its limit); the same checks are the last lines of standard error.  A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result.  The compile cache is the program's
(``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import List  # noqa: E402

import numpy as np  # noqa: E402

# the TPU runtime would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(REPO, "src"))

from chipbench import check, client, registry, system, traffic  # noqa: E402
from chipbench import weights, xtrace  # noqa: E402
from chipbench.peaks import PEAKS, peaks_of  # noqa: E402
from chipbench.record import Run  # noqa: E402

# the traced part of a --trace 1 window: where it starts (share of the
# window) and how long it lasts (seconds, at most a third of the window)
TRACE_AT = 0.4
TRACE_SECONDS = 6.0


class NoChip(RuntimeError):
    pass


def _devices(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX's devices are "
                     f"{devices[0].platform!r}")
    if require_chip and len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips; found "
                     f"{len(devices)}")
    return devices


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@dataclass
class Served:
    """A run up to its check: the result without ``correct`` and
    ``checks``, and what the check needs.  The engine is freed."""
    out: dict
    items: List[check.Item]
    bad_lengths: int
    compiles: int
    dims: object                  # the layout's Dims
    config: dict
    layout: object                # the configuration's layout module
    reference: object
    window: client.Window


def serve(workload: str, seed: int, seconds: float, trace: bool, *,
          base=registry.BENCH_DIR, repo=registry.REPO,
          require_chip: bool = True, fault=None, mix_update=None,
          t_start: float = T_START) -> Served:
    """Set-up, the window and its metrics; then the engine is freed.
    ``fault`` (tests only) gets the built system before warm-up;
    ``mix_update`` (the knee sweep) replaces keys of the traffic mix."""
    bench = registry.benchmark(repo)
    cell = registry.workload(bench, workload)
    cfg_doc = registry.load_json("configs", cell["config"], base)
    mix = {**registry.load_json("traffic", cell["traffic"], base),
           **(mix_update or {})}
    layout = registry.layout(cell["config"], cfg_doc, base)
    reference = registry.module("reference", cfg_doc["reference"], base)
    readers = {m["name"]: (m, registry.module("metrics", m["name"], base))
               for m in registry.metrics_for(bench, workload, trace)}
    chips = cell["chips"]
    devices = _devices(chips, require_chip)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    kind = devices[0].device_kind
    peaks = peaks_of(kind) if require_chip else PEAKS.get(kind)

    sut = system.build(cfg_doc, layout, mix, seed, diags=trace)
    if fault is not None:
        fault(sut)
    engine, rec = sut.engine, sut.recorder
    engine.warmup()
    rec.clear()
    if trace:
        client.warm_profiler()
    specs = traffic.generate(mix, seed, seconds, sut.dims.vocab, base)
    setup_s = time.perf_counter() - t_start

    win = client.run(
        engine, system.requests(specs), seconds, mix["drain_seconds"],
        outputs=rec.outputs, trace_at=TRACE_AT * seconds if trace else None,
        trace_seconds=min(TRACE_SECONDS, seconds / 3),
        on_trace=lambda on: setattr(rec, "tracing", on))
    peak = _peak_bytes(devices[:chips])
    red = None
    if win.trace_dir:
        import jax
        tr = xtrace.read(jax.profiler.ProfileData.from_file(
            client.trace_file(win.trace_dir)))
        client.remove_trace(win)
        span = [s for s in tr.spans if s.name == client.WINDOW_SPAN]
        if tr.ops and span:
            red = xtrace.reduce(tr, span[0].start, span[0].end)
    run = Run(window=win, calls=rec.calls, dims=sut.dims, config=cfg_doc,
              mix=mix, peaks=peaks, chips=chips, setup_s=setup_s,
              trace=red)
    metrics = {}
    for name, (m, reader) in readers.items():
        v = reader.read(run)
        if v is not None and math.isfinite(v):
            metrics[name] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": peak}
    out = {"attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": dev}
    if red is not None:
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        out["breakdown"] = xtrace.breakdown(red)

    # the finished requests, each of which must have served its whole
    # output (the mix sends no end-of-sequence token)
    due = {s.rid: s for s in specs}
    done = [o for o in win.requests.values() if o.finished]
    items = [check.Item(o.rid, due[o.rid].prompt,
                        np.asarray(o.tokens, np.int32)) for o in done]
    bad = sum(o.n != due[o.rid].output_len or len(o.tokens) != o.n
              for o in done)
    dims = sut.dims
    del sut, engine, rec, run
    gc.collect()
    return Served(out=out, items=items, bad_lengths=bad,
                  compiles=win.compiles, dims=dims, config=cfg_doc,
                  layout=layout, reference=reference, window=win)


def judge(sv: Served, seed: int) -> dict:
    """The check, with the program's state freed: the result object."""
    cc = sv.config["check"]
    chosen = check.sample(sv.items, seed, cc["sample_tokens"])
    got = check.gaps(sv.reference, sv.layout, weights.seed_key(seed),
                     sv.dims, chosen)
    checks = {
        "mean_logit_gap": {"value": got["mean_logit_gap"],
                           "limit": cc["mean_logit_gap"]},
        "served_length_errors": {"value": sv.bad_lengths, "limit": 0},
        "compiles_in_window": {"value": sv.compiles, "limit": 0},
    }
    correct = bool(sv.items) and all(c["value"] <= c["limit"]
                                     for c in checks.values())
    return {"correct": correct, **sv.out, "checks": checks}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run of ``workload``; returns the result object."""
    return judge(serve(workload, seed, seconds, trace, **kw), seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
