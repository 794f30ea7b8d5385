#!/usr/bin/env python3
"""The knee sweep of a served cell, on the chip (run once, when the
cell's rate is chosen; not part of the benchmark's runs).

    python3 benchmarks/chip/knee.py --workload qwen15-L6.chat \\
        --seconds 45 --rates 0.3 0.45 0.6 0.8 --seed 5

For each rate: one run of the cell's mix at that Poisson rate, drained
for at most ``--drain`` seconds.  The queue grows when requests wait
longer the later they arrive: the line gives the mean wait before
admission and the mean time to first token of the requests due in the
window's first and last thirds (the engine admits at once and queues
prompts for prefill, so the growth shows in the second), the requests
never served or never finished, and the tails.  The knee is the highest
rate at which the last third waits no longer than the first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from chipbench.stats import percentile  # noqa: E402


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args()
    for rate in args.rates:
        t0 = time.perf_counter()
        sv = run.serve(args.workload, args.seed, args.seconds, False,
                       mix_update={"arrivals": {"process": "poisson",
                                                "rate_per_s": rate},
                                   "drain_seconds": args.drain},
                       t_start=t0)
        w = sv.window
        obs = list(w.requests.values())
        third = args.seconds / 3
        early = [o for o in obs if o.arrival < third]
        late = [o for o in obs if o.arrival >= 2 * third]
        tpot = [o.tpot for o in obs if o.tpot is not None]
        print(json.dumps({
            "rate_per_s": rate, "attempted": w.attempted,
            "failed": w.failed, "unfinished": w.unfinished,
            "drain_s": w.drain_s,
            "unadmitted": sum(o.admitted is None for o in obs),
            "wait_first_third_s": _mean([o.admitted - o.arrival
                                         for o in early if o.admitted]),
            "wait_last_third_s": _mean([o.admitted - o.arrival
                                        for o in late if o.admitted]),
            "ttft_first_third_s": _mean([o.ttft for o in early]),
            "ttft_last_third_s": _mean([o.ttft for o in late]),
            "ttft_p50_ms": percentile([o.ttft for o in obs], 50) * 1e3,
            "ttft_p90_ms": percentile([o.ttft for o in obs], 90) * 1e3,
            "tpot_p50_ms": percentile(tpot, 50) * 1e3,
            "tpot_p90_ms": percentile(tpot, 90) * 1e3,
            "steps": len(w.steps), "run_s": time.perf_counter() - t0}),
            flush=True)


if __name__ == "__main__":
    main()
