#!/usr/bin/env python3
"""Readings of a cell's correctness check, program and control, on the
chip (not part of the benchmark's runs).

    python3 benchmarks/chip/control.py --workload qwen15-L6.chat \\
        --seconds 30 --seeds 101 102 103

For each seed: one run of the cell at its own load for ``--seconds``
(drained as usual), then, on the same seeded sample of served tokens,
the program's mean and widest logit gap below the float32 reference and
the control's: the gaps of the tokens that the reference computed one
precision below the configuration's (its ``check.control``) puts first.
One JSON line per seed.  The limit in the configuration's ``check`` is
set between the largest program reading and the smallest control one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from chipbench import check, weights  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        t0 = time.perf_counter()
        sv = run.serve(args.workload, seed, args.seconds, False, t_start=t0)
        cc = sv.config["check"]
        chosen = check.sample(sv.items, seed, cc["sample_tokens"])
        t1 = time.perf_counter()
        got = check.gaps(sv.reference, sv.layout, weights.seed_key(seed),
                         sv.dims, chosen, control=cc["control"])
        print(json.dumps({
            "seed": seed, "requests": len(sv.items),
            "sampled": [it.rid for it in chosen], **got,
            "control": cc["control"], "limit": cc["mean_logit_gap"],
            "served_length_errors": sv.bad_lengths,
            "compiles_in_window": sv.compiles,
            "run_s": t1 - t0, "check_s": time.perf_counter() - t1}),
            flush=True)


if __name__ == "__main__":
    main()
