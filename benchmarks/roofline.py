"""Roofline analysis from the dry-run matrix (assignment §ROOFLINE).

Reads results/dryrun/<arch>__<shape>__single.json and derives, per cell:

  compute   = HLO_FLOPs   / (chips * 197e12)      [s]
  memory    = HLO_bytes   / (chips * 819e9)       [s]
  collective= coll_bytes  / (chips * 50e9)        [s]

FLOPs/bytes/collective-bytes are the trip-count-corrected per-device values
(launch/hlo_analysis.py) multiplied back to all chips. MODEL_FLOPS is the
analytic 6*N(_active)*D (train) / 2*N*D (inference). The dominant term is the
bottleneck the §Perf loop iterates on.

  PYTHONPATH=src python -m benchmarks.roofline [--json]
"""
from __future__ import annotations

import glob
import json
import os
import sys

from repro.core.qthreshold import V5E

PEAK_FLOPS = V5E.peak_flops
HBM_BW = V5E.hbm_bw
ICI_BW = V5E.ici_bw

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")


def load_cells(tag="single"):
    cells = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, f"*__{tag}.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def roofline_row(cell):
    chips = cell["n_chips"]
    # corrected values are per-device; terms are per-chip times directly
    t_comp = cell["flops"] / PEAK_FLOPS
    t_mem = cell["bytes_accessed"] / HBM_BW
    t_coll = cell["collectives"]["total"] / ICI_BW
    dom = max(("compute", t_comp), ("memory", t_mem),
              ("collective", t_coll), key=lambda kv: kv[1])[0]
    hlo_flops_total = cell["flops"] * chips
    mf = cell.get("model_flops", 0.0)
    useful = mf / hlo_flops_total if hlo_flops_total else 0.0
    bound = max(t_comp, t_mem, t_coll)
    # roofline fraction: useful model FLOPs per chip-second at the bound
    frac = (mf / chips / PEAK_FLOPS) / bound if bound > 0 else 0.0
    return {
        "arch": cell["arch"], "shape": cell["shape"], "chips": chips,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dom, "model_flops": mf,
        "useful_flops_ratio": useful, "roofline_fraction": frac,
        "peak_gb": (cell["memory"]["peak_bytes"] or 0) / 1e9,
        "temp_gb": (cell["memory"]["temp_bytes"] or 0) / 1e9,
    }


def main():
    cells = load_cells("single")
    rows = [roofline_row(c) for c in cells]
    if "--json" in sys.argv:
        print(json.dumps(rows, indent=2))
        return
    hdr = (f"{'arch':<22}{'shape':<13}{'comp_s':>10}{'mem_s':>10}"
           f"{'coll_s':>10} {'dominant':<11}{'useful':>8}{'roofl%':>8}")
    print(hdr)
    print("-" * len(hdr))
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        print(f"{r['arch']:<22}{r['shape']:<13}"
              f"{r['t_compute_s']:>10.4f}{r['t_memory_s']:>10.4f}"
              f"{r['t_collective_s']:>10.4f} {r['dominant']:<11}"
              f"{r['useful_flops_ratio']:>8.3f}"
              f"{100 * r['roofline_fraction']:>7.1f}%")


if __name__ == "__main__":
    main()
