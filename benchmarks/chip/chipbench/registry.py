"""Discovery: everything that belongs to one item is found by its name.

* ``BENCHMARK.json`` at the repository root lists cells and metrics;
* configuration ``<name>``: its file ``configs/<name>.json``, its layout
  ``layouts/<doc["layout"]>.py`` (the model's widths, its weights and
  where they sit in the program's tree, its FLOPs per token) and its
  reference ``reference/<doc["reference"]>.py``;
* traffic mix ``<name>``: ``traffic/<name>.json``; the arrival process it
  names: ``arrivals/<process>.py``, a module with ``generate``;
* metric ``<name>``: ``metrics/<name>.py``, a module with ``read(run)``
  returning the value, or None when the run holds nothing to read.

Adding a configuration is adding its file, its layout and its reference
(a model shaped like one already here names that one's layout); adding a
mix, an arrival process or a metric is adding a file.  Each but a process
also gets an entry in ``BENCHMARK.json``; no other file changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/chip
REPO = BENCH_DIR.parents[1]


def benchmark(repo: Path = REPO) -> Dict[str, Any]:
    with open(Path(repo) / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_json(kind: str, name: str, base: Path = BENCH_DIR) -> Dict[str, Any]:
    with open(Path(base) / kind / f"{name}.json") as f:
        return json.load(f)


def module(kind: str, name: str, base: Path = BENCH_DIR):
    path = Path(base) / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    # registered, as an import would be, so that dataclasses can resolve
    # the module's annotations
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def layout(config: str, cfg_doc: Dict[str, Any], base: Path = BENCH_DIR):
    """The layout module that configuration ``config`` names; a
    configuration without one, or naming one with no file, is refused."""
    name = cfg_doc.get("layout")
    if not name:
        raise ValueError(f"configuration {config!r} names no layout: its "
                         f"file needs a \"layout\" key naming a module in "
                         f"{Path(base) / 'layouts'}")
    path = Path(base) / "layouts" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config!r} names layout "
                                f"{name!r}, which has no file {path}")
    return module("layouts", name, base)


def metrics_for(bench: Dict[str, Any], cell: str, traced: bool
                ) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics (``traced`` false) or per-layer
    metrics (``traced`` true)."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
