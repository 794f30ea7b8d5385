"""The two per-layer metrics read from the engine's own spans
(``step_host_ms``, ``decode_commit_ms``): on a synthetic run, on a run
whose program has no spans, and on the tiny CPU harness run traced, where
``step_host_ms`` reads close to ``host_gap_ms`` (the same host time
measured around ``step()`` from outside)."""
from __future__ import annotations

import json

import pytest

import bench_tiny

import run  # benchmarks/chip/run.py
from chipbench import client, registry
from chipbench.record import Run

SEED = 2 ** 35 + 17
SECONDS = 4.0
NEW = ("step_host_ms", "decode_commit_ms")


def _run_with(report):
    win = client.Window(seconds=1.0, report=report)
    return Run(window=win, calls=[], dims=None, config={}, mix={},
               peaks=None, chips=1, setup_s=0.0)


def _span(count, seconds):
    return {"count": count, "seconds": seconds, "self_seconds": 0.0}


def test_span_readers_on_a_synthetic_run():
    spans = {"serve.step": _span(10, 3.0), "serve.prefill": _span(4, 0.3),
             "serve.decode": _span(9, 2.6), "serve.commit": _span(9, 0.018),
             "serve.admit": _span(10, 0.001)}
    r = _run_with({"spans": spans})
    host = registry.module("metrics", "step_host_ms").read(r)
    assert host == pytest.approx((3.0 - 0.3 - 2.6) / 10 * 1e3)
    commit = registry.module("metrics", "decode_commit_ms").read(r)
    assert commit == pytest.approx(0.018 / 9 * 1e3)
    # speculative verify steps count as the jitted work and the steps
    spans = {"serve.step": _span(2, 1.0), "serve.verify": _span(2, 0.8),
             "serve.commit": _span(2, 0.01)}
    r = _run_with({"spans": spans})
    assert registry.module("metrics", "step_host_ms").read(r) \
        == pytest.approx(100.0)
    assert registry.module("metrics", "decode_commit_ms").read(r) \
        == pytest.approx(5.0)


@pytest.mark.parametrize("report", [{}, {"spans": {}},
                                    {"spans": {"serve.step": _span(0, 0.0)}}],
                         ids=["no_spans", "empty", "no_steps"])
def test_span_readers_give_nothing_without_spans(report):
    for name in NEW:
        assert registry.module("metrics", name).read(_run_with(report)) \
            is None


def test_traced_tiny_run_reports_the_span_metrics(tmp_path):
    tree = bench_tiny.write_tree(tmp_path)
    path = tree / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"] += [
        {"name": n, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "engine step loop",
         "moves": "itl_p90_ms"} for n in NEW]
    path.write_text(json.dumps(bench))
    out = run.run_cell(bench_tiny.CELL, SEED, SECONDS, True, base=tree,
                       repo=tree, require_chip=False)
    assert out["correct"], out["checks"]
    got = {n: m["value"] for n, m in out["metrics"].items()}
    assert {"host_gap_ms", *NEW} <= set(got)
    assert 0 < got["decode_commit_ms"] < got["step_host_ms"]
    # the same host time, read inside the program and from outside it
    assert got["step_host_ms"] == pytest.approx(got["host_gap_ms"],
                                                rel=0.5, abs=2.0)
