"""A model shaped unlike Qwen1.5-MoE goes into the benchmark as files: the
program's reduced Switch Transformer (dense and MoE layers alternating,
top-1, ungated GELU FFNs, no shared expert, tied embeddings), with a
layout and a reference of its own tree (``bench_switch``), runs through
the unchanged harness on the CPU.  A sound run is correct; a decode step
that keeps its state, or alters a token, is not; the reference one
precision down fails the same limit; and the reference agrees with the
program when both compute in float32."""
from __future__ import annotations

import dataclasses
import json
import re

import jax
import numpy as np
import pytest

import bench_checks
import bench_switch
import bench_tiny

import run  # benchmarks/chip/run.py
from chipbench import check, registry, system, weights

SEED = 2 ** 34 + 9          # wider than 32 bits, as the driver's are
SECONDS = 4.0
# float32 on both sides; they differ in reduction order only
TOL = 1e-4


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_switch.write_tree(tmp_path_factory.mktemp("switch"))


def _run(tree, fault=None):
    return run.run_cell(bench_switch.CELL, SEED, SECONDS, False, base=tree,
                        repo=tree, require_chip=False, fault=fault)


def test_switch_sound_run_is_correct(tree):
    out = _run(tree)
    assert out["correct"], out["checks"]
    assert out["attempted"] == round(3.0 * SECONDS) and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p90_ms", "tpot_p90_ms", "setup_s"}
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [bench_checks.state_unchanged,
                                   bench_checks.token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_switch_fault_is_not_correct(tree, fault):
    out = _run(tree, fault=bench_checks.wrap_decode(fault))
    assert not out["correct"]
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"], gap


@pytest.mark.parametrize("seed", [11, 2 ** 40 + 3, 977])
def test_switch_control_is_not_correct(tree, seed):
    """The reference in fp8 (one below the served bfloat16) puts first
    tokens that miss the limit the reference's own greedy tokens meet."""
    cfg = registry.load_json("configs", "switch", tree)
    ref = registry.module("reference", cfg["reference"], tree)
    layout = registry.layout("switch", cfg, tree)
    dims = layout.from_config(cfg)
    key = weights.seed_key(seed)
    items = bench_checks.greedy_items(ref, layout, key, dims, seed,
                                      served=bench_switch.SERVED)
    got = check.gaps(ref, layout, key, dims, items,
                     control=cfg["check"]["control"])
    assert got["mean_logit_gap"] == 0.0
    assert got["control_mean_gap"] > cfg["check"]["mean_logit_gap"]


@pytest.mark.parametrize("length", [5, 37])
def test_switch_reference_matches_program_logits(tmp_path, length):
    """At float32 the program's prefill logits are the reference's, and a
    reference that routed every token through all experts is not."""
    tree = bench_switch.write_tree(tmp_path)
    path = tree / "configs" / "switch.json"
    cfg = {**json.loads(path.read_text()), "torch_dtype": "float32"}
    path.write_text(json.dumps(cfg))
    layout = registry.layout("switch", cfg, tree)
    sut = system.build(cfg, layout, bench_switch.MIX, SEED)
    ref = registry.module("reference", cfg["reference"], tree)
    toks = np.random.default_rng(length).integers(
        0, sut.dims.vocab, length).astype(np.int32)
    got = jax.jit(sut.engine.model.prefill)(
        sut.engine.params, {"tokens": toks[None]})[0]
    got = np.asarray(got)[0, :sut.dims.vocab]
    key = weights.seed_key(SEED)
    want = ref.logits(layout, key, sut.dims, toks, np.asarray([length - 1]))
    other = ref.logits(layout, key, dataclasses.replace(sut.dims, top_k=8),
                       toks, np.asarray([length - 1]))
    assert np.max(np.abs(got - want[0])) <= TOL
    assert np.max(np.abs(got - other[0])) > 100 * TOL


def test_harness_names_no_model():
    """Neither model's layout leaks into the harness: its library, its
    command line and its control name no model's keys or leaves."""
    files = [*(bench_tiny.BENCH / "chipbench").glob("*.py"),
             bench_tiny.BENCH / "run.py", bench_tiny.BENCH / "control.py"]
    named = re.compile(r"shared_expert_intermediate_size|num_key_value_heads"
                       r"|shared_w_in|MoEDims|qwen2_moe|switch|d_kv")
    for f in files:
        assert not named.search(f.read_text()), f
    assert not (bench_tiny.BENCH / "layouts" / "switch_transformers.py"
                ).exists()
