"""The chip benchmark's run, driven on the CPU at a tiny size: a sound run
is correct, and each fault a one-chip serving cell can have, planted in
the timed path, turns ``correct`` false.  The control (the reference one
precision down in the program's place) fails the same comparison.  Also:
the run refuses a machine without a TPU, and refuses by name a
configuration whose layout is missing, unknown, or does not fit the
program; the weights it fills are pinned."""
from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_checks
import bench_tiny

import run  # benchmarks/chip/run.py
from chipbench import check, registry, system, weights

SEED = 2 ** 33 + 5          # wider than 32 bits, as the driver's are
SECONDS = 4.0


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tiny.write_tree(tmp_path_factory.mktemp("bench"))


def _run(tree, fault=None, seed=SEED):
    return run.run_cell(bench_tiny.CELL, seed, SECONDS, False, base=tree,
                        repo=tree, require_chip=False, fault=fault)


def test_sound_run_is_correct(tree):
    out = _run(tree)
    assert out["correct"], out["checks"]
    assert out["attempted"] == round(3.0 * SECONDS) and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p90_ms", "tpot_p90_ms", "setup_s"}
    assert out["metrics"]["itl_p90_ms"]["value"] > 0
    assert list(out)[-1] == "checks"
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [bench_checks.state_unchanged,
                                   bench_checks.half_batch,
                                   bench_checks.token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_is_not_correct(tree, fault):
    out = _run(tree, fault=bench_checks.wrap_decode(fault))
    assert not out["correct"]
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"], gap


@pytest.mark.parametrize("seed", [11, 2 ** 40 + 3, 977])
def test_control_is_not_correct(tree, seed):
    """The reference computed one precision below the configuration's
    (fp8 for this bfloat16 model) fails the limit that the reference's
    own greedy tokens meet, on the same prompts and tokens."""
    cfg = registry.load_json("configs", "tiny", tree)
    ref = registry.module("reference", cfg["reference"], tree)
    layout = registry.layout("tiny", cfg, tree)
    dims = layout.from_config(cfg)
    key = weights.seed_key(seed)
    items = bench_checks.greedy_items(ref, layout, key, dims, seed)
    got = check.gaps(ref, layout, key, dims, items,
                     control=cfg["check"]["control"])
    assert got["mean_logit_gap"] == 0.0
    assert got["control_mean_gap"] > cfg["check"]["mean_logit_gap"]


def test_refuses_a_machine_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = run.main(["--workload", "qwen15-L6.chat", "--seed", "1",
                   "--seconds", "1"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == ""
    assert "no TPU" in captured.err


def test_weights_reach_program_and_reference_alike(tree):
    """The program's tree (as filled in one jitted call) and the
    reference's per-layer, per-expert draws are the same numbers, up to
    one unit in the last place of the served bfloat16 (two compiled
    programs may round the float32 draw's scaling differently, which
    rarely tips a rounding to bfloat16)."""
    cfg = registry.load_json("configs", "tiny", tree)
    layout = registry.layout("tiny", cfg, tree)
    dims = layout.from_config(cfg)
    key = weights.seed_key(SEED)
    d, f = dims.d_model, dims.d_expert
    shapes = {"stack": {"blocks": {"sub0": {
        "moe": {"w_in": jax.ShapeDtypeStruct((2, 8, d, f), jnp.bfloat16)},
        "attn": {"wq": jax.ShapeDtypeStruct((2, d, 4, 32), jnp.bfloat16)}}}}}
    for ranks in (1, 4):
        tree_ = jax.jit(lambda k: layout.fill_program_tree(
            shapes, dims, ranks, k))(key)
        blk = tree_["stack"]["blocks"]["sub0"]
        rows = weights.expert_of_row(8, ranks)
        for l in range(2):
            for r in range(8):
                want = layout.expert(key, l, int(rows[r]), dims)["w_in"]
                np.testing.assert_allclose(
                    np.asarray(blk["moe"]["w_in"][l, r], np.float32), want,
                    rtol=2 ** -7, atol=0)
            np.testing.assert_allclose(
                np.asarray(blk["attn"]["wq"][l], np.float32),
                layout.layer(key, l, dims)["wq"], rtol=2 ** -7, atol=0)
    assert weights.expert_of_row(8, 4).tolist() == [0, 4, 1, 5, 2, 6, 3, 7]


# float64 sums of the tiny tree's weights at SEED, read before the layout
# moved out of the harness; the program's tree as filled in one jitted
# call, and the reference's draws of layer 1 and its expert 5
PINNED_TREE = {
    "embed": -1.8706700393231586,
    "stack/blocks/sub0/attn/wq[1]": -7.506348013877869,
    "stack/blocks/sub0/moe/w_in[1,3]": 2.1437333822250366,
    "stack/blocks/sub0/moe/router": 0.40583869582587795,
    "final_norm/scale": -0.5545365333091468,
}
PINNED_LAYER = {
    "norm1": -0.2674581111641601, "norm2": -0.7047602433594875,
    "wq": -7.506348013877869, "wk": -8.258558988571167,
    "wv": 2.384454697370529, "wo": -6.796992778778076,
    "router": -0.22475579504134657,
    "shared_w_in": 28.210099577903748, "shared_w_gate": 16.38469934463501,
    "shared_w_out": -17.5086487531662,
}
PINNED_EXPERT = {"w_in": -15.226040482521057, "w_gate": -4.005103945732117,
                 "w_out": -0.5510120391845703}


def _sum(x) -> float:
    return float(np.asarray(x, np.float64).sum())


@pytest.fixture(scope="module")
def filled(tree):
    cfg = registry.load_json("configs", "tiny", tree)
    mix = registry.load_json("traffic", "tiny", tree)
    layout = registry.layout("tiny", cfg, tree)
    return system.build(cfg, layout, mix, SEED).engine.params


@pytest.mark.parametrize("leaf", sorted(PINNED_TREE))
def test_filled_weights_are_pinned(filled, leaf):
    """The weights the run fills are those it filled before its layout
    became a file of its own, to the last bit."""
    path, _, idx = leaf.partition("[")
    x = filled
    for k in path.split("/"):
        x = x[k]
    if idx:
        x = x[tuple(int(i) for i in idx.rstrip("]").split(","))]
    assert _sum(x) == PINNED_TREE[leaf]


def test_reference_draws_are_pinned(tree):
    cfg = registry.load_json("configs", "tiny", tree)
    layout = registry.layout("tiny", cfg, tree)
    dims, key = layout.from_config(cfg), weights.seed_key(SEED)
    assert {n: _sum(x) for n, x in layout.layer(key, 1, dims).items()} \
        == PINNED_LAYER
    assert {n: _sum(x) for n, x in layout.expert(key, 1, 5, dims).items()} \
        == PINNED_EXPERT


def _no_layout(cfg):
    del cfg["layout"]


def _unknown_layout(cfg):
    cfg["layout"] = "no_such_layout"


def _wider_expert(cfg):
    cfg["moe_intermediate_size"] = 96


def _lead_dense_layer(cfg):
    # the program's reduced moonshot model has every width of the tiny
    # configuration, and a leading dense layer this layout does not know
    cfg["program"] = {"arch": "moonshot-v1-16b-a3b", "reduced": True}


@pytest.mark.parametrize("edit, error, named", [
    (_no_layout, ValueError, "configuration 'tiny' names no layout"),
    (_unknown_layout, FileNotFoundError, "layout 'no_such_layout'"),
    (_wider_expert, ValueError, "d_expert: program 64, file 96"),
    (_lead_dense_layer, KeyError, "program leaf 'stack/lead/0/"),
], ids=["no_layout", "unknown_layout", "program_differs", "unknown_leaf"])
def test_setup_refuses_a_layout_by_name(tmp_path, edit, error, named):
    tree = bench_tiny.write_tree(tmp_path)
    path = tree / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))
    with pytest.raises(error, match=re.escape(named)):
        _run(tree)
