"""The chip benchmark's run, driven on the CPU at a tiny size: a sound run
is correct, and each fault a one-chip serving cell can have, planted in
the timed path, turns ``correct`` false.  The control (the reference one
precision down in the program's place) fails the same comparison.  Also:
the run refuses a machine without a TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny

import run  # benchmarks/chip/run.py
from chipbench import check, registry, weights

SEED = 2 ** 33 + 5          # wider than 32 bits, as the driver's are
SECONDS = 4.0


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tiny.write_tree(tmp_path_factory.mktemp("bench"))


def _run(tree, fault=None, seed=SEED):
    return run.run_cell(bench_tiny.CELL, seed, SECONDS, False, base=tree,
                        repo=tree, require_chip=False, fault=fault)


def _wrap_decode(fn):
    def fault(sut):
        core = sut.engine.core
        inner = core.decode_fn
        broken = fn(inner)
        broken._cache_size = inner._cache_size
        core.decode_fn = broken
    return fault


def _state_unchanged(inner):
    # the decode step's K/V writes are dropped: it returns the pool as
    # it was given
    def f(params, tok, pool, *rest):
        nxt, _, diags = inner(params, tok, pool, *rest)
        return nxt, pool, diags
    return f


def _half_batch(inner):
    # every other slot is left out of the step's batch
    def f(params, tok, pool, pos, bt, key, active, *rest):
        keep = active & (np.arange(active.shape[0]) % 2 == 0)
        return inner(params, tok, pool, pos, bt, key, keep, *rest)
    return f


def _token_altered(inner):
    # each step, one slot's token (the slots in turn) is replaced by its
    # neighbour id, so every request that decodes long enough has some
    steps = [0]

    def f(*args):
        nxt, pool, diags = inner(*args)
        slot = steps[0] % nxt.shape[0]
        steps[0] += 1
        return nxt.at[slot].set((nxt[slot] + 1) % 512), pool, diags
    return f


def test_sound_run_is_correct(tree):
    out = _run(tree)
    assert out["correct"], out["checks"]
    assert out["attempted"] == round(3.0 * SECONDS) and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p90_ms", "tpot_p90_ms", "setup_s"}
    assert out["metrics"]["itl_p90_ms"]["value"] > 0
    assert list(out)[-1] == "checks"
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_is_not_correct(tree, fault):
    out = _run(tree, fault=_wrap_decode(fault))
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
    dims = weights.MoEDims.from_config(cfg)
    key = weights.seed_key(seed)
    rng = np.random.default_rng(seed)
    items = []
    for rid in range(6):
        prompt = rng.integers(0, dims.vocab, 20).astype(np.int32)
        toks = list(prompt)
        served = []
        for _ in range(8):       # greedy continuation by the reference
            lg = ref.logits(key, dims, np.asarray(toks, np.int32),
                            np.asarray([len(toks) - 1]))
            served.append(int(lg[0].argmax()))
            toks.append(served[-1])
        items.append(check.Item(rid, prompt, np.asarray(served, np.int32)))
    got = check.gaps(ref, key, dims, items, control=cfg["check"]["control"])
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
    dims = weights.MoEDims.from_config(cfg)
    key = weights.seed_key(SEED)
    d, f = dims.d_model, dims.d_expert
    shapes = {"stack": {"blocks": {"sub0": {
        "moe": {"w_in": jax.ShapeDtypeStruct((2, 8, d, f), jnp.bfloat16)},
        "attn": {"wq": jax.ShapeDtypeStruct((2, d, 4, 32), jnp.bfloat16)}}}}}
    for ranks in (1, 4):
        tree_ = jax.jit(lambda k: weights.fill_program_tree(
            shapes, dims, ranks, k))(key)
        blk = tree_["stack"]["blocks"]["sub0"]
        rows = weights.expert_of_row(8, ranks)
        for l in range(2):
            for r in range(8):
                want = weights.expert(key, l, int(rows[r]), dims)["w_in"]
                np.testing.assert_allclose(
                    np.asarray(blk["moe"]["w_in"][l, r], np.float32), want,
                    rtol=2 ** -7, atol=0)
            np.testing.assert_allclose(
                np.asarray(blk["attn"]["wq"][l], np.float32),
                weights.layer(key, l, dims)["wq"], rtol=2 ** -7, atol=0)
    assert weights.expert_of_row(8, 4).tolist() == [0, 4, 1, 5, 2, 6, 3, 7]
