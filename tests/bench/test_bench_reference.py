"""The benchmark's float32 reference agrees with the program at the
reduced size on the CPU: embedding, causal attention with RoPE, the
router's top-k, the routed and shared experts, the final norm and the
logits over the whole vocabulary.  So a wrong reference cannot mark the
chip runs incorrect."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

import bench_tiny

from chipbench import registry, system, weights

SEED = 2 ** 36 + 17
# float32 on both sides; the two differ only in reduction order (the
# program's chunked online softmax and grouped expert tiles), ~1e-6 of
# logits of magnitude ~1
TOL = 1e-4


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tree = bench_tiny.write_tree(tmp_path_factory.mktemp("bench"),
                                 dtype="float32")
    cfg = registry.load_json("configs", "tiny", tree)
    mix = registry.load_json("traffic", "tiny", tree)
    layout = registry.layout("tiny", cfg, tree)
    sut = system.build(cfg, layout, mix, SEED)
    ref = registry.module("reference", cfg["reference"], tree)
    return sut, ref, layout, sut.dims


@pytest.mark.parametrize("length", [3, 17, 40])
def test_reference_matches_program_logits(served, length):
    sut, ref, layout, dims = served
    model, params = sut.engine.model, sut.engine.params
    toks = np.random.default_rng(length).integers(
        0, dims.vocab, length).astype(np.int32)
    got, _, _, _ = jax.jit(model.prefill)(params, {"tokens": toks[None]})
    got = np.asarray(got)[0, :dims.vocab]
    want = ref.logits(layout, weights.seed_key(SEED), dims, toks,
                      np.asarray([length - 1]))[0]
    assert np.max(np.abs(got - want)) <= TOL
    assert got.argmax() == want.argmax()


def test_reference_routes_like_the_program(served):
    """The reduced model routes top-2 of 8; a reference that ignored the
    router (all experts, or none) would not match the program."""
    sut, ref, layout, dims = served
    toks = np.arange(24, dtype=np.int32) % dims.vocab
    got = np.asarray(jax.jit(sut.engine.model.prefill)(
        sut.engine.params, {"tokens": toks[None]})[0])[0, :dims.vocab]
    key = weights.seed_key(SEED)
    want = ref.logits(layout, key, dims, toks, np.asarray([23]))[0]
    other = ref.logits(layout, key, dataclasses.replace(dims, top_k=8),
                       toks, np.asarray([23]))[0]
    assert np.max(np.abs(got - want)) <= TOL
    assert np.max(np.abs(got - other)) > 100 * TOL
