"""What the tiny trees' tests plant in the timed path, and the control's
inputs: faults a one-chip serving cell can have, each wrapping the
engine's jitted decode step, and prompts continued greedily by a
reference, on which the reference one precision down is read."""
from __future__ import annotations

import numpy as np

import bench_tiny  # noqa: F401  (puts benchmarks/chip on the path)
from chipbench import check

VOCAB = 512                 # both tiny trees' vocabulary


def wrap_decode(fn):
    """A fault for ``run.run_cell``: the engine's decode step replaced by
    ``fn(decode_step)``."""
    def fault(sut):
        core = sut.engine.core
        inner = core.decode_fn
        broken = fn(inner)
        broken._cache_size = inner._cache_size
        core.decode_fn = broken
    return fault


def state_unchanged(inner):
    # the decode step's K/V writes are dropped: it returns the pool as
    # it was given
    def f(params, tok, pool, *rest):
        nxt, _, diags = inner(params, tok, pool, *rest)
        return nxt, pool, diags
    return f


def half_batch(inner):
    # every other slot is left out of the step's batch
    def f(params, tok, pool, pos, bt, key, active, *rest):
        keep = active & (np.arange(active.shape[0]) % 2 == 0)
        return inner(params, tok, pool, pos, bt, key, keep, *rest)
    return f


def token_altered(inner):
    # each step, one slot's token (the slots in turn) is replaced by its
    # neighbour id, so every request that decodes long enough has some
    steps = [0]

    def f(*args):
        nxt, pool, diags = inner(*args)
        slot = steps[0] % nxt.shape[0]
        steps[0] += 1
        return nxt.at[slot].set((nxt[slot] + 1) % VOCAB), pool, diags
    return f


def greedy_items(ref, layout, key, dims, seed, requests=6, prompt=20,
                 served=8):
    """``requests`` random prompts, each continued by ``served`` tokens
    that the reference puts first: the served tokens a sound program
    would give, on which the control is read."""
    rng = np.random.default_rng(seed)
    items = []
    for rid in range(requests):
        p = rng.integers(0, dims.vocab, prompt).astype(np.int32)
        toks = list(p)
        out = []
        for _ in range(served):
            lg = ref.logits(layout, key, dims, np.asarray(toks, np.int32),
                            np.asarray([len(toks) - 1]))
            out.append(int(lg[0].argmax()))
            toks.append(out[-1])
        items.append(check.Item(rid, p, np.asarray(out, np.int32)))
    return items
