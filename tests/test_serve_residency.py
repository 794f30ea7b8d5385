"""Tiered expert-residency serving differential (multi-device subprocess,
like test_serve_rebalance.py):

* greedy token streams are *identical* across a fully-resident budget, a
  tight budget under every prefetch policy, and residency off — device
  parameters stay authoritative, so the tier emulation moves scheduling
  and accounting, never math;
* the ``[G, W]`` residency table rides into the decode jit entry as a
  traced argument: the decode cache holds ONE entry and nothing
  recompiles after warmup, across live working-set swaps;
* the same holds under prefix sharing + speculative k=4 (the verify-step
  decode path shares the residency threading);
* ``report()["residency"]`` is populated (hit_rate, stall_units, swaps,
  bytes_staged) and the engine-level config validation rejects bad
  budgets and unknown policies.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # the child never races for a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


_COMMON = """
import numpy as np, jax
from repro.configs.base import ModelConfig, MoEConfig, ParallelConfig
from repro.launch.mesh import make_host_mesh
from repro.models.model import MeshShape, build_model
from repro.serve import (Request, ServeEngine, VirtualClock,
                         engine_config_for)

CFG = ModelConfig(
    name="tinymoe", family="moe", num_layers=2, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
    head_dim=16, dtype="float32",
    moe=MoEConfig(num_experts=8, num_experts_per_tok=2, d_ff_expert=32,
                  policy="harmoeny", router_skew=0.95, q_tokens=1,
                  num_foreign_slots=2))
MESH = make_host_mesh(1, 4)
MS = MeshShape(tuple(zip(MESH.axis_names, MESH.devices.shape)))
MODEL = build_model(CFG, ParallelConfig(attn_chunk=8, loss_chunk=8),
                    batch=4, seq_len=16, mesh_shape=MS, mesh=MESH)
with MESH:
    PARAMS = MODEL.init(jax.random.PRNGKey(0))


def requests(shared_prefix=0):
    rng = np.random.default_rng(7)
    pre = rng.integers(1, 60, size=shared_prefix).astype(np.int32)
    out = []
    for i in range(6):
        toks = rng.integers(1, 60, size=8).astype(np.int32)
        toks[:shared_prefix] = pre
        out.append(Request(rid=i, tokens=toks, max_new_tokens=6,
                           arrival_time=0.0))
    return out


def run_engine(resident, policy, shared_prefix=0, **ekw):
    ecfg = engine_config_for(CFG, max_slots=4, prompt_len=8,
                             max_new_tokens=6, prefill_chunk=4,
                             resident_experts=resident,
                             prefetch_policy=policy, **ekw)
    eng = ServeEngine(MODEL, PARAMS, ecfg, mesh=MESH,
                      clock=VirtualClock(0.5))
    eng.warmup()
    # capture every finished request's exact greedy token stream
    tokens = {}
    orig = eng._finish
    def capture(st, now):
        tokens[st.req.rid] = list(st.output)
        orig(st, now)
    eng._finish = capture
    rep = eng.run(requests(shared_prefix))
    return rep, tokens
"""


def test_residency_budgets_token_identical_and_jit_stable():
    """Plain-decode differential over the same skewed request stream:
    residency off / fully resident / tight budget x {predictive,
    on_demand, none} all produce bit-identical greedy token streams with
    one decode jit entry and zero post-warmup recompiles, while the
    tight budgets actually swap (staging scatters dispatched) and report
    a populated residency section."""
    _run(_COMMON + """
    cells = {
        "off":  run_engine(0, "predictive"),
        "full": run_engine(8, "predictive"),
        "pred": run_engine(4, "predictive"),
        "odem": run_engine(4, "on_demand"),
        "none": run_engine(4, "none"),
    }
    base = cells["off"][1]
    assert base and all(len(v) for v in base.values())
    for name, (rep, toks) in cells.items():
        assert toks == base, f"{name} diverged from residency-off"
        lb = rep["load_balance"]["decode"]
        assert lb["send_drops_total"] == 0, name
        assert lb["dest_drops_total"] == 0, name
        assert rep["jit_entries"]["decode"] == 1, name
        assert rep["recompiled_after_warmup"] is False, name

    # residency section populated, hits+misses == lookups
    for name in ("full", "pred", "odem", "none"):
        res = cells[name][0]["residency"]
        assert res["lookups"] > 0, name
        assert res["hits"] + res["misses"] == res["lookups"], name
    full = cells["full"][0]["residency"]
    assert full["hit_rate"] == 1.0 and full["swaps"] == 0

    # tight budgets miss and (except under "none") stage weights in
    pred_rep, odem_rep = cells["pred"][0], cells["odem"][0]
    for rep in (pred_rep, odem_rep):
        res = rep["residency"]
        assert res["swaps"] >= 1 and res["bytes_staged"] > 0
        assert rep["engine"]["residency_stages"] >= 1
        assert rep["jit_entries"]["residency_stage"] >= 1
    none_res = cells["none"][0]["residency"]
    assert none_res["swaps"] == 0 and none_res["bytes_staged"] == 0
    assert none_res["stall_units"] >= odem_rep["residency"]["stall_units"]
    assert cells["pred"][0]["residency"]["prefetches"] >= 1
    print("OK")
    """)


def test_residency_under_prefix_sharing_and_speculation():
    """The verify-step decode path (paged + prefix sharing + k=4
    self-drafting) threads the same residency table: tight-budget
    predictive stays token-identical to residency off, with one decode
    jit entry and no post-warmup recompiles across swaps."""
    _run(_COMMON + """
    kw = dict(paged=True, kv_block_size=4, prefix_sharing=True,
              speculative_k=4)
    off_rep, off_toks = run_engine(0, "predictive", shared_prefix=4, **kw)
    res_rep, res_toks = run_engine(4, "predictive", shared_prefix=4, **kw)
    assert off_toks and res_toks == off_toks, "residency diverged the stream"
    for name, rep in (("off", off_rep), ("res", res_rep)):
        assert rep["jit_entries"]["decode"] == 1, name
        assert rep["recompiled_after_warmup"] is False, name
        lb = rep["load_balance"]["decode"]
        assert lb["send_drops_total"] == 0, name
        assert lb["dest_drops_total"] == 0, name
    res = res_rep["residency"]
    assert res["lookups"] > 0
    assert res["hits"] + res["misses"] == res["lookups"]
    assert res_rep["engine"]["prefetch_policy"] == "predictive"
    # prefix sharing still worked under residency
    assert res_rep["prefix_hit_rate"] and res_rep["prefix_hit_rate"] > 0
    print("OK")
    """)


def test_engine_rejects_bad_residency_budget():
    """Budgets that don't split across the EP degree — or exceed the
    pod's expert rows — are admission-time errors, not silent clamps."""
    _run(_COMMON + """
    for bad in (3, 12):     # not a multiple of G=4; > 8 pod expert rows
        ecfg = engine_config_for(CFG, max_slots=4, prompt_len=8,
                                 max_new_tokens=6, prefill_chunk=4,
                                 resident_experts=bad)
        try:
            ServeEngine(MODEL, PARAMS, ecfg, mesh=MESH)
        except ValueError as e:
            assert "resident_experts" in str(e), e
        else:
            raise AssertionError(f"budget {bad} was accepted")
    print("OK")
    """)


def test_engine_config_validation():
    from repro.serve.engine import EngineConfig
    with pytest.raises(ValueError):
        EngineConfig(resident_experts=-1)
    with pytest.raises(ValueError):
        EngineConfig(prefetch_policy="psychic")
    EngineConfig(resident_experts=8, prefetch_policy="on_demand")  # valid


def test_residency_needs_moe():
    from repro.serve.metrics import ServeMetrics
    m = ServeMetrics()
    assert "residency" not in m.report()       # off => section absent
    m.residency = {"hits": 1, "lookups": 1, "hit_rate": 1.0}
    assert m.report()["residency"]["hit_rate"] == 1.0
