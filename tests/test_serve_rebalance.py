"""Hot-expert replication tests: rebalancer policy units, metrics routing,
and (in a multi-device subprocess, like test_distributed.py) the serving
differential — greedy token streams are identical across scheduling
policies and with replication on, while the jit caches never grow."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.topology import make_topology
from repro.serve.metrics import ServeMetrics
from repro.serve.rebalance import ExpertRebalancer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ----------------------------------------------------------------------
# ExpertRebalancer policy (pure host-side numpy)
# ----------------------------------------------------------------------
def test_uniform_load_never_replicates():
    rb = ExpertRebalancer(make_topology(4, 8), 2)
    for _ in range(5):
        rb.observe(np.full(8, 10.0))
    dec = rb.propose()
    assert dec.hot_experts == []
    assert (dec.replica_ids == -1).all()
    assert not dec.changed              # init state is already all-empty


def test_hot_expert_replicated_on_non_hosts():
    topo = make_topology(4, 8)
    rb = ExpertRebalancer(topo, 2)
    load = np.full(8, 5.0)
    load[3] = 200.0                     # expert 3 is scorching
    for _ in range(3):
        rb.observe(load)
    dec = rb.propose()
    assert dec.hot_experts == [3]
    host = int(topo.host_of[3, 0])
    from repro.core.topology import local_slot_of
    src_row = host * topo.experts_per_rank + int(local_slot_of(topo)[host, 3])
    for g in range(4):
        if g == host:                   # host serves it from a local slot
            assert (dec.replica_ids[g] == -1).all()
        else:
            assert dec.replica_ids[g, 0] == 3
            # weight row = the host's stacked expert row for expert 3
            assert dec.weight_rows[g * 2 + 0] == src_row
    assert dec.changed
    # identical EMA -> identical proposal -> no swap
    dec2 = rb.propose()
    assert not dec2.changed
    assert (dec2.replica_ids == dec.replica_ids).all()


def test_ema_tracks_shifting_hotspot():
    """When the stream's hotspot drifts, the proposal follows it — the
    live-rebalancing behavior static placements cannot match."""
    rb = ExpertRebalancer(make_topology(4, 8), 1, ema_alpha=0.5)
    a = np.full(8, 1.0)
    a[0] = 100.0
    for _ in range(4):
        rb.observe(a)
    assert rb.propose().hot_experts == [0]
    b = np.full(8, 1.0)
    b[5] = 100.0
    for _ in range(6):
        rb.observe(b)
    dec = rb.propose()
    assert dec.hot_experts == [5]
    assert dec.changed


def test_per_layer_ema_is_independent_of_the_global_one():
    """``observe(load, layer=l)`` feeds the residency predictor's
    per-layer EMAs without perturbing anything the replication policy
    reads: the global EMA, ``hot()`` and ``propose()`` are bit-identical
    whether or not callers tag their observations with a layer."""
    topo = make_topology(4, 8)
    a = np.full(8, 1.0)
    a[2] = 50.0
    b = np.full(8, 1.0)
    b[6] = 50.0
    plain = ExpertRebalancer(topo, 1, ema_alpha=0.5)
    tagged = ExpertRebalancer(topo, 1, ema_alpha=0.5)
    for load, layer in ((a, 0), (b, 1), (a, 0), (b, 1)):
        plain.observe(load)
        tagged.observe(load, layer=layer)
    assert np.array_equal(plain.ema, tagged.ema)
    assert plain.hot() == tagged.hot()
    assert (plain.propose().replica_ids == tagged.propose().replica_ids).all()

    # never tagging leaves the per-layer table empty...
    assert plain.layer_ema == {}
    # ...and tagged layers fold separately: layer 0 only ever saw ``a``
    # (seed copy then one alpha=0.5 fold of the same vector => exactly a)
    assert set(tagged.layer_ema) == {0, 1}
    assert np.array_equal(tagged.layer_ema[0], a.astype(np.float64))
    assert np.array_equal(tagged.layer_ema[1], b.astype(np.float64))
    assert tagged.layer_ema[0][2] == 50.0 and tagged.layer_ema[0][6] == 1.0

    # a drifting layer follows the fold: 0.5 * 50 + 0.5 * 1 on slot 2
    tagged.observe(b, layer=0)
    assert tagged.layer_ema[0][2] == pytest.approx(25.5)
    assert tagged.layer_ema[0][6] == pytest.approx(25.5)
    # layer 1 untouched by layer 0's update
    assert np.array_equal(tagged.layer_ema[1], b.astype(np.float64))


def test_top_r_limit_and_threshold():
    rb = ExpertRebalancer(make_topology(4, 8), 2, hot_threshold=1.5)
    load = np.array([100.0, 90.0, 80.0, 1, 1, 1, 1, 1])
    rb.observe(load)
    hot = rb.hot()
    assert hot == [0, 1]                # R=2 caps the set, hottest first
    # threshold is mean-relative: scaling the whole vector changes nothing
    rb2 = ExpertRebalancer(make_topology(4, 8), 2, hot_threshold=1.5)
    rb2.observe(load * 1000)
    assert rb2.hot() == [0, 1]


def test_rebalancer_validates_shapes():
    rb = ExpertRebalancer(make_topology(4, 8), 1)
    with pytest.raises(ValueError):
        rb.observe(np.ones(5))
    with pytest.raises(ValueError):
        ExpertRebalancer(make_topology(4, 8), 0)
    with pytest.raises(ValueError):
        ExpertRebalancer(make_topology(4, 2), 1)   # E < G: no unique hosts


# ----------------------------------------------------------------------
# Metrics: vector diagnostics -> load_balance report
# ----------------------------------------------------------------------
def test_metrics_load_balance_section():
    m = ServeMetrics()
    m.record_step({"moved_units": 3.0, "send_drops": 0.0, "dest_drops": 1.0,
                   "rank_load": np.array([9.0, 1.0, 1.0, 1.0]),
                   "expert_load": np.arange(8, dtype=np.float64)},
                  4, phase="decode")
    m.record_step({"moved_units": 1.0, "send_drops": 0.0, "dest_drops": 0.0,
                   "rank_load": np.array([3.0, 1.0, 1.0, 1.0]),
                   "expert_load": np.arange(8, dtype=np.float64)},
                  4, phase="decode")
    rep = m.report()
    lb = rep["load_balance"]["decode"]
    assert lb["rank_load_mean"] == [6.0, 1.0, 1.0, 1.0]
    assert len(lb["expert_load_mean"]) == 8
    assert lb["max_load_mean"] == 6.0
    assert lb["straggler_wait_units"] == pytest.approx((6.0 + 1.5) / 2)
    assert lb["max_mean_ratio"] == pytest.approx((3.0 + 2.0) / 2)
    assert lb["dest_drops_total"] == 1.0
    # vectors never leak into the scalar "moe" means
    assert "decode/rank_load" not in rep["moe"]
    assert rep["moe"]["decode/moved_units"] == 2.0


def test_metrics_scalar_only_has_no_load_balance():
    m = ServeMetrics()
    m.record_step({"moved_units": 1.0}, 2, phase="decode")
    assert "load_balance" not in m.report()


# ----------------------------------------------------------------------
# Engine integration (multi-device subprocess)
# ----------------------------------------------------------------------
def _run(body: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # the child never races for a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_serve_policies_token_identical_and_jit_stable():
    """On a 4-rank expert-parallel mesh under 0.95 router skew:

    * greedy token streams are identical across harmoeny / round_robin /
      even_split AND harmoeny + live replication (scheduling moves compute,
      never changes math) — static_opt is excluded by design: its placement
      permutes the expert->weight-row mapping, so it is a different model;
    * with replication on, at least one hot-expert swap fires, the decode
      jit cache stays at ONE entry, and nothing recompiles after warmup;
    * harmoeny redistributes: its decode max/mean rank-load ratio beats
      round_robin's under skew, and drops stay zero everywhere.
    """
    _run("""
    import numpy as np, jax
    from repro.configs.base import ModelConfig, MoEConfig, ParallelConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import MeshShape, build_model
    from repro.serve import (Request, ServeEngine, VirtualClock,
                             engine_config_for)

    def run(policy, rep_slots=0, interval=0):
        cfg = ModelConfig(
            name="tinymoe", family="moe", num_layers=2, d_model=32,
            num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
            head_dim=16, dtype="float32",
            moe=MoEConfig(num_experts=8, num_experts_per_tok=2,
                          d_ff_expert=32, policy=policy, router_skew=0.95,
                          q_tokens=1, num_foreign_slots=2,
                          num_replica_slots=rep_slots))
        mesh = make_host_mesh(1, 4)
        ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
        model = build_model(cfg, ParallelConfig(attn_chunk=8, loss_chunk=8),
                            batch=4, seq_len=16, mesh_shape=ms, mesh=mesh)
        with mesh:
            params = model.init(jax.random.PRNGKey(0))
        ecfg = engine_config_for(cfg, max_slots=4, prompt_len=8,
                                 max_new_tokens=6, prefill_chunk=4,
                                 rebalance_interval=interval,
                                 replica_slots=rep_slots)
        eng = ServeEngine(model, params, ecfg, mesh=mesh,
                          clock=VirtualClock(0.5))
        eng.warmup()
        rng = np.random.default_rng(7)
        reqs = [Request(rid=i,
                        tokens=rng.integers(1, 60, size=8).astype(np.int32),
                        max_new_tokens=6, arrival_time=0.0)
                for i in range(6)]
        return eng.run(reqs)

    reports = {}
    for name, kw in (("harmoeny", {}),
                     ("round_robin", {}),
                     ("even_split", {}),
                     ("harmoeny+rep", dict(rep_slots=1, interval=3))):
        pol = name.split("+")[0]
        reports[name] = run(pol, **kw)

    # 1. token-identical greedy streams (drops are zero in every cell)
    streams = {}
    for name, rep in reports.items():
        lb = rep["load_balance"]["decode"]
        assert lb["send_drops_total"] == 0, (name, lb)
        assert lb["dest_drops_total"] == 0, (name, lb)
        streams[name] = tuple((r["rid"], r["n_generated"])
                              for r in rep["requests"])
    base = streams["harmoeny"]
    for name, s in streams.items():
        assert s == base, f"{name} diverged from harmoeny"

    # 2. replication fired and never recompiled
    rep = reports["harmoeny+rep"]
    assert rep["engine"]["replica_swaps"] >= 1
    assert rep["engine"]["hot_experts"], "EMA found no hot expert at 0.95"
    assert rep["jit_entries"]["decode"] == 1
    assert rep["jit_entries"]["replica_swap"] == 1
    assert rep["recompiled_after_warmup"] is False

    # 3. harmoeny balances better than round_robin under heavy skew
    r_h = reports["harmoeny"]["load_balance"]["decode"]["max_mean_ratio"]
    r_rr = reports["round_robin"]["load_balance"]["decode"]["max_mean_ratio"]
    assert r_h < r_rr, (r_h, r_rr)
    print("OK", r_h, r_rr)
    """)


def test_engine_config_validation():
    from repro.serve.engine import EngineConfig
    with pytest.raises(ValueError):
        EngineConfig(moe_policy="nope")
    with pytest.raises(ValueError):
        EngineConfig(rebalance_interval=4)        # no replica slots
    with pytest.raises(ValueError):
        EngineConfig(replica_slots=-1)
    EngineConfig(moe_policy="round_robin")        # valid override
    EngineConfig(replica_slots=2, rebalance_interval=8)


def test_engine_rejects_replica_slot_mismatch():
    """The model must be BUILT with the replica slots (shapes are static);
    asking the engine for slots the parameters lack is a config error."""
    _run("""
    import jax, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig, ParallelConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import MeshShape, build_model
    from repro.serve import ServeEngine, engine_config_for

    cfg = ModelConfig(
        name="tinymoe", family="moe", num_layers=2, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
        head_dim=16, dtype="float32",
        moe=MoEConfig(num_experts=8, num_experts_per_tok=2, d_ff_expert=32,
                      policy="harmoeny", num_foreign_slots=2))
    mesh = make_host_mesh(1, 4)
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(cfg, ParallelConfig(attn_chunk=8, loss_chunk=8),
                        batch=2, seq_len=16, mesh_shape=ms, mesh=mesh)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    ecfg = engine_config_for(cfg, max_slots=2, prompt_len=8,
                             max_new_tokens=4, prefill_chunk=4,
                             replica_slots=1, rebalance_interval=2)
    try:
        ServeEngine(model, params, ecfg, mesh=mesh)
    except ValueError as e:
        assert "num_replica_slots" in str(e)
        print("OK")
    else:
        raise AssertionError("mismatched replica slots were accepted")
    """)


def test_sharded_serving_path_fused_attention():
    """The serving CLI's build path on a 4-rank mesh: parameters are
    initialised on their shards (experts over ``model``), the paged pool
    splits its kv heads the same way, the fused paged kernel runs per
    device under ``shard_map``, every jit entry compiles once, and the
    greedy streams match the reference block-table gather."""
    out = _run("""
    import jax
    from repro.launch.serve import build_parser, build_serving_engine
    from repro.serve import poisson_requests

    def run(*extra):
        args = build_parser().parse_args([
            "--arch", "qwen15-moe-a27b", "--reduced", "--model-par", "4",
            "--batch", "4", "--prompt-len", "16", "--gen", "4", "--paged",
            "--kv-block-size", "8", "--prefill-chunk", "8", "--skew", "0.9",
            "--q-tokens", "1", "--fused-moe", *extra])
        cfg, eng = build_serving_engine(args)
        outs, finish = {}, eng._finish

        def capture(st, now):
            outs[st.req.rid] = list(st.output)
            finish(st, now)
        eng._finish = capture
        eng.warmup()
        rep = eng.run(poisson_requests(4, rate=0.0, vocab_size=cfg.vocab_size,
                                       prompt_len=16, max_new_tokens=4))
        return eng, rep, outs

    eng, rep, fused = run("--fused-attention")
    leaves = jax.tree_util.tree_leaves_with_path(eng.params)
    w_in = [l for p, l in leaves if "moe" in jax.tree_util.keystr(p)
            and jax.tree_util.keystr(p).endswith("['w_in']")][0]
    assert w_in.addressable_shards[0].data.shape[-3] \\
        == w_in.shape[-3] // 4, w_in.sharding
    for p, l in jax.tree_util.tree_leaves_with_path(eng.pool):
        assert l.addressable_shards[0].data.shape[-2] == l.shape[-2] // 4
    assert all(n == 1 for n in rep["jit_entries"].values()), rep["jit_entries"]
    assert rep["recompiled_after_warmup"] is False
    assert rep["attention_fallbacks"] == {}
    assert rep["attention_dispatch"]["decode"]["fused"]
    _, rep_ref, ref = run()
    assert not rep_ref["attention_dispatch"]["decode"]["fused"]
    assert fused == ref and len(fused) == 4, (fused, ref)
    print("OK")
    """)
    assert "OK" in out
