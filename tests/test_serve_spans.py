"""The serving engine's names: the span recorder in ``ServeMetrics``,
the named jitted entries, and the named scopes its decode step carries
into the compiled program."""
import json
import re
import time

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, ParallelConfig
from repro.launch.mesh import make_host_mesh
from repro.models.model import MeshShape, build_model
from repro.serve import (ServeEngine, VirtualClock, engine_config_for,
                         poisson_requests)
from repro.serve import engine as engine_mod
from repro.serve.kvstore import HandoffRecord
from repro.serve.metrics import ServeMetrics

TINY_MOE = ModelConfig(
    name="tiny-moe", family="moe", num_layers=2, d_model=32, num_heads=2,
    num_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16, dtype="float32",
    moe=MoEConfig(num_experts=4, num_experts_per_tok=2, d_ff_expert=32,
                  num_shared_experts=1, policy="harmoeny",
                  num_foreign_slots=1))

# the stages the decode step names (attention block, paper Algorithm 1's
# steps in the MoE block, the shared expert, the head and the sampler)
SCOPES = ("attention", "router", "schedule", "dispatch", "expert_weights",
          "expert_ffn", "combine", "shared_expert", "lm_head", "sample")


def test_span_nesting_self_time_and_counts():
    m = ServeMetrics()
    for _ in range(2):
        with m.span("serve.step", step_num=3) as outer:
            with m.span("serve.decode", rows=4) as inner:
                time.sleep(0.002)
            time.sleep(0.001)
    assert inner.seconds >= 0.002 and outer.seconds > inner.seconds
    rep = m.report()["spans"]
    assert rep["serve.step"]["count"] == 2
    assert rep["serve.decode"]["count"] == 2
    step, dec = rep["serve.step"], rep["serve.decode"]
    assert dec["self_seconds"] == pytest.approx(dec["seconds"])
    assert step["self_seconds"] == pytest.approx(
        step["seconds"] - dec["seconds"])
    assert step["self_seconds"] >= 0.002
    json.dumps(m.report(), allow_nan=False)


def test_span_closes_on_error_and_keeps_the_stack():
    m = ServeMetrics()
    with pytest.raises(ValueError):
        with m.span("serve.step"):
            with m.span("serve.admit"):
                raise ValueError("x")
    assert m._open == []
    assert m.report()["spans"]["serve.admit"]["count"] == 1
    with m.span("serve.idle"):
        pass
    assert m.report()["spans"]["serve.idle"]["self_seconds"] >= 0


def test_span_lands_in_a_profiler_trace(tmp_path):
    m = ServeMetrics()
    jax.profiler.start_trace(str(tmp_path))
    with m.span("serve.step", step_num=7):
        with m.span("serve.prefill", rid=1, tokens=16, start=0):
            jax.block_until_ready(jax.numpy.arange(4) + 1)
    jax.profiler.stop_trace()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    names = {e.name for p in jax.profiler.ProfileData.from_file(
        str(files[0])).planes for line in p.lines for e in line.events}
    assert {"serve.step", "serve.prefill"} <= names


def test_handoff_record_carries_the_prefill_start():
    rec = HandoffRecord(rid=1, prompt_tokens=np.arange(4, dtype=np.int32),
                        output=[3], pos=4, pad_len=4, prefill_chunk=4,
                        max_new_tokens=2, eos_id=None,
                        prefill_start_time=1.5)
    assert HandoffRecord.from_npz_bytes(
        rec.to_npz_bytes()).prefill_start_time == 1.5
    rec.prefill_start_time = None
    assert HandoffRecord.from_npz_bytes(
        rec.to_npz_bytes()).prefill_start_time is None


@pytest.fixture(scope="module")
def moe_engine():
    mesh = make_host_mesh(1, 1)
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(TINY_MOE, ParallelConfig(attn_chunk=8, loss_chunk=8),
                        batch=2, seq_len=8, mesh_shape=ms, mesh=mesh)
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    ecfg = engine_config_for(TINY_MOE, max_slots=2, prompt_len=8,
                             max_new_tokens=3, prefill_chunk=4, paged=True,
                             kv_block_size=4, prefix_sharing=True)
    return ServeEngine(model, params, ecfg, mesh=mesh,
                       clock=VirtualClock(0.5))


def test_jitted_entries_are_named(moe_engine):
    eng = moe_engine
    names = {"prefill_chunk": eng.core.prefill_fn.__name__,
             "decode": eng.core.decode_fn.__name__,
             "write": eng.kv.write_fn.__name__,
             "gather": eng.kv.gather_fn.__name__,
             "copy": eng.kv.copy_fn.__name__,
             "swap": jax.jit(engine_mod.swap_replicas).__name__,
             "stage": jax.jit(engine_mod.stage_weights).__name__}
    assert names == {"prefill_chunk": "prefill_chunk",
                     "decode": "decode_step", "write": "pool_write",
                     "gather": "prefix_gather", "copy": "block_copy",
                     "swap": "swap_replicas", "stage": "stage_weights"}


def test_decode_step_carries_every_stage_scope(moe_engine):
    eng = moe_engine
    B = eng.ecfg.max_slots
    bt = np.zeros_like(eng.block_table)
    with eng._ctx():
        text = eng.core.decode_fn.lower(
            eng.params, eng.tok[:, None], eng.pool, eng.pos, bt, None,
            np.ones((B,), bool), eng._replica_ids,
            eng._residency_ids).as_text(debug_info=True)
    assert "jit(decode_step)" in text
    # op locations name their scope path: "jit(decode_step)/.../<scope>/
    # <op>", or "<scope>/<op>" inside the layer loop's body
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    missing = [s for s in SCOPES
               if not any(re.search(rf"(^|/){s}(/|$)", p) for p in paths)]
    assert not missing, missing


def test_served_window_reports_every_step_span(moe_engine):
    eng = moe_engine
    eng.warmup()
    eng.reset_metrics()
    rep = eng.run(poisson_requests(3, rate=0.0, vocab_size=64, prompt_len=8,
                                   max_new_tokens=3, seed=2))
    assert {"serve.step", "serve.admit", "serve.prefill",
            "serve.first_token", "serve.ensure_blocks", "serve.decode",
            "serve.commit"} <= set(rep["spans"])
    assert rep["recompiled_after_warmup"] is False
