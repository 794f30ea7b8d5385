"""The chip benchmark's yardstick without a chip: trace reduction on a
synthetic trace, operation and byte counts against hand-worked values
at Qwen1.5-MoE widths, the traffic generator, percentiles, discovery."""
from __future__ import annotations

import json
import math

import numpy as np
import pytest
from jax.profiler import ProfileData

import bench_tiny  # noqa: F401  (puts benchmarks/chip on the path)

from chipbench import client, counts, registry, stats, system, traffic, xtrace
from chipbench.peaks import peaks_of
from chipbench.record import Run

QWEN_L6 = registry.module("layouts", "qwen2_moe").Dims(
    layers=6, d_model=2048, heads=16, kv_heads=16, head_dim=128, experts=60,
    top_k=4, d_expert=1408, d_shared=5632, vocab=151936, rope_theta=1e6,
    norm_eps=1e-6)


# device op names as a v5e trace spells them (the decode-step kernels of
# qwen15-L6.chat: paged attention over a [32, 288] block table, and the
# grouped expert FFN over a [66]-tile map)
PAGED = ('%closed_call.54 = bf16[32,16,1,128]{3,2,1,0:T(2,128)(2,1)S(1)} '
         'custom-call(s32[32,288]{1,0:T(8,128)S(1)} %copy-done.1, '
         's32[32]{0:T(128)S(1)} %get-tuple-element.3161, bf16[32,16,1,128]'
         '{3,2,1,0:T(2,128)(2,1)S(1)} %pad_maximum_fusion.8), '
         'custom_call_target="tpu_custom_call", '
         'frontend_attributes={kernel_metadata={}}')
GMM = ('%closed_call.55 = bf16[8448,2048]{1,0:T(8,128)(2,1)S(1)} '
       'custom-call(s32[66]{0:T(128)S(1)} %copy-done.28, bf16[8448,2048]'
       '{1,0:T(8,128)(2,1)} %fusion.396, bf16[64,2048,1408]{2,1,0:T(8,128)'
       '(2,1)} %pad_maximum_fusion.9), custom_call_target="tpu_custom_call"'
       ', frontend_attributes={kernel_metadata={}}')
FUSION = ('%fusion.3 = bf16[256,5632]{1,0:T(8,128)(2,1)} fusion(bf16[256,'
          '2048]{1,0} %custom-call.93), kind=kOutput, calls=%fused.46')
LOOP = '%while.195 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1)'


def _plane(pid, name, line, events, names):
    evs = "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * 1000)} "
        f"duration_ps: {int(d * 1000)} }}\n" for m, s, d in events)
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: {json.dumps(n)} }} }}\n" for i, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
            f'{meta}}}\n')


def _synthetic_trace():
    """Two chips over a 100 ns window.  Chip 0: the attention kernel
    [10, 30) overlapping a fusion [20, 40), the expert kernel [60, 70),
    all inside a loop [5, 75) that does no work of its own; chip 1: one
    fusion [0, 50).  Host: the window [0, 100), a decode dispatch at 5, a
    prefill dispatch at 55, and a step span [0, 90)."""
    names = {1: PAGED, 2: FUSION, 3: GMM, 4: LOOP}
    tpu0 = _plane(1, "/device:TPU:0", "XLA Ops",
                  [(4, 5, 70), (1, 10, 20), (2, 20, 20), (3, 60, 10)], names)
    tpu1 = _plane(2, "/device:TPU:1", "XLA Ops", [(2, 0, 50)], names)
    host = _plane(3, "/host:CPU", "python",
                  [(1, 0, 100), (2, 5, 2), (3, 55, 2), (4, 0, 90)],
                  {1: "bench.window", 2: "bench.decode",
                   3: "bench.prefill", 4: "bench.step"})
    return xtrace.read(ProfileData.from_text_proto(tpu0 + tpu1 + host))


def test_trace_reduction():
    tr = _synthetic_trace()
    assert sorted(tr.ops) == [0, 1]
    win = [s for s in tr.spans if s.name == "bench.window"][0]
    red = xtrace.reduce(tr, win.start, win.end)
    assert red.chips == 2
    assert red.window_s == pytest.approx(100e-9)
    # chip 0 busy [10, 40) + [60, 70) = 40 ns (the loop is no work of its
    # own); chip 1 50 ns; mean 45
    assert red.busy_s == pytest.approx(45e-9)
    assert red.idle_share == pytest.approx(0.55)
    # by name, mean over chips; the loop is left out
    assert red.op_seconds[PAGED] == pytest.approx(10e-9)
    assert red.op_seconds[FUSION] == pytest.approx(35e-9)
    assert LOOP not in red.op_seconds
    # the metrics' own kernel patterns, by phase
    attn = registry.module("metrics", "paged_attention_roofline").KERNEL
    gmm = registry.module("metrics", "moe_gmm_roofline").KERNEL
    assert red.op_time(attn, phase="decode") == pytest.approx(10e-9)
    assert red.op_time(attn, phase="prefill") == 0.0
    assert red.op_time(gmm, phase="prefill") == pytest.approx(5e-9)
    assert red.op_time(gmm, phase="decode") == 0.0
    assert red.op_time(("fusion",), phase="none") == pytest.approx(25e-9)
    # idle: chip 0 [0,10) [40,60) [70,100), chip 1 [50,100), each named
    # by the innermost span at its midpoint: [0,10) by the decode
    # dispatch [5,7), the rest by the step [0,90)
    assert red.idle_by_span["bench.decode"] == pytest.approx(5e-9)
    assert red.idle_by_span["bench.step"] == pytest.approx(50e-9)
    bd = xtrace.breakdown(red)
    assert bd["device_ops"] == [
        ["fusion", pytest.approx(35e-9)],
        ["kernel bf16[32,16,1,128]", pytest.approx(10e-9)],
        ["kernel bf16[8448,2048]", pytest.approx(5e-9)]]
    assert bd["idle_gaps"][0][0] == "bench.step"


def test_roofline_readers_on_a_traced_decode_step():
    """One traced decode step of a 1-layer model: two rows at context
    1000, each routing to 2 of its 4 experts; its kernels took 1 ms."""
    dims = QWEN_L6.__class__(**{**QWEN_L6.__dict__, "layers": 1})
    loads = np.array([[2.0, 1.0, 1.0, 0.0] + [0.0] * 56])
    call = system.Call("decode", 0.0, [(1, 1000), (1, 1000)], True, loads)
    red = xtrace.Reduction(
        window_s=0.01, chips=1, busy_s=0.005, op_seconds={},
        phase_op_seconds={("decode", GMM): 1e-3, ("decode", PAGED): 1e-3,
                          ("prefill", GMM): 5.0},
        idle_by_span={})
    run = Run(window=client.Window(seconds=1.0), calls=[call], dims=dims,
              config={}, mix={}, peaks=peaks_of("TPU v5 lite"), chips=1,
              setup_s=0.0, trace=red)
    gmm = registry.module("metrics", "moe_gmm_roofline").read(run)
    # 4 routed rows, 3 experts hit: bytes bound
    want = (3 * 3 * 2048 * 1408 + 2 * 4 * 2048) * 2 / 819e9 / 1e-3 * 100
    assert gmm == pytest.approx(want)
    attn = registry.module("metrics", "paged_attention_roofline").read(run)
    want = 2 * (2 * 1000 * 16 * 128 + 2 * 16 * 128) * 2 / 819e9 / 1e-3 * 100
    assert attn == pytest.approx(want)
    run.trace = None
    assert registry.module("metrics", "moe_gmm_roofline").read(run) is None


def test_union_of_intervals():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_moe_gmm_counts_at_qwen_widths():
    # 32 decode rows x top-4 = 128 routed rows, 53 experts hit
    w = counts.moe_gmm(128, 53, 2048, 1408)
    assert w.flops == 2 * 128 * 2048 * 1408 * 3 == 2_214_592_512
    assert w.bytes == (53 * 3 * 2048 * 1408 + 2 * 128 * 2048) * 2 \
        == 918_028_288
    t, bound = w.least_seconds(197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(918_028_288 / 819e9)


def test_paged_attention_counts_at_qwen_widths():
    # one decode row at context 1000: 1000 keys, K and V read once
    w = counts.paged_attention([(1, 1000)], 16, 16, 128)
    assert w.flops == 4 * 16 * 128 * 1000 == 8_192_000
    assert w.bytes == (2 * 1000 * 16 * 128 + 2 * 16 * 128) * 2 == 8_200_192
    # a 256-token chunk at offset 256: 256 * 256 + 256 * 257 / 2 keys
    w = counts.paged_attention([(256, 512)], 16, 16, 128)
    assert w.flops == 4 * 16 * 128 * 98_432 == 806_354_944


def test_token_flops_at_qwen_widths():
    per_layer = (2048 * 2048 * 2 + 2048 * 2048 * 2 + 2048 * 60
                 + 4 * 3 * 2048 * 1408 + 3 * 2048 * 5632)
    assert per_layer == 86_106_112
    base = 2 * per_layer * 6 + 4 * 16 * 128 * 1000 * 6
    assert QWEN_L6.token_flops(1000, logits=False) == base \
        == 1_082_425_344
    assert QWEN_L6.token_flops(1000, logits=True) == \
        base + 2 * 2048 * 151936


def test_peaks_table():
    assert peaks_of("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks_of("TPU v9 imaginary")


MIX = {"arrivals": {"process": "poisson", "rate_per_s": 2.0},
       "prompt_tokens": {"dist": "lognormal", "median": 1024, "sigma": 0.9,
                         "min": 64, "max": 4096},
       "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                         "min": 8, "max": 512}}


def test_traffic_is_deterministic_and_clipped():
    a = traffic.generate(MIX, 2 ** 35 + 1, 30.0, 151936)
    b = traffic.generate(MIX, 2 ** 35 + 1, 30.0, 151936)
    c = traffic.generate(MIX, 7, 30.0, 151936)
    assert len(a) == 60
    assert [(s.arrival, s.output_len, s.prompt.tolist()) for s in a] == \
        [(s.arrival, s.output_len, s.prompt.tolist()) for s in b]
    for s in a:
        assert 64 <= len(s.prompt) <= 4096 and 8 <= s.output_len <= 512
        assert 0 < s.arrival < 30.0
        assert s.prompt.min() >= 0 and s.prompt.max() < 151936
    arr = [s.arrival for s in a]
    assert arr == sorted(arr)
    # another seed: the same lengths and gaps, drawn into a new order
    pa, pc = [len(s.prompt) for s in a], [len(s.prompt) for s in c]
    assert sorted(pa) == sorted(pc) and pa != pc
    assert sorted(s.output_len for s in a) == sorted(s.output_len for s in c)
    ga, gc = np.diff([0.0] + arr), np.diff([0.0] + [s.arrival for s in c])
    assert sorted(ga) == pytest.approx(sorted(gc)) and ga.tolist() != gc.tolist()
    # not a local reshuffle of one arrangement: somewhere a prompt moves
    # far from where the first seed put it
    rank = {v: i for i, v in enumerate(sorted(set(pa)))}
    assert max(abs(rank[x] - rank[y]) for x, y in zip(pa, pc)) > 10
    assert np.median([len(s.prompt) for s in a]) == pytest.approx(1024,
                                                                  rel=0.05)


def test_batch_traffic_is_due_at_once():
    mix = dict(MIX, arrivals={"process": "batch", "requests": 40})
    specs = traffic.generate(mix, 3, 45.0, 1000)
    assert len(specs) == 40 and all(s.arrival == 0.0 for s in specs)


def test_percentile():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(range(11), 90) == pytest.approx(9.0)
    assert stats.percentile([1.0] * 19 + [math.inf], 90) == 1.0
    assert stats.percentile([1.0] * 9 + [math.inf], 90) == math.inf
    assert np.percentile([3, 1, 7, 5], 90) == \
        pytest.approx(stats.percentile([3, 1, 7, 5], 90))


def test_new_items_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a metric added as files, with a
    BENCHMARK.json entry, are found without editing any other file."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"hidden_size": 64}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"arrivals": {"process": "batch", "requests": 3}}))
    (tmp_path / "metrics" / "queue_ms.x.py").write_text(
        "def read(run):\n    return run * 2\n")
    bench = {"workloads": [{"name": "new.burst", "config": "new-model",
                            "traffic": "burst", "chips": 1, "why": "w"}],
             "end_to_end": [],
             "per_layer": [{"name": "queue_ms.x", "unit": "ms",
                            "workloads": ["new.burst"]},
                           {"name": "other", "unit": "ms",
                            "workloads": ["elsewhere"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = registry.benchmark(tmp_path)
    cell = registry.workload(b, "new.burst")
    assert registry.load_json("configs", cell["config"],
                              tmp_path)["hidden_size"] == 64
    assert registry.load_json("traffic", cell["traffic"],
                              tmp_path)["arrivals"]["requests"] == 3
    found = registry.metrics_for(b, "new.burst", traced=True)
    assert [m["name"] for m in found] == ["queue_ms.x"]
    assert registry.module("metrics", "queue_ms.x", tmp_path).read(21) == 42


def test_new_arrival_process_is_found_by_name(tmp_path):
    """A mix that names an arrival process added as a file under
    ``arrivals/`` is generated by it, with no other file edited."""
    (tmp_path / "arrivals").mkdir()
    (tmp_path / "arrivals" / "bursts.py").write_text(
        "import numpy as np\n"
        "from chipbench import traffic\n"
        "def generate(mix, rng, seconds, vocab):\n"
        "    a = mix['arrivals']\n"
        "    t = np.repeat(np.arange(a['bursts']) * seconds / a['bursts'],\n"
        "                  a['size'])\n"
        "    return traffic.requests(mix, rng, t, vocab)\n")
    mix = dict(MIX, arrivals={"process": "bursts", "bursts": 3, "size": 4})
    specs = traffic.generate(mix, 5, 30.0, 1000, base=tmp_path)
    assert [s.arrival for s in specs] == [0.0] * 4 + [10.0] * 4 + [20.0] * 4
    assert all(64 <= len(s.prompt) <= 4096 for s in specs)
    with pytest.raises(FileNotFoundError):
        traffic.generate(dict(MIX, arrivals={"process": "none"}), 5, 30.0,
                         1000, base=tmp_path)


def test_token_gaps_sum_to_the_request_s_decode_time():
    """The client's per-token gaps: the first token is the engine's stamp,
    a step that made two tokens splits its time, and the gaps of a
    request add up to its first-to-last time (so their mean is its
    TPOT)."""
    o = client.Obs(rid=0, arrival=0.0)
    o.served(1, 0.5, 0.6)
    o.served(2, 0.5, 0.9)
    o.served(4, 0.5, 1.5)
    o.served(4, 0.5, 1.7)          # nothing new in this step
    assert o.gaps == pytest.approx([0.4, 0.3, 0.3])
    assert (o.first, o.last, o.n) == (0.5, 1.5, 4)
    assert sum(o.gaps) / len(o.gaps) == pytest.approx(o.tpot)
    run = Run(window=client.Window(seconds=1.0, requests={0: o}), calls=[],
              dims=QWEN_L6, config={}, mix={}, peaks=None, chips=1,
              setup_s=0.0)
    itl = registry.module("metrics", "itl_p90_ms").read(run)
    assert itl == pytest.approx(np.percentile([400, 300, 300], 90))


def test_long_idle_gap_names_the_host_event_in_it():
    """A gap of at least the long-gap length is named by the innermost
    host event of the spans' thread around its midpoint."""
    names = {1: FUSION}
    tpu = _plane(1, "/device:TPU:0", "XLA Ops", [(1, 0, 10), (1, 60, 40)],
                 names)
    host = _plane(2, "/host:CPU", "python",
                  [(1, 0, 100), (2, 0, 90), (3, 20, 30), (4, 25, 15)],
                  {1: "bench.window", 2: "bench.step",
                   3: "TransferFromDevice", 4: "Wait"})
    other = _plane(3, "/host:CPU", "runtime", [(1, 30, 1)], {1: "Other"})
    tr = xtrace.read(ProfileData.from_text_proto(tpu + host + other))
    assert [e.name for e in tr.host] == ["TransferFromDevice", "Wait"]
    red = xtrace.reduce(tr, 0, 100, long_gap_s=30e-9)
    # idle [10, 60): midpoint 35, inside the wait [25, 40)
    assert red.idle_by_span == {"bench.step > Wait": pytest.approx(50e-9)}
    red = xtrace.reduce(tr, 0, 100)
    assert red.idle_by_span == {"bench.step": pytest.approx(50e-9)}


def test_the_benchmark_names_files_that_exist():
    b = registry.benchmark()
    for cell in b["workloads"]:
        cfg = registry.load_json("configs", cell["config"])
        registry.load_json("traffic", cell["traffic"])
        layout = registry.layout(cell["config"], cfg)
        for name in ("Dims", "FROM_FILE", "from_config", "from_program",
                     "program_config", "fill_program_tree"):
            assert hasattr(layout, name), name
        assert hasattr(registry.module("reference", cfg["reference"]),
                       "logits")
        for traced in (False, True):
            for m in registry.metrics_for(b, cell["name"], traced):
                assert hasattr(registry.module("metrics", m["name"]), "read")
