"""A tiny benchmark tree for the harness tests: the reduced Qwen1.5-MoE
(d_model 128, 8 experts, top-2, served in bfloat16) as a configuration, a short
Poisson mix, and a BENCHMARK.json naming them, written into a directory;
the cell's metrics, arrival processes, layout and reference are the real
ones."""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CONFIG = {
    "source": "reduced Qwen1.5-MoE of the program's tests",
    "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
    "num_experts": 8, "num_experts_per_tok": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "vocab_size": 512, "torch_dtype": "bfloat16",
    "program": {"arch": "qwen15-moe-a27b", "reduced": True},
    "layout": "qwen2_moe",
    "reference": "qwen15_moe",
    "deployment": {"chips": 1, "model_par": 1, "max_slots": 4,
                   "prefill_chunk": 16, "kv_block_size": 8, "kv_blocks": 40,
                   "moe_policy": "harmoeny", "fused_attention": True,
                   "fused_moe": True},
    # on the CPU at this size the program reads a mean gap of 0 to 0.005
    # and the fp8 control 0.030 to 0.037 (four seeds each)
    "check": {"sample_tokens": 24, "mean_logit_gap": 0.012,
              "control": "fp8"},
}
MIX = {
    "arrivals": {"process": "poisson", "rate_per_s": 3.0},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 4, "max": 40},
    "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 10},
    "router_skew": 0.0, "drain_seconds": 120,
}
CELL = "tiny.chat"


def benchmark(cell: str, config: str, traffic: str) -> dict:
    """A BENCHMARK.json of one one-chip cell with the chat cell's
    end-to-end metrics and one per-layer metric."""
    return {
        "workloads": [{"name": cell, "config": config, "traffic": traffic,
                       "chips": 1, "why": "tests"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("itl_p90_ms", "ms"), ("tpot_p90_ms", "ms"),
                         ("setup_s", "s"))],
        "per_layer": [
            {"name": "host_gap_ms", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "engine step loop",
             "moves": "itl_p90_ms"}],
    }


def write_tree(root: Path, dtype: str = "bfloat16") -> Path:
    """configs/, traffic/, metrics/, arrivals/, layouts/ and reference/
    under ``root`` (the last four linked to the benchmark's own) and
    BENCHMARK.json; the model is served in ``dtype``."""
    for kind in ("configs", "traffic"):
        (root / kind).mkdir(parents=True, exist_ok=True)
    (root / "configs" / "tiny.json").write_text(
        json.dumps({**CONFIG, "torch_dtype": dtype}))
    (root / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    for kind in ("metrics", "arrivals", "layouts", "reference"):
        os.symlink(BENCH / kind, root / kind)
    (root / "BENCHMARK.json").write_text(
        json.dumps(benchmark(CELL, "tiny", "tiny")))
    return root
