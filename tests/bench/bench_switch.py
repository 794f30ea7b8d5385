"""A second tiny benchmark tree, for a model shaped unlike Qwen1.5-MoE: the
program's Switch Transformer at its reduced size (``switch128 --reduced``:
d_model 128, dense and MoE layers alternating, 8 experts, top-1, ungated
GELU FFNs, no shared expert, tied embeddings; served in bfloat16).

Its layout and its float32 reference are files of this tree alone
(``tests/bench/switch/``); the harness, the metrics and the arrival
processes are the benchmark's.  So a model of another shape goes into
the benchmark as files, with no edit of the harness.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

import bench_tiny

HERE = Path(__file__).resolve().parent / "switch"

CONFIG = {
    "source": "the program's switch128 --reduced (Switch Transformer, "
              "arXiv:2101.03961, at the program's test size)",
    "model_type": "switch_transformers",
    "d_model": 128, "d_kv": 32, "num_heads": 4, "d_ff": 256,
    "num_layers": 4, "num_experts": 8, "decoder_sparse_step": 2,
    "vocab_size": 512, "layer_norm_epsilon": 1e-06,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    # the program's reduced experts are narrower than its dense FFN, and
    # it places tokens by RoPE
    "expert_d_ff": 64, "rope_theta": 10000.0,
    "departures": [
        "RoPE on q and k in place of T5's relative attention bias",
        "ungated GELU (tanh form) FFNs in place of ReLU",
        "RMSNorm scaled by 1 + s; a decoder only, no encoder",
    ],
    "program": {"arch": "switch128", "reduced": True},
    "layout": "switch_transformers",
    "reference": "switch128",
    "deployment": bench_tiny.CONFIG["deployment"],
    # every served token is compared.  Top-1 routing turns a rounding into
    # another expert for a token, so the bf16 program's gaps are heavy-
    # tailed: on the CPU it read a mean gap of 0.0002 to 0.0096 over 12
    # seeds (about 290 tokens each), the fp8 control 0.021 to 0.042 over
    # 12 seeds of 144 greedy tokens; the limit lies between, with room on
    # both sides
    "check": {"sample_tokens": 400, "mean_logit_gap": 0.015,
              "control": "fp8"},
}
# the tiny mix with longer outputs (16-32 tokens): more served tokens a
# run steady the mean gap of a top-1 model
MIX = {**bench_tiny.MIX,
       "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                         "min": 16, "max": 32}}
CELL = "switch.chat"
SERVED = 24                 # greedy tokens a control request is read on


def write_tree(root: Path) -> Path:
    """configs/, traffic/ and BENCHMARK.json under ``root``; layouts/ and
    reference/ linked to this tree's, metrics/ and arrivals/ to the
    benchmark's."""
    for kind in ("configs", "traffic"):
        (root / kind).mkdir(parents=True, exist_ok=True)
    (root / "configs" / "switch.json").write_text(json.dumps(CONFIG))
    (root / "traffic" / "switch.json").write_text(json.dumps(MIX))
    for kind in ("metrics", "arrivals"):
        os.symlink(bench_tiny.BENCH / kind, root / kind)
    for kind in ("layouts", "reference"):
        os.symlink(HERE / kind, root / kind)
    (root / "BENCHMARK.json").write_text(
        json.dumps(bench_tiny.benchmark(CELL, "switch", "switch")))
    return root
