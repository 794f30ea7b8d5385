"""The one traffic generator: reads a mix's data file, makes requests.

A mix is a JSON object::

    {"arrivals": {"process": "poisson", "rate_per_s": 0.9}
                 | {"process": "batch", "requests": 512},
     "prompt_tokens": {"dist": "lognormal", "median": 1024, "sigma": 0.9,
                       "min": 64, "max": 4096},
     "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 8, "max": 512},
     "router_skew": 0.0, "drain_seconds": 60}

The arrival process is found by name: ``arrivals/<process>.py``, a module
with ``generate(mix, rng, seconds, vocab)`` returning the requests (see
``arrivals/poisson.py``).  A new process is a new file there, and a mix
that names it; this file does not change.

``drain_seconds`` is how long the engine may go on after the window for
the requests due in it: a minute, as for any answer checked after the
window; a request still decoding then is measured on the tokens it has.

Every seed gets the same set of lengths (the distribution's quantiles at
``(i + 0.5) / n``), and a Poisson mix the same set of gaps between
arrivals (the exponential's), each drawn into a new order by the seed;
so the amount and the shape of the work stay, and which prompt meets
which output and when changes from seed to seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

from chipbench import registry


@dataclass(frozen=True)
class Spec:
    rid: int
    arrival: float
    prompt: np.ndarray
    output_len: int


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at ``(i + 0.5) / n``,
    rounded and clipped to its ``min`` and ``max``, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    q = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)


def requests(mix: Dict[str, Any], rng, arrivals: np.ndarray,
             vocab: int) -> List[Spec]:
    """One request per due time in ``arrivals`` (ascending): the mix's
    prompt and output lengths in the order ``rng`` draws, prompts of
    random token ids."""
    n = len(arrivals)
    prompts = rng.permutation(quantiles(mix["prompt_tokens"], n))
    outputs = rng.permutation(quantiles(mix["output_tokens"], n))
    return [Spec(rid=i, arrival=float(arrivals[i]),
                 prompt=rng.integers(0, vocab, int(prompts[i]),
                                     dtype=np.int64).astype(np.int32),
                 output_len=int(outputs[i]))
            for i in range(n)]


def max_lengths(mix: Dict[str, Any]) -> tuple:
    """The longest prompt and output the mix can send (the engine's
    static shapes)."""
    return mix["prompt_tokens"]["max"], mix["output_tokens"]["max"]


def generate(mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
             base: Path = registry.BENCH_DIR) -> List[Spec]:
    """The mix's requests for a window of ``seconds``, from ``seed``."""
    process = registry.module("arrivals", mix["arrivals"]["process"], base)
    return process.generate(mix, np.random.default_rng(seed), seconds, vocab)
