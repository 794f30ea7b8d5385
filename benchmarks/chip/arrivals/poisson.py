"""Poisson arrivals at ``rate_per_s``: ``rate * seconds`` requests, all
due inside the window, their gaps the exponential's quantiles at
``(i + 0.5) / n`` in the order the seed draws."""
import math

import numpy as np

from chipbench import traffic


def generate(mix, rng, seconds, vocab):
    rate = mix["arrivals"]["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])
    # the quantile gaps sum to just under n / rate = seconds; a last
    # arrival past the window is pulled back to its close
    arrivals = np.minimum(np.cumsum(rng.permutation(gaps)),
                          seconds * (1 - 1e-6))
    return traffic.requests(mix, rng, arrivals, vocab)
