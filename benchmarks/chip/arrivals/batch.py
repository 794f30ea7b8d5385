"""An offline batch: all ``requests`` due at the window's start."""
import numpy as np

from chipbench import traffic


def generate(mix, rng, seconds, vocab):
    n = int(mix["arrivals"]["requests"])
    return traffic.requests(mix, rng, np.zeros(n), vocab)
