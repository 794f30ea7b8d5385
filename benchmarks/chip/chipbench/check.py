"""The comparison that decides ``correct``.

After the window, a sample of the requests that the timed path finished
is drawn from the seed: the one that served the most tokens, then others
in a seeded order until ``sample_tokens`` served tokens are in it.  The
configuration's reference runs over each prompt followed by its served
tokens, and for every served token reads how far its logit lies below
the reference's best at that position: 0 for a greedy token the program
computed as the reference does, the width of a near tie where rounding
tipped it.  The number compared is the mean of these gaps over the
sample; the widest one is reported beside it.  (The widest gap is set by
one near tie in some hundreds of tokens, where rounding also flips an
expert choice; it reads about as high for the reference rounded to
bfloat16 as for the program, and does not separate the program from
the control.)

The control reads the same gaps for the tokens that the reference, run
in the next precision down, puts first at each position.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass(frozen=True)
class Item:
    rid: int
    prompt: np.ndarray
    served: np.ndarray


def sample(items: List[Item], seed: int, sample_tokens: int) -> List[Item]:
    if not items:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7])
    by_len = sorted(items, key=lambda it: (-len(it.served), it.rid))
    chosen = [by_len[0]]
    rest = [by_len[i] for i in rng.permutation(np.arange(1, len(by_len)))]
    n = len(chosen[0].served)
    for it in rest:
        if n >= sample_tokens:
            break
        chosen.append(it)
        n += len(it.served)
    return chosen


def _inputs(it: Item):
    tokens = np.concatenate([it.prompt, it.served[:-1]]).astype(np.int32)
    positions = len(it.prompt) - 1 + np.arange(len(it.served))
    return tokens, positions


def gaps(reference, layout, key, dims, items: List[Item],
         control: Optional[str] = None) -> Dict[str, float]:
    """``mean_logit_gap`` and ``max_logit_gap``: the mean and the widest
    gap of a served token below the reference's best, over ``tokens``
    served tokens; with ``control`` (a precision), ``control_mean_gap``
    and ``control_max_gap``: the same for the tokens the reference
    computed in that precision puts first.  The reference reads its
    weights from ``layout`` (the configuration's, whose ``Dims`` ``dims``
    is)."""
    got, low = [], []
    for it in items:
        tokens, positions = _inputs(it)
        ref = reference.logits(layout, key, dims, tokens, positions)
        best = ref.max(axis=1)
        rows = np.arange(len(it.served))
        got.append(best - ref[rows, it.served])
        if control is not None:
            pick = reference.logits(layout, key, dims, tokens, positions,
                                    precision=control).argmax(axis=1)
            low.append(best - ref[rows, pick])
    got = np.concatenate(got) if got else np.zeros(0)
    out = {"mean_logit_gap": float(got.mean()) if got.size else 0.0,
           "max_logit_gap": float(got.max()) if got.size else 0.0,
           "tokens": int(got.size)}
    if control is not None and low:
        low = np.concatenate(low)
        out["control_mean_gap"] = float(low.mean())
        out["control_max_gap"] = float(low.max())
    return out
