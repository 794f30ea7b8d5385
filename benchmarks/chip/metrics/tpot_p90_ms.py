"""tpot_p90_ms: 90th percentile, over the requests due in the window, of
each request's mean gap between output tokens (its first to its latest
token, over the tokens after the first).  A request still decoding when
the drain ended contributes the tokens it had; one with no first token
counts as missing (infinitely late)."""
import math

from chipbench.stats import percentile


def read(run):
    xs = []
    for o in run.window.requests.values():
        if o.first is None:
            xs.append(math.inf)
        elif o.tpot is not None:
            xs.append(o.tpot)
    return percentile(xs, 90) * 1e3 if xs else None
