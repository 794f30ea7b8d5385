"""itl_p90_ms: 90th percentile of the gap between consecutive output
tokens, over every token after the first of every request due in the
window (the drain included): the pace a reader of the answer sees at
the tail, over thousands of gaps where a per-request tail has a few
dozen requests."""
from chipbench.stats import percentile


def read(run):
    xs = [g for o in run.window.requests.values() for g in o.gaps]
    return percentile(xs, 90) * 1e3 if xs else None
