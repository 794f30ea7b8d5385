"""Operations and bytes that one kernel call needs, from shapes and token
counts (the yardstick of the roofline metrics; the FLOPs of one token,
which ``step_mfu`` reads, are the layout's ``Dims.token_flops``).

Counts are of the algorithm, not of what a kernel happens to do: rows are
real routed rows (no tile padding), weights are those of the experts that
received rows, attention reads each live K/V position once.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_seconds(self, flops_per_s: float, bytes_per_s: float
                      ) -> Tuple[float, str]:
        """The roofline's least time and the bound that sets it."""
        tc, tb = self.flops / flops_per_s, self.bytes / bytes_per_s
        return (tc, "compute") if tc >= tb else (tb, "memory")


def moe_gmm(rows: int, experts_hit: int, d: int, f: int, *,
            gated: bool = True, itemsize: int = 2) -> Work:
    """One grouped expert FFN call: ``rows`` routed rows through their
    experts (``x @ w_in``, ``x @ w_gate``, ``h @ w_out`` when gated), the
    weights of ``experts_hit`` experts read once, rows read and written."""
    n_mat = 3 if gated else 2
    return Work(flops=2.0 * rows * d * f * n_mat,
                bytes=(experts_hit * n_mat * d * f + 2 * rows * d)
                * itemsize)


def paged_attention(queries: Iterable[Tuple[int, int]], heads: int,
                    kv_heads: int, head_dim: int, *,
                    itemsize: int = 2) -> Work:
    """One attention call over rows given as (queries, context): a row's
    ``q`` queries are the last ``q`` of ``context`` positions, each
    attending causally (query i sees ``context - q + i + 1`` keys).  Each
    live K and V position is read once; queries read and outputs
    written."""
    flops = 0.0
    nbytes = 0.0
    for q, ctx in queries:
        keys = q * (ctx - q) + q * (q + 1) / 2        # causal key count
        flops += 4.0 * heads * head_dim * keys       # QK^T and PV
        nbytes += (2 * ctx * kv_heads * head_dim
                   + 2 * q * heads * head_dim) * itemsize
    return Work(flops, nbytes)
