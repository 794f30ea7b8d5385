"""moe_gmm_roofline: the decode steps' grouped expert FFN kernel
(``kernels/moe_gmm``) as a share of its roofline, in percent: the least
time the chip needs for the traced decode steps' expert FFN calls (per
layer: the routed rows, and the weights of the experts that received
them read once; ``counts.moe_gmm``), over the kernel's device time in
those steps.  Memory bound at decode sizes."""
import numpy as np

from chipbench.counts import moe_gmm

# the kernel's device op, read from a chip trace: a Mosaic call whose
# first operand is the [tiles] tile-to-group map and whose output is the
# [rows, d_model] buffer (the kernel carries no name of its own yet)
KERNEL = (r"= bf16\[\d+,\d+\]\{[^}]*\} custom-call\(s32\[\d+\]\{.*"
          r'custom_call_target="tpu_custom_call"',)


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.op_time(KERNEL, phase="decode")
    d = run.dims
    least = 0.0
    for call in run.traced_calls("decode"):
        if call.loads is None:
            return None
        # per layer: routed units per expert
        for row in np.asarray(call.loads, np.float64):
            least += run.least_seconds_per_chip(moe_gmm(
                int(round(row.sum())), int((row > 0).sum()), d.d_model,
                d.d_expert))
    if not spent or not least:
        return None
    return least / spent * 100.0
