"""paged_attention_roofline: the decode steps' paged attention kernel
(``kernels/paged_attention``) as a share of its roofline, in percent: the
least time the chip needs to read every live K/V position of the traced
decode steps once, with its queries and outputs, and to do their QK and
PV products (``counts.paged_attention``, every layer), over the kernel's
device time in those steps.  Memory bound."""
from chipbench.counts import paged_attention

# the kernel's device op, read from a chip trace: a Mosaic call whose
# first operands are the [rows, blocks] block table and the [rows]
# lengths and whose output is [rows, kv heads, queries, head dim] (the
# kernel carries no name of its own yet)
KERNEL = (r"= bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} custom-call\("
          r"s32\[\d+,\d+\]\{[^}]*\} %[\w.-]+, s32\[\d+\]\{.*"
          r'custom_call_target="tpu_custom_call"',)


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.op_time(KERNEL, phase="decode")
    d = run.dims
    least = sum(run.least_seconds_per_chip(paged_attention(
        call.rows, d.heads, d.kv_heads, d.head_dim)) * d.layers
        for call in run.traced_calls("decode"))
    if not spent or not least:
        return None
    return least / spent * 100.0
