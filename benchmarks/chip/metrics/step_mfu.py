"""step_mfu: model FLOPs of the tokens the traced window processed (every
prefill and decode token through the projections, router, top-k and
shared experts and causal attention, plus the output head where logits
were taken; the layout's ``Dims.token_flops``), over the traced window's
length times the chips times the chip's peak bf16 FLOP/s, in percent."""


def read(run):
    if run.trace is None:
        return None
    flops = 0.0
    for c in run.traced_calls():
        for q, ctx in c.rows:
            for i in range(q):
                flops += run.dims.token_flops(ctx - q + i + 1,
                                              logits=(i == q - 1))
    if not flops:
        return None
    return flops / (run.trace.window_s * run.chips
                    * run.peaks.bf16_flops) * 100.0
