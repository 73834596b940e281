"""step_mfu: model FLOPs of the tokens that the phase's ticks of the
measured window served (their prefills and decoded tokens), as the cell's
reference counts them (``prefill_flops``, ``decode_flops``), over the host
time of those ticks' ``step()`` spans times the chip's bf16 peak, in %."""


def read(ctx, phase):
    steps = ctx.steps(phase)
    busy = sum(s.t1 - s.t0 for s in steps)
    if busy <= 0:
        return None
    flops = 0.0
    for s in steps:
        flops += sum(ctx.reference.prefill_flops(ctx.model, n)
                     for n, _ in s.prefills)
        flops += sum(ctx.reference.decode_flops(ctx.model, c)
                     for c, _ in s.decodes)
    if flops <= 0:
        return None
    return 100.0 * flops / (busy * ctx.peak["bf16_flops_per_s"])
