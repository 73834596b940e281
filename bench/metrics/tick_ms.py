"""tick_ms: median host time of one scheduler tick of the phase, in ms.

The span is the benchmark's own, around each ``step()`` of the measured
window (admission and prefills, the tick's decode launch, and the host sync
on its tokens); ``.decode`` reads the ticks that only decoded."""
import statistics


def read(ctx, phase):
    spans = [s.t1 - s.t0 for s in ctx.steps(phase)]
    return statistics.median(spans) * 1e3 if spans else None
