"""device_idle: share of the phase's traced ticks in which no operation ran
on the device, in %: 1 - (union of device-op intervals / the ticks' time).
A tick's time runs from the start of its ``step()`` to the next one's."""


def read(ctx, phase):
    dev = ctx.device(phase)
    if dev is None or dev.period_s <= 0:
        return None
    return 100.0 * (1.0 - dev.busy_s / dev.period_s)
