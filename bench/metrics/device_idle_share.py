"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips (%)."""


def read(ctx):
    devs = ctx.trace.devices[:ctx.chips]
    if len(devs) < ctx.chips or ctx.trace.window_s() <= 0:
        return None
    busy = sum(ctx.trace.busy_s(d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / ctx.trace.window_s())
