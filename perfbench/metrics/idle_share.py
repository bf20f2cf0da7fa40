"""Device idle share of the traced window: 1 - the union of device-op
intervals over the window, averaged over the chips used, in %."""


def read(ctx):
    if not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
