"""Share of the traced window in which no operation ran on the device, simulation cells."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_ns() / ctx.trace.window_ns)
