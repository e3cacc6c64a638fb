"""Share of the traced window in which no operation runs on the device
(1 - union of the XLA op intervals over the window), the mean over the
chips the cell uses."""


def read(ctx):
    return ctx.trace.idle_percent()
