"""90th percentile over the window's requests of the time from a
request's due time until the engine took it off its queue (observed
after each ``Engine.step``)."""


def read(ctx):
    return ctx.counters.get("admit_wait_p90_ms")
