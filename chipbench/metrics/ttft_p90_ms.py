"""90th percentile over the window's requests of the time from a
request's due time to its first token on the host; above the knee the
queue grows through the window, so this tail is recorded, not judged."""


def read(ctx):
    return ctx.counters.get("ttft_p90_ms")
