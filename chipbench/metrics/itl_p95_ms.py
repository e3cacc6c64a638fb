"""95th percentile of every gap between consecutive output tokens of the
window's requests, as the host sees them after each ``Engine.step``."""


def read(ctx):
    return ctx.counters.get("itl_p95_ms")
