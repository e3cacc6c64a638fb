"""Mean number of slots decoding per batched decode step over the window
(decode tokens the engine produced over ``Engine.decode_steps``)."""


def read(ctx):
    return ctx.counters.get("decode_occupancy")
