"""Device milliseconds per traced train step of the MoE layers' token
movement: routing (``moe/route``: scores, top-k, gates, balance loss),
dispatch (``moe/dispatch``: the sort by expert, the gather into the
expert buffer, padding) and combine (``moe/combine``: each token's gated
sum of its copies), forward, backward and recomputed; any matrix product
or quantization event under them (``gemm``, ``mor_quant``) is left out.
See ``_moe.py``."""
from chipbench.metrics._moe import per_step_ms

PARTS = ("route", "dispatch", "combine")


def read(ctx):
    ms = per_step_ms(ctx)
    kept = [v for (part, inner), v in ms.items()
            if part in PARTS and not inner]
    return sum(kept) if kept else None
