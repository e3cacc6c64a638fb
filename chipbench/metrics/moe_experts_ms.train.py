"""Device milliseconds per traced train step under the MoE layers'
``moe/experts`` scope: the routed experts' grouped products and their
MoR quantization events, and the activation between them, forward,
backward and recomputed. See ``_moe.py``."""
from chipbench.metrics._moe import per_step_ms


def read(ctx):
    kept = [v for (part, _), v in per_step_ms(ctx).items()
            if part == "experts"]
    return sum(kept) if kept else None
