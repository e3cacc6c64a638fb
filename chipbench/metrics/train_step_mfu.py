"""Whole training step's share of the chips' bf16 peak: the forward and
backward operations of every matrix product and of causal attention
(the family's ``train_step_flops``; the embedding lookup and remat
recomputation not counted) times the steps of the traced run's window,
over its wall time, chips and peak."""


def read(ctx):
    c = ctx.counters
    if not c.get("steps") or not c.get("window_s"):
        return None
    rate = c["step_flops"] * c["steps"] / c["window_s"]
    return 100.0 * rate / (ctx.chips * ctx.peaks["peak_flops_bf16"])
