"""Share of the chip's bf16 peak that a decode step's useful work
reaches: the matrix products of the slots that decode and attention over
the cache lengths they read (the family's ``decode_step_flops``, the
mean over the decode steps the traced window launched), over the mean
device time of the decode program's runs."""
from chipbench.metrics._decode import decode_runs


def read(ctx):
    runs = decode_runs(ctx)
    lens = [c[1] for c in ctx.counters.get("traced_calls", [])
            if c[0] == "decode"]
    if not runs or not lens:
        return None
    count = ctx.cell.family.decode_step_flops
    flops = sum(count(ctx.cfg, l) for l in lens) / len(lens)
    spent = sum(r.dur for r in runs) / len(runs) / 1e9
    return 100.0 * flops / spent / ctx.peaks["peak_flops_bf16"]
