"""Device time of one batched decode step: the mean duration of the
engine's decode program runs in the traced window."""
from chipbench.metrics._decode import decode_runs


def read(ctx):
    runs = decode_runs(ctx)
    if not runs:
        return None
    return sum(r.dur for r in runs) / len(runs) / 1e6
