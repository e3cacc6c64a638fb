"""The engine's decode-step runs in a trace (shared by the decode
readers; not a metric). The engine compiles one step program per token
block: (slots, 1) for decode, (1, chunk) for a prompt chunk. A decode
run is a run of that program whose mixed GEMMs take ``slots`` rows."""

GEMM = "mixed_gemm_blocks"


def decode_runs(ctx):
    rows = ctx.cell.traffic["engine"]["slots"]
    head = f"bf16[{rows},"
    return ctx.trace.runs_of(
        "step_fn",
        lambda o: o.short == GEMM and o.kind.split(" ", 1)[-1]
        .startswith(head))
