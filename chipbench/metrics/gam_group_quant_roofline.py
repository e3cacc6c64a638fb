"""The grouped MoR fake-quantization kernels (``kernels/gam_quant.py``)
against their roofline, over the traced train steps. They quantize the
rows of an MoE layer's expert buffer that hold routed token-copies and
their padding, in two passes, and skip the buffer's empty tail: the
error pass (``gam_group_err``) reads the live rows once, the apply pass
(``gam_group_quant``) reads and writes them once. Both are memory-bound;
their least time is those bytes (``counts.fake_quant_bytes`` for the
apply pass, half of it for the error pass) over the chip's HBM
bandwidth. The live rows are not in the trace: they are the family's
expected rows of one layer's buffer (``expected_group_rows``: the copies
routed to the held experts under even routing, and each group's padding
to a whole block), with the width from the event's HLO shape. Share =
summed least time over summed device time of those events; None for a
family without experts or a trace without the kernels."""
from chipbench import counts

# Kernel name -> share of ``fake_quant_bytes`` (read + write) it moves.
KERNELS = {"gam_group_quant": 1.0, "gam_group_err": 0.5}


def _width(text: str):
    for dt, shape, _ in counts.shape_bytes(text.split("=", 1)[-1]):
        if dt == "bf16" and len(shape) == 2:
            return shape[1]
    return None


def read(ctx):
    rows_of = getattr(ctx.cell.family, "expected_group_rows", None)
    if rows_of is None:
        return None
    job = ctx.cell.traffic
    rows = rows_of(ctx.cfg, job["global_batch"] * job["seq_len"])
    least = spent = 0.0
    for ops in ctx.trace.ops.values():
        for op in ops:
            share = KERNELS.get(op.short)
            if share is None or op.dur <= 0:
                continue
            width = _width(op.text)
            if width is None:
                continue
            nbytes = share * counts.fake_quant_bytes((rows, width))
            least += counts.roofline_s(0.0, nbytes, ctx.peaks)
            spent += op.dur / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
