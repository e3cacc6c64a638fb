"""The MoR fake-quantization kernel (``kernels/gam_quant.py``) against
its roofline, over the traced train steps. The kernel is memory-bound:
its least time is the bytes of reading each event's operand and writing
the fake-quantized operand once (``counts.fake_quant_bytes``, from the
operand shape in the event's HLO text) over the chip's HBM bandwidth.
Share = summed least time over summed device time of those events."""
from chipbench import counts

MARK = "gam_quant"


def _operand_shape(text: str):
    for dt, shape, _ in counts.shape_bytes(text.split("=", 1)[-1]):
        if dt == "bf16" and len(shape) == 2:
            return shape
    return None


def read(ctx):
    least = spent = 0.0
    for ops in ctx.trace.ops.values():
        for op in ops:
            if MARK not in op.name and MARK not in op.text:
                continue
            shape = _operand_shape(op.text)
            if shape is None or op.dur <= 0:
                continue
            least += counts.roofline_s(0.0, counts.fake_quant_bytes(shape),
                                       ctx.peaks)
            spent += op.dur / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
