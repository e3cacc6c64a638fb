"""The mixed-representation GEMM kernel (``kernels/mixed_gemm.py``,
events ``mixed_gemm_blocks.N``) inside the decode program's runs,
against its roofline: 2*m*k*n operations, and the payload, tag and
scale, activation and output bytes of ``counts.mixed_gemm_bytes``, with
(m, n) from the event's result and k from its one-byte payload operand.
Share = summed least time over summed device time of those events."""
from chipbench import counts
from chipbench.metrics._decode import GEMM, decode_runs


def _mkn(text):
    head, _, rest = text.partition(" = ")
    out = counts.shape_bytes(rest.split(" custom-call(", 1)[0])
    ops = counts.shape_bytes(rest.split(" custom-call(", 1)[-1])
    res = [s for dt, s, _ in out if dt == "bf16" and len(s) == 2]
    pay = [s for dt, s, _ in ops if dt == "u8" and len(s) == 2]
    if not res or not pay:
        return None
    m, n = res[0]
    pn, k = max(pay, key=lambda s: s[0] * s[1])
    return (m, k, n) if pn == n else None


def read(ctx):
    least = spent = 0.0
    for run in decode_runs(ctx):
        for op in ctx.trace.ops_within(run):
            mkn = _mkn(op.text) if op.short == GEMM else None
            if mkn is None or op.dur <= 0:
                continue
            m, k, n = mkn
            least += counts.roofline_s(
                2.0 * m * k * n, counts.mixed_gemm_bytes(m, k, n), ctx.peaks)
            spent += op.dur / 1e9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
