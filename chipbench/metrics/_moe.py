"""Device time per part of the MoE layer, from the program's
``moe/<part>`` scopes (shared by the ``moe_*`` readers; not a metric).

``_scopes.per_step_ms`` gives each op the deepest layer it knows; the
MoE layer's parts (``moe/route``, ``moe/dispatch``, ``moe/experts``,
``moe/shared``, ``moe/combine``) are not among those layers, so this
reads the same trace and ``tf_op`` names with the same path rule and
sorts each op by the part of the MoE layer it runs under, and by
whether a matrix product or a quantization event (``gemm``,
``mor_quant``) lies under that part.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

from chipbench import trace as T
from chipbench.metrics._scopes import (
    SKIP,
    STEP_SPAN,
    _segments,
    _tf_ops_by_device,
    trace_file,
)

PARTS = ("route", "dispatch", "experts", "shared", "combine")
INNER = ("gemm", "mor_quant")


def part_of(tf_op: str) -> Optional[Tuple[str, bool]]:
    """(MoE part, whether a product or quantization event lies under it)
    of an instruction's ``tf_op``, or None outside the MoE parts."""
    path = tf_op.split(";", 1)[0]
    head, _, last = path.rpartition("/")
    path = f"{head}/{last.split(':', 1)[0]}" if head else last
    segs = [s for s in _segments(path) if s not in SKIP]
    for i in range(len(segs) - 1):
        if segs[i] == "moe" and segs[i + 1] in PARTS:
            return segs[i + 1], any(s in INNER for s in segs[i + 2:])
    return None


def per_step_ms(ctx) -> Dict[Tuple[str, bool], float]:
    """Device milliseconds per traced train step of each (part, inner)
    of the MoE layers, the mean over the chips the cell uses. Empty where
    the trace holds no step span or no op runs under an MoE part."""
    path = trace_file(ctx.cell.name)
    steps = sum(1 for s in ctx.trace.spans if s.name == STEP_SPAN)
    if path is None or steps == 0 or not ctx.trace.ops:
        return {}
    tf_ops = _tf_ops_by_device(path, os.path.getmtime(path))
    t0, t1 = ctx.trace.t0, ctx.trace.t1
    ns: Dict[Tuple[str, bool], int] = {}
    for dev, evs in ctx.trace.ops.items():
        names = tf_ops.get(dev, {})
        for o in evs:
            if o.short in T.CONTAINERS or o.end <= t0 or o.start >= t1:
                continue
            key = part_of(names.get(o.name, ""))
            if key is not None:
                ns[key] = ns.get(key, 0) + min(o.end, t1) - max(o.start, t0)
    return {k: v / 1e6 / steps / len(ctx.trace.ops) for k, v in ns.items()}
