"""Device time per layer of the program, from the scopes it names
(shared by the per-layer readers; not a metric).

The program wraps each layer of its step in ``jax.named_scope``; XLA
keeps the scope path in every instruction's ``op_name``, and the TPU
profiler writes it as the ``tf_op`` stat of the instruction's event
metadata on the device plane. ``jax.profiler.ProfileData`` does not
expose metadata stats, so this reads ``XSpace.planes[].event_metadata``
and ``stat_metadata`` straight from the protobuf wire format of the
cell's newest ``.xplane.pb`` (the file ``Tracer.reduce`` read), and
joins each op of ``ctx.trace.ops`` to its ``tf_op`` by the instruction
text, which is both the op's name and its metadata's name.

Path rule: take the ``tf_op`` before any ``;`` and ``:``, split it on
``/`` outside parentheses, unwrap transforms (``jvp(x)``,
``transpose(x)``, ``vmap(x)`` give ``x``), drop ``jit(...)`` and the
segments of remat and control flow (``checkpoint``, ``while/body``);
the deepest known layer segment wins. ``attn`` and ``mlp`` name their
parts (``attn/core``, ``mlp/fc1``). A fusion belongs to its root's
``tf_op``, which is XLA's own attribution: a matmul with the quantizer's
amax fused into it counts as ``gemm``. Container ops (``while``,
``conditional``, ``call``) are left out, as in ``Reduced.breakdown``.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Optional

from chipbench import trace as T

LAYERS = ("embed", "stack", "norm", "residual", "attn", "mlp", "mor_quant",
          "gemm", "head", "loss", "optim")
PARTS = {"attn": ("qkv", "rope", "core", "proj"),
         "mlp": ("fc1", "act", "fc2")}
SKIP = {"", "checkpoint", "remat", "rematted_computation", "while", "body",
        "cond", "closed_call"}
STEP_SPAN = T.SPAN_PREFIX + "train_step"

_WRAP = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$", re.S)


def _segments(path: str):
    """The scope segments of a name-stack path, transforms unwrapped
    (``transpose(jvp(attn/core))`` gives ``attn``, ``core``) and
    ``jit(...)`` dropped."""
    depth, start = 0, 0
    for i, ch in enumerate(path + "/"):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            piece = path[start:i]
            start = i + 1
            m = _WRAP.match(piece)
            if m is None:
                yield piece
            elif m.group(1) not in ("jit", "pjit"):
                yield from _segments(m.group(2))


def layer_of(tf_op: str) -> Optional[str]:
    """The layer an instruction's ``tf_op`` (or ``op_name``) names, or
    None where it names none."""
    path = tf_op.split(";", 1)[0]
    head, _, last = path.rpartition("/")
    path = f"{head}/{last.split(':', 1)[0]}" if head else last
    layer, prev = None, None
    for seg in _segments(path):
        if seg in SKIP:
            continue
        if seg in LAYERS:
            layer = seg
        elif seg in PARTS.get(prev, ()):
            layer = f"{prev}/{seg}"
        prev = seg
    return layer


# ------------------------------------------------------- protobuf wire --
def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: Optional[int] = None):
    """(field number, value) of each field of one message in buf[i:end];
    a length-delimited value is its (start, end) in ``buf``."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    for num, val in _fields(buf, *span):
        if num == 2:
            yield val


def _plane_tf_ops(buf, span) -> tuple:
    """(plane name, {event metadata name: tf_op}) of one XPlane."""
    name, events, stat_names = "", [], {}
    for num, val in _fields(buf, *span):
        if num == 2:
            name = _text(buf, val)
        elif num == 4:
            events.extend(_map_values(buf, val))
        elif num == 5:
            for v in _map_values(buf, val):
                sid, sname = None, ""
                for n, x in _fields(buf, *v):
                    if n == 1:
                        sid = x
                    elif n == 2:
                        sname = _text(buf, x)
                stat_names[sid] = sname
    tf_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    out: Dict[str, str] = {}
    if tf_id is None:
        return name, out
    for ev in events:
        ev_name, op = "", ""
        for num, val in _fields(buf, *ev):
            if num == 2:
                ev_name = _text(buf, val)
            elif num == 5:
                stat = dict(_fields(buf, *val))
                if stat.get(1) != tf_id:
                    continue
                if 5 in stat:
                    op = _text(buf, stat[5])
                elif 7 in stat:
                    op = stat_names.get(stat[7], "")
        if op and ev_name not in out:
            out[ev_name] = op
    return name, out


def read_tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane name: {instruction text: tf_op}} of one
    ``.xplane.pb``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = {}
    for num, val in _fields(buf):
        if num == 1:
            name, ops = _plane_tf_ops(buf, val)
            if name.startswith(T.DEVICE_PREFIX):
                planes[name] = ops
    return planes


@functools.lru_cache(maxsize=4)
def _tf_ops_by_device(path: str, mtime: float) -> Dict[int, Dict[str, str]]:
    out = {}
    for name, ops in read_tf_ops(path).items():
        dev = T._device_index(name)
        if dev is not None:
            out[dev] = ops
    return out


def trace_file(cell_name: str) -> Optional[str]:
    files = glob.glob(str(T.OUT / "trace" / cell_name / "**" /
                          "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def per_step_ms(ctx) -> Dict[Optional[str], float]:
    """Device milliseconds per traced train step of each layer (None:
    under no layer scope), the mean over the chips the cell uses. Empty
    where the trace holds no step span or no op carries a scope."""
    path = trace_file(ctx.cell.name)
    steps = sum(1 for s in ctx.trace.spans if s.name == STEP_SPAN)
    if path is None or steps == 0 or not ctx.trace.ops:
        return {}
    tf_ops = _tf_ops_by_device(path, os.path.getmtime(path))
    t0, t1 = ctx.trace.t0, ctx.trace.t1
    ns: Dict[Optional[str], int] = {}
    for dev, evs in ctx.trace.ops.items():
        names = tf_ops.get(dev, {})
        for o in evs:
            if o.short in T.CONTAINERS or o.end <= t0 or o.start >= t1:
                continue
            layer = layer_of(names.get(o.name, ""))
            ns[layer] = ns.get(layer, 0) + min(o.end, t1) - max(o.start, t0)
    if not any(k is not None for k in ns):
        return {}
    return {k: v / 1e6 / steps / len(ctx.trace.ops) for k, v in ns.items()}


def layer_ms(ctx, layer: str) -> Optional[float]:
    """Device milliseconds per train step under ``layer``, or None where
    no op of the trace carries it."""
    return per_step_ms(ctx).get(layer)
