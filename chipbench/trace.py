"""Profiler capture around the benchmark's own calls, and the reduction
of the ``.xplane.pb`` it writes to what the per-layer metrics read.

The benchmark opens a ``jax.profiler.TraceAnnotation`` named
``bench.<layer>`` around each call it makes into the program (a train
step, an engine step). The reduction keeps, per device, the operations
of the ``XLA Ops`` line (each named by its whole HLO instruction) and
the program runs of the ``XLA Modules`` line, and every host event (the
benchmark's spans, and JAX's and Python's, which label idle gaps).
Nothing here knows a kernel by name: the metric readers match names.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import pathlib
import shutil
from typing import Dict, List, Optional, Tuple

OUT = pathlib.Path(__file__).resolve().parent.parent / "chipbench_out"

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


# HLO instructions that only hold other ops: their time is their
# children's, so the breakdown leaves them out.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    """One device event. On the TPU the event's name is the whole HLO
    instruction, ``%name.N = <result shapes> op(<operands>), ...``."""

    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur

    @property
    def text(self) -> str:
        return self.name

    @property
    def short(self) -> str:
        """The instruction's name without its number: ``fusion``,
        ``gam_quant_blocks``, ``convolution_convert_fusion``."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        base, _, num = head.rpartition(".")
        return base if base and num.isdigit() else head

    @property
    def kind(self) -> str:
        """The short name with the first result shape, the breakdown's
        key: ``gam_quant_blocks bf16[8192,16384]``."""
        rest = self.name.split(" = ", 1)[1] if " = " in self.name else ""
        shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
        return f"{self.short} {shape}".strip()


@dataclasses.dataclass
class Span:
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


def union_ns(intervals: List[Tuple[int, int]]) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class Reduced:
    """One traced window: device ops per chip, program runs per chip,
    the benchmark's host spans and every host event of the host
    threads (to label idle gaps)."""

    ops: Dict[int, List[Op]]
    modules: Dict[int, List[Op]]
    spans: List[Span]
    host: List[Span]
    t0: int
    t1: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def clipped(self, evs) -> List[Tuple[int, int]]:
        return [(max(e.start, self.t0), min(e.end, self.t1)) for e in evs
                if e.end > self.t0 and e.start < self.t1]

    def busy_ns(self, dev: int) -> int:
        return union_ns(self.clipped(self.ops.get(dev, [])))

    @property
    def busy_s(self) -> float:
        devs = sorted(self.ops) or [0]
        return sum(self.busy_ns(d) for d in devs) / len(devs) / 1e9

    def runs_of(self, mark: str, holds, dev: int = 0) -> List[Op]:
        """Runs of the compiled programs whose name contains ``mark``
        and which hold an op for which ``holds(op)`` is true: the way to
        tell apart two programs of one jitted function by their shapes."""
        runs = [m for m in self.modules.get(dev, []) if mark in m.name
                and m.end > self.t0 and m.start < self.t1]
        return [r for r in runs if any(holds(o)
                                       for o in self.ops_within(r, dev))]

    def ops_within(self, run: Op, dev: int = 0) -> List[Op]:
        return [o for o in self.ops.get(dev, [])
                if o.start >= run.start and o.end <= run.end]

    def idle_percent(self) -> Optional[float]:
        if not self.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def gaps(self, dev: int = 0, min_ns: int = 20_000
             ) -> List[Tuple[int, int]]:
        """Idle intervals of one device inside the window."""
        out, cur = [], self.t0
        for s, e in sorted(self.clipped(self.ops.get(dev, []))):
            if s - cur >= min_ns:
                out.append((cur, s))
            cur = max(cur, e)
        if self.t1 - cur >= min_ns:
            out.append((cur, self.t1))
        return out

    def label(self, s: int, e: int) -> str:
        """What the host was doing across a gap: the innermost host event
        that covers the gap's middle, under the benchmark span open
        there."""
        mid = (s + e) // 2
        outer = [x for x in self.spans if x.start <= mid < x.end]
        inner = [x for x in self.host if x.start <= mid < x.end
                 and not x.name.startswith(SPAN_PREFIX)]
        head = outer[-1].name if outer else "outside bench spans"
        if inner:
            return f"{head} > {min(inner, key=lambda x: x.dur).name}"
        return f"{head} > python"

    def breakdown(self, top: int = 10) -> Dict[str, List[List[object]]]:
        """The device ops that took most time (summed over every chip
        used, by name) and the longest idle gaps of chip 0, by label."""
        by_op: Dict[str, int] = {}
        for evs in self.ops.values():
            for o in evs:
                if o.short in CONTAINERS or o.end <= self.t0 \
                        or o.start >= self.t1:
                    continue
                by_op[o.kind] = by_op.get(o.kind, 0) + \
                    min(o.end, self.t1) - max(o.start, self.t0)
        by_gap: Dict[str, int] = {}
        for s, e in self.gaps(min(self.ops) if self.ops else 0):
            k = self.label(s, e)
            by_gap[k] = by_gap.get(k, 0) + e - s
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def _device_index(plane_name: str) -> Optional[int]:
    if not plane_name.startswith(DEVICE_PREFIX):
        return None
    tail = plane_name[len(DEVICE_PREFIX):]
    digits = "".join(ch for ch in tail if ch.isdigit())
    return int(digits) if digits and tail[: len(digits)] == digits else None


def reduce_file(path: str, n_devices: int) -> Reduced:
    """Reads one ``.xplane.pb``; the window runs from the first benchmark
    span's start to the last one's end."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    mods: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    host: List[Span] = []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is not None and dev < n_devices:
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dst = ops if line.name == OPS_LINE else mods
                dst.setdefault(dev, []).extend(
                    Op(ev.name, ev.start_ns, ev.duration_ns)
                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = Span(ev.name, ev.start_ns, ev.duration_ns)
                    (spans if ev.name.startswith(SPAN_PREFIX) else host
                     ).append(s)
    spans.sort(key=lambda x: x.start)
    if not spans:
        raise RuntimeError(f"no {SPAN_PREFIX}* span in {path}")
    # From the first benchmark span to the end of the work it launched:
    # the device runs asynchronously, after the host's span has closed.
    t0 = spans[0].start
    t1 = max([x.end for x in spans] + [o.end for evs in ops.values()
                                       for o in evs if o.start >= t0])
    return Reduced(ops, mods, spans, host, t0, t1)


class Tracer:
    """Profiles part of a window into ``chipbench_out/trace/<cell>``:
    the first ``steps`` train steps, or the engine steps that start in
    the ``seconds`` after the middle of a serving window."""

    def __init__(self, cell, steps: int = 0, seconds: float = 0.0):
        self.dir = OUT / "trace" / cell.name
        self.steps, self.seconds = steps, seconds
        self.on = False
        self.done = False
        self.layer = "train_step" if cell.kind == "train" else "engine_step"

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self.on = True

    def stop(self, outputs=None):
        import jax

        if outputs is not None:
            jax.block_until_ready(outputs)
        jax.profiler.stop_trace()
        self.on, self.done = False, True

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield

    def reduce(self, n_devices: int) -> Reduced:
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no trace under {self.dir}")
        return reduce_file(max(files, key=os.path.getmtime), n_devices)
