"""Per-layer metric readers, one file each, found by the metric's name.

A reader is ``metrics/<name>.py`` with ``read(ctx) -> float | None``.
``ctx`` has ``cell``, ``cfg`` (the ArchConfig), ``trace`` (a
``trace.Reduced``), ``counters`` (what the run counted and timed),
``peaks`` (the chip's row of ``peaks.json``) and ``chips``. A reader that
finds nothing to read returns None and the metric is left out of the
line; it never returns 0 for a share of a roofline or a peak.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
from typing import Any, Dict, Optional

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Ctx:
    cell: Any
    cfg: Any
    trace: Any
    counters: Dict[str, Any]
    peaks: Dict[str, float]
    chips: int


def reader(name: str):
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics._{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(cell, trace, counters, device_kind: str, chips: int
             ) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell that its reader finds."""
    from .. import counts

    ctx = Ctx(cell, cell.family.arch_config(cell.config), trace, counters,
              counts.peaks(device_kind), chips)
    out = {}
    for m in cell.per_layer:
        value: Optional[float] = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
