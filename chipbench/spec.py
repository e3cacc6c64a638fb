"""Finds a cell's configuration, traffic mix and limits by name.

Everything that belongs to one configuration, one traffic mix or one
cell is a JSON file of its own under this directory; ``BENCHMARK.json``
at the repository root names them. A configuration's ``family`` names
the Python file ``families/<family>.py`` that maps its keys onto the
program and holds its reference equations and operation counts.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

class SpecError(ValueError):
    """A cell, configuration or traffic file is missing or malformed."""


def _load(path: pathlib.Path) -> Dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _load(root / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Optional[Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    # The module of the configuration's family file, loaded with the cell.
    family: Any = dataclasses.field(init=False, repr=False, compare=False)

    def __post_init__(self):
        object.__setattr__(self, "family", family_of(self.config))

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files loaded."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = _load(root / confs[w["config"]]["file"])
    traffic = _load(HERE / "traffic" / f"{w['traffic']}.json")
    lim_path = HERE / "limits" / f"{name}.json"
    limits = _load(lim_path) if lim_path.exists() else None
    for key in ("source", "reduced", "assumed", "family"):
        if key not in conf:
            raise SpecError(f"configuration {w['config']} lacks {key!r}")
    if traffic.get("kind") not in ("train", "serve"):
        raise SpecError(f"traffic {w['traffic']}: kind must be train|serve")
    return Cell(
        name=name, chips=int(w["chips"]), config=conf, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def family_of(conf: Dict[str, Any]):
    """The module of ``families/<family>.py``, the file that the
    configuration's ``family`` names, loaded by its path."""
    name = conf.get("family")
    path = HERE / "families" / f"{name}.py"
    if not isinstance(name, str) or "/" in name or not path.is_file():
        raise SpecError(f"family {name!r} has no file: add "
                        f"chipbench/families/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.families.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
