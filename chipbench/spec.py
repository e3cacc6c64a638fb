"""Finds a cell's configuration, traffic mix and limits by name.

Everything that belongs to one configuration, one traffic mix or one
cell is a JSON file of its own under this directory; ``BENCHMARK.json``
at the repository root names them.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

# Source keys of a dense decoder's config.json -> the repository's
# ArchConfig fields. A configuration file states its model in the
# source's own keys; this table is the only place that maps them.
_DENSE_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "hidden_act": "act",
    "rope_theta": "rope_theta",
}


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
    for key in ("source", "reduced", "assumed"):
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


def arch_config(conf: Dict[str, Any]):
    """The repository's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig

    if conf.get("family") != "dense":
        raise SpecError(f"family {conf.get('family')!r} has no mapping")
    kw = {field: conf[key] for key, field in _DENSE_KEYS.items()}
    return ArchConfig(
        name=conf["name"], family="dense", unit=("dense",),
        norm="rms", dtype="bfloat16", **kw,
    )
