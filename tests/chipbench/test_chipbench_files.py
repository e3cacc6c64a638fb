"""Every file the benchmark names loads and states what it must."""
import json

import pytest

from chipbench_tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_source_reduced_assumed(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    assert isinstance(data["assumed"], dict) and data["assumed"]
    assert data["deployment"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_loads_with_its_traffic_and_limits(cell):
    from chipbench import spec

    c = spec.load_cell(cell["name"])
    assert c.kind in ("train", "serve")
    assert c.traffic["why"]
    assert c.limits is not None, "every cell has its limits file"
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_each_per_layer_metric_has_a_reader(metric):
    from chipbench import metrics

    assert callable(metrics.reader(metric["name"]))


def test_traffic_files_are_plain_data():
    for path in (ROOT / "chipbench" / "traffic").glob("*.json"):
        data = json.loads(path.read_text())
        assert data["kind"] in ("train", "serve"), path


def test_config_maps_onto_the_program(cpu_devices):
    from chipbench import spec

    for conf in BENCH["configs"]:
        c = json.loads((ROOT / conf["file"]).read_text())
        cfg = spec.family_of(c).arch_config(c)
        assert cfg.n_layers >= 1 and cfg.d_model % cfg.head_dim == 0


NAME = __import__("re").compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = __import__("re").compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
