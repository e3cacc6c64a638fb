"""Tiny cells for the benchmark's CPU tests: the cell's own traffic and
configuration files with widths cut to what a test can hold."""
from __future__ import annotations

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 64, "intermediate_size": 256,
        "vocab_size": 512}


def _json(rel):
    return json.loads((ROOT / "chipbench" / rel).read_text())


def tiny_cell(kind: str, limits=None):
    from chipbench import spec

    if kind == "train":
        conf = _json("configs/nemotron3-8b-l2.json")
        traffic = _json("traffic/s2048b4.json")
        traffic.update(seq_len=64, global_batch=2, trace_steps=2)
        e2e = ["train_tokens_per_s", "setup_s"]
    else:
        conf = _json("configs/minitron-4b.json")
        traffic = _json("traffic/chat_overload.json")
        traffic["engine"].update(slots=4, max_seq=256, prefill_chunk=32)
        traffic.update(
            rate_per_s=3.0, drain_s=30, trace_seconds=1.0,
            prompt={"median": 40, "sigma": 0.6, "min": 8, "max": 120},
            output={"median": 6, "sigma": 0.4, "min": 2, "max": 12},
            check={"tokens": 40, "max_requests": 4})
        e2e = ["serve_tokens_per_s", "setup_s"]
    conf.update(TINY)
    return spec.Cell(
        name=f"tiny.{kind}", chips=1, config=conf, traffic=traffic,
        limits=limits,
        end_to_end=[{"name": n, "unit": "x"} for n in e2e], per_layer=[])



def file_cell(config: str, traffic: str):
    """A cell of a configuration and a traffic file as they stand,
    whether or not BENCHMARK.json runs it."""
    from chipbench import spec

    return spec.Cell(
        name=f"{traffic}.{config}", chips=1,
        config=_json(f"configs/{config}.json"),
        traffic=_json(f"traffic/{traffic}.json"), limits=None,
        end_to_end=[], per_layer=[])
