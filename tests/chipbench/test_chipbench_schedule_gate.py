"""The open-loop schedule is a function of the seed alone, and a run
refuses any machine but a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import ROOT


def _chat():
    return json.loads((ROOT / "chipbench" / "traffic" / "chat_overload.json")
                      .read_text())


def _key(plans):
    return [(p.rid, round(p.due, 9), p.prompt.tobytes(), p.max_tokens,
             p.greedy, p.seed) for p in plans]


def test_schedule_is_a_function_of_the_seed():
    from chipbench import serve

    big = 2**31 + 12345
    a = serve.schedule(_chat(), big, 40.0, 256000)
    b = serve.schedule(_chat(), big, 40.0, 256000)
    c = serve.schedule(_chat(), big + 1, 40.0, 256000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # Every seed offers the same work, in another order.
    sizes = lambda ps: sorted((len(p.prompt), p.max_tokens, p.greedy)
                              for p in ps)
    assert sizes(a) == sizes(c)
    assert len(a) == len(c) == round(_chat()["rate_per_s"] * 40)
    assert all(0 <= p.due <= 40.0 for p in a)
    assert [p.due for p in a] == sorted(p.due for p in a)


def test_schedule_lengths_stay_in_their_bounds():
    from chipbench import serve

    t = _chat()
    plans = serve.schedule(t, 7, 40.0, 256000)
    assert all(t["prompt"]["min"] <= len(p.prompt) <= t["prompt"]["max"]
               for p in plans)
    assert all(t["output"]["min"] <= p.max_tokens <= t["output"]["max"]
               for p in plans)
    greedy = sum(p.greedy for p in plans)
    assert greedy == round(t["greedy_share"] * len(plans))
    assert all(p.prompt.max() < 256000 for p in plans)


def test_seeded_weights_beyond_32_bits_are_reproducible(cpu_devices):
    import jax

    from chipbench import weights
    from chipbench_tiny import tiny_cell

    cell = tiny_cell("train")
    fam = cell.family
    cfg = fam.arch_config(cell.config)
    a = weights.make_params(fam, cfg, 2**33 + 5)
    b = weights.make_params(fam, cfg, 2**33 + 5)
    c = weights.make_params(fam, cfg, 5)
    weights.check_tree(cfg, a)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["lm_head"] == c["lm_head"]).all())
    one = weights.make_leaf(fam, weights.base_key(2**33 + 5), cfg,
                            "blocks/dense/mlp/wi", 1)
    assert bool((one == a["blocks"]["dense"]["mlp"]["wi"][1]).all())


def test_device_gate_refuses_the_cpu(monkeypatch):
    from chipbench import run

    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    run.use_program()
    with pytest.raises(run.GateError, match="no TPU"):
        run.device_gate(1)


def _run(cwd, env):
    cmd = [sys.executable, "-m", "chipbench.run", "--workload",
           "train.nemotron3-8b-l2.s2048b4", "--seed", "3", "--seconds", "1",
           "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_on_the_cpu_exits_nonzero_with_no_result():
    env = {k: v for k, v in os.environ.items()
           if k not in ("REPRO_KERNEL_INTERPRET", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run(ROOT, env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run(tmp_path, env)
    assert out.returncode != 0
    assert "no program" in out.stderr
    assert '"correct"' not in out.stdout
