"""chip_smoke.py's phases at a tiny width on the CPU, and its gates.

The script itself refuses to run anywhere but on a TPU; these tests run
its train and serve phases with the sizes cut down (interpret-mode
kernels, see conftest.py), so a broken phase shows up before a chip
call does.
"""
import dataclasses
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
import chip_smoke  # noqa: E402


@pytest.fixture
def tiny(monkeypatch):
    for name, value in (("SEQ", 128), ("BATCH", 2), ("MAX_SEQ", 128),
                        ("PROMPT", 32), ("NEW_TOKENS", 8)):
        monkeypatch.setattr(chip_smoke, name, value)
    return dataclasses.replace(
        chip_smoke.smoke_config(), d_model=128, n_heads=4, n_kv=4,
        head_dim=32, d_ff=256, vocab=512,
    )


def test_smoke_config_is_published_width():
    cfg = chip_smoke.smoke_config()
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff) == (
        4096, 32, 128, 16384
    )
    assert (cfg.n_layers, cfg.vocab, cfg.act) == (2, 32000, "relu2")


def test_train_phase_tiny(tiny):
    res = chip_smoke.train_phase(tiny, jax.devices()[0], seed=0)
    assert res["step_s"] > 0 and res["tokens"] == 2 * 128


def test_serve_phase_tiny(tiny):
    chip_smoke.serve_phase(tiny, jax.devices()[0], seed=0)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["interpret_env", "no_tpu"])
def test_refuses_without_tpu(monkeypatch, capsys, interpret):
    if interpret:
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    else:
        monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
