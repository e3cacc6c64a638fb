"""Puts the repository root on the path for the benchmark's tests."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def cpu_devices():
    import jax

    return jax.devices("cpu")[:1]
