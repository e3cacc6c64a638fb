"""Production mesh factory and per-chip hardware peaks.

Single-pod: (data=16, model=16) = 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is an
additional pure-data-parallel dimension crossing the inter-pod DCN/ICI
boundary (gradient all-reduces over 'pod' are the cross-pod traffic the
compression tricks in repro.optim target).

Meshes use ``AxisType.Auto`` axes: the model code places activations
with ``with_sharding_constraint`` and lets GSPMD propagate the rest, so
reshapes and gathers of sharded operands need no explicit
``out_sharding``.

Defined as functions (never module-level constants) so importing this
module can never touch jax device state -- smoke tests must keep seeing
one CPU device.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = [
    "make_mesh", "make_production_mesh", "make_local_mesh", "HW",
    "ChipPeaks", "hw_peaks",
]


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, devices=None):
    """Small (data, model) mesh over local devices (tests, chip_smoke)."""
    return make_mesh((data, model), ("data", "model"), devices=devices)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    peak_flops_bf16: float  # FLOP/s
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link (~per-direction per chip)
    hbm_bytes: int


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
HW = {
    "TPU v5 lite": ChipPeaks(
        peak_flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,
        hbm_bytes=16 * 2**30,
    ),
}


def hw_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of a chip by ``device_kind``; an unknown kind is an error."""
    try:
        return HW[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(HW)}"
        ) from None
