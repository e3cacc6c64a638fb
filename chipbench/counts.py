"""Operations and bytes of the work a cell asks for, from shapes alone.

These are the yardstick's own counts: they follow the mathematics of
the work, not what any kernel happens to do, so a faster kernel cannot
change them. A step's operations follow its model family
(``train_step_flops`` and ``decode_step_flops`` in
``families/<family>.py``); here are the bytes of single kernels, the
bytes of HLO shapes, and the chip's peaks from ``peaks.json``.
"""
from __future__ import annotations

import json
import pathlib
import re
from typing import Dict, List, Sequence, Tuple

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1,
                "s16": 2, "u16": 2, "f64": 8, "s64": 8, "u64": 8}


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown kind is an error."""
    table = json.loads(_PEAKS.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------- shapes in HLO --
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")


def shape_bytes(text: str) -> List[Tuple[str, Tuple[int, ...], int]]:
    """Every ``dtype[d0,d1,...]`` in an HLO text, with its bytes."""
    out = []
    for dt, dims in _SHAPE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",") if x)
        n = 1
        for x in shape:
            n *= x
        out.append((dt, shape, n * _DTYPE_BYTES[dt]))
    return out


def hlo_io_bytes(long_name: str) -> int:
    """Bytes an HLO instruction reads and writes: its result shapes plus
    its operand shapes, as its text states them."""
    if "=" not in long_name:
        return 0
    result, rest = long_name.split("=", 1)
    call = rest[rest.find("("):] if "(" in rest else ""
    head = rest[: rest.find("(")] if "(" in rest else rest
    return sum(b for _, _, b in shape_bytes(head)) + \
        sum(b for _, _, b in shape_bytes(call.split("),")[0]))


def fake_quant_bytes(shape: Sequence[int], dtype_bytes: int = 2) -> int:
    """Least traffic of one fake-quantization event: read the operand
    once and write the fake-quantized operand once."""
    n = 1
    for x in shape:
        n *= x
    return 2 * n * dtype_bytes


def mixed_gemm_bytes(m: int, k: int, n: int, block=(128, 128),
                     act_bytes: int = 2, out_bytes: int = 2) -> int:
    """Least traffic of one mixed GEMM over a (n, k) weight stored at one
    byte per element: the payload, one tag byte and one f32 scale per
    block, the (m, k) activation read and the (m, n) output written."""
    blocks = -(-n // block[0]) * -(-k // block[1])
    return n * k + blocks * (1 + 4) + m * k * act_bytes + m * n * out_bytes


def roofline_s(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """Least time on one chip: the larger of compute and memory time."""
    return max(flops / pk["peak_flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
