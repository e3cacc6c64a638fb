"""StableHLO / HLO structural-rule primitives.

Single source of truth for the cross-lowering helpers that used to be
copy-pasted between ``benchmarks/bench_kernels.py``,
``tests/test_quantize_pack.py``, ``tests/test_nvfp4.py`` and
``tests/test_mixed_gemm.py``: lowering a jitted entry point for TPU on
any host, counting fused-kernel launches, counting operand-sized XLA
passes, and scanning for forbidden op families (f64 arithmetic,
operand-sized convert/pad/bitcast packing passes, host transfers).

The contract registry (:mod:`repro.analysis.contracts`) evaluates its
declarative rules with these primitives; benches and tests import the
same functions so the two can never drift apart. Compiled-HLO rules
(donation aliasing on the running backend) lean on
:mod:`repro.launch.hlo_analysis` for parsing.
"""
from __future__ import annotations

import re
from typing import Callable, List, Sequence, Tuple

import jax

__all__ = [
    "tpu_lowering_text",
    "lowering_text",
    "compiled_hlo_text",
    "count_custom_calls",
    "operand_sized_ops",
    "operand_sized_packing_ops",
    "f64_lines",
    "host_transfer_lines",
    "donated_arg_count",
    "compiled_f64_instrs",
]


def tpu_lowering_text(fn: Callable, *args) -> str:
    """StableHLO text of ``jit(fn)(*args)`` cross-lowered for TPU.

    Works on any host (no TPU needed): the Pallas path becomes
    ``tpu_custom_call`` ops in the text.
    """
    traced = jax.jit(fn).trace(*args)
    return traced.lower(lowering_platforms=("tpu",)).as_text()


def lowering_text(fn: Callable, *args, donate_argnums=()) -> str:
    """StableHLO text on the *default* platform (donation markers --
    ``tf.aliasing_output`` -- preserved on the func signature)."""
    return (
        jax.jit(fn, donate_argnums=donate_argnums)
        .trace(*args)
        .lower()
        .as_text()
    )


def compiled_hlo_text(fn: Callable, *args, donate_argnums=()) -> str:
    """Optimized (post-fusion) HLO text on the running backend --
    the input :func:`repro.launch.hlo_analysis.parse_hlo` consumes."""
    return (
        jax.jit(fn, donate_argnums=donate_argnums)
        .lower(*args)
        .compile()
        .as_text()
    )


def count_custom_calls(txt: str) -> int:
    """Fused-kernel launches in a TPU cross-lowering."""
    return txt.count("tpu_custom_call")


TENSOR_DIMS_RE = re.compile(r"tensor<([0-9]+(?:x[0-9]+)*)x[a-z]")


def _line_max_elements(ln: str) -> int:
    best = 0
    for m in TENSOR_DIMS_RE.finditer(ln):
        p = 1
        for d in m.group(1).split("x"):
            p *= int(d)
        best = max(best, p)
    return best


def _operand_sized_lines(txt: str, shape: Tuple[int, int]) -> List[str]:
    thresh = shape[0] * shape[1] // 2
    out = []
    for ln in txt.splitlines():
        if ("=" not in ln or "custom_call" in ln or "func" in ln
                or "return" in ln):
            continue
        if _line_max_elements(ln) >= thresh:
            out.append(ln)
    return out


def operand_sized_ops(txt: str, shape: Tuple[int, int]) -> int:
    """Operand-sized op count in a TPU cross-lowering (stablehlo): how
    many non-custom-call ops still touch an operand-sized buffer -- the
    'XLA pass' count of the pallas path. Counted by element product
    (>= half the operand), so blocked 4-D views ((nm, nk, bm, bk)
    reshapes/transposes of the old packer) and the packed-nibble lane
    count too, whatever their rank."""
    return len(_operand_sized_lines(txt, shape))


# The op families a fused pack/GEMM lowering must not re-introduce at
# operand size: XLA packing passes re-blocking (`pad`), re-casting
# (`convert`) or re-interpreting (`bitcast_convert`) the whole operand
# after the kernel already emitted the payload lanes.
PACKING_OP_FAMILIES = ("convert", "pad", "bitcast_convert")


def operand_sized_packing_ops(
    txt: str,
    shape: Tuple[int, int],
    families: Sequence[str] = PACKING_OP_FAMILIES,
) -> List[str]:
    """Operand-sized lines from the forbidden packing-op families."""
    hits = []
    for ln in _operand_sized_lines(txt, shape):
        if any(f"stablehlo.{fam}" in ln for fam in families):
            hits.append(ln.strip())
    return hits


_F64_RE = re.compile(r"xf64[>x]|tensor<f64>")


def f64_lines(txt: str) -> List[str]:
    """Lines of a stablehlo lowering that touch an f64 tensor. MoR
    kernels and their callers are bf16/f32 (+ sub-byte payload lanes);
    any f64 means an accidental x64 promotion doubled a buffer."""
    return [ln.strip() for ln in txt.splitlines() if _F64_RE.search(ln)]


# Markers of host<->device traffic in a lowering: infeed/outfeed,
# send/recv, host callbacks (io_callback / pure_callback / debug
# prints) and host-placement annotations. A jitted decode step with
# any of these stalls the accelerator on the host every token.
HOST_TRANSFER_MARKERS = (
    "stablehlo.infeed",
    "stablehlo.outfeed",
    "stablehlo.send",
    "stablehlo.recv",
    "xla_python_cpu_callback",
    "xla_ffi_python",
    "host_callback",
    "annotate_device_placement",
)


def host_transfer_lines(txt: str) -> List[str]:
    """Lines of a lowering that move data between host and device."""
    return [
        ln.strip()
        for ln in txt.splitlines()
        if any(m in ln for m in HOST_TRANSFER_MARKERS)
    ]


def donated_arg_count(txt: str) -> int:
    """Number of donated (output-aliased) arguments in a lowering --
    ``tf.aliasing_output`` markers on the main func signature."""
    return txt.count("tf.aliasing_output")


def compiled_f64_instrs(hlo_text: str) -> List[str]:
    """Names of optimized-HLO instructions with an f64 result, via the
    :mod:`repro.launch.hlo_analysis` parser (post-fusion view: catches
    promotions the stablehlo text hides behind composites)."""
    from repro.launch.hlo_analysis import parse_hlo

    out = []
    for instrs in parse_hlo(hlo_text).values():
        for ins in instrs:
            if "f64[" in ins.shape:
                out.append(ins.name)
    return out
