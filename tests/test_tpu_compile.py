"""Main-path kernels compiled for a described TPU v5e at real widths.

Interpret mode never sees the chip's memory limits or its tiling rules;
the TPU compiler, which is installed here, does. Each case compiles one
kernel for one chip of a described ``v5e:2x2`` topology (no chip needed)
at the widths ``chip_smoke.py`` runs: nemotron3-8b's d_model 4096 and
d_ff 16384, 8192-token training batches, 2048-token prefill, 4 decode
slots and a 32,000-column logits gradient. A refused kernel (SMEM or
VMEM size, lane alignment, an unsupported cast) fails here at no chip
time.

The kernels keep their instruction names under the program's named
scopes, and the tiny train step compiled for the chip names one layer
per instruction (``tests/chipbench/scoped_hlo.py``).

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and every test worker imports
every test file.
"""
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import NVFP4_MICRO
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.gam_quant import gam_quant_blocks
from repro.kernels.mor_select import mor_select_blocks
from repro.kernels.ref import (
    MixedOperand,
    _ms_compact_shape,
    _nib_compact_shape,
)

D_MODEL, D_FF, TOKENS, VOCAB = 4096, 16384, 8192, 32000
BLOCK = (128, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def spec(one_chip, no_persistent_cache):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize(
    "shape", [(TOKENS, D_MODEL), (D_FF, D_MODEL), (TOKENS, D_FF)],
    ids=["act", "weight", "act_ff"],
)
def test_gam_quant_compiles(spec, shape):
    _compile(
        lambda x, m: gam_quant_blocks(x, m, block=BLOCK),
        spec(shape, jnp.bfloat16), spec((), jnp.float32),
    )


@pytest.mark.parametrize("emit", ["select", "pack"])
@pytest.mark.parametrize(
    "shape", [(TOKENS, D_MODEL), (D_FF, D_MODEL), (TOKENS, VOCAB)],
    ids=["act", "weight", "logits_grad"],
)
def test_mor_select_sub3_compiles(spec, shape, emit):
    _compile(
        lambda x, m, g: mor_select_blocks(
            x, m, g, block=BLOCK, mode="sub3", emit=emit
        ),
        spec(shape, jnp.bfloat16), spec((3,), jnp.float32),
        spec((), jnp.float32),
    )


def _operand(spec, rows, k, row_block, *, fp8, bf16, nvfp4=False):
    """Shapes of a (rows, k) mixed operand; unused lanes compact."""
    nr, nk = rows // row_block, k // BLOCK[1]
    blk = (row_block, BLOCK[1])
    return MixedOperand(
        payload_q=spec((rows, k) if fp8 else blk, jnp.uint8),
        payload_bf16=spec((rows, k) if bf16 else blk, jnp.bfloat16),
        tags=spec((nr, nk), jnp.int32),
        scales=spec((nr, nk), jnp.float32),
        block=blk,
        shape=(rows, k),
        payload_nib=spec(
            (rows // 2, k) if nvfp4 else _nib_compact_shape(blk), jnp.uint8
        ),
        micro_scales=spec(
            (rows, k // NVFP4_MICRO) if nvfp4 else _ms_compact_shape(blk),
            jnp.uint8,
        ),
        has_nvfp4=nvfp4,
    )


@pytest.mark.parametrize(
    "rows", [2048, 4], ids=["prefill_2048", "decode_4_slots"]
)
@pytest.mark.parametrize(
    "n, k, recipe",
    [(D_FF, D_MODEL, "sub3"), (D_MODEL, D_FF, "sub3"),
     (D_FF, D_MODEL, "sub4")],
    ids=["fc1", "fc2", "fc1_sub4"],
)
def test_mixed_gemm_compiles(spec, rows, n, k, recipe):
    """A bf16 activation pack against a weight whose fp8 and bf16 lanes
    (and for sub4 the NVFP4 lanes) are all dense: a mixed weight, the
    most bytes per block."""
    a = _operand(spec, max(rows, 16), k, kops.decode_row_block(rows),
                 fp8=False, bf16=True)
    b = _operand(spec, n, k, BLOCK[0], fp8=True, bf16=True,
                 nvfp4=recipe == "sub4")
    _compile(lambda a, b: kops.mixed_gemm(a, b, backend="pallas"), a, b)


def _kernel_cases(spec):
    """Each Pallas kernel at a small shape: (name, fn, argument shapes)."""
    x = spec((256, 256), jnp.bfloat16)
    mixed_a = _operand(spec, 256, 256, kops.decode_row_block(256),
                       fp8=False, bf16=True)
    mixed_b = _operand(spec, 256, 256, BLOCK[0], fp8=True, bf16=True)
    qkv = spec((2, 512, 128), jnp.bfloat16)
    return {
        "gam_quant_blocks": (
            lambda x, m: gam_quant_blocks(x, m, block=BLOCK),
            (x, spec((), jnp.float32))),
        "mor_select_blocks": (
            lambda x, m, g: mor_select_blocks(x, m, g, block=BLOCK,
                                              mode="sub3", emit="select"),
            (x, spec((3,), jnp.float32), spec((), jnp.float32))),
        "mixed_gemm_blocks": (
            lambda a, b: kops.mixed_gemm(a, b, backend="pallas"),
            (mixed_a, mixed_b)),
        "flash_attention_fwd": (flash_attention_fwd, (qkv, qkv, qkv)),
    }


# fp8_gemm is left out: its (1, 1) blocks of scales do not lower for the
# TPU at all.
@pytest.mark.parametrize("kernel", [
    "gam_quant_blocks", "mor_select_blocks", "mixed_gemm_blocks",
    "flash_attention_fwd"])
def test_kernel_keeps_its_name_under_scopes(spec, kernel):
    """A kernel called under the program's layer scopes keeps the
    instruction name the trace readers match, and its op_name carries
    the scopes."""
    fn, args = _kernel_cases(spec)[kernel]

    def scoped(*a):
        with jax.named_scope("attn"), jax.named_scope("mor_quant"):
            return fn(*a)

    text = _compile(scoped, *args).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", line), line
        assert "/attn/mor_quant/" in line, line


def test_train_step_names_one_layer_per_instruction(one_chip,
                                                    no_persistent_cache):
    """The tiny train cell's step compiled for one v5e chip with the
    kernels on Pallas: the instructions the trace readers attribute."""
    here = pathlib.Path(__file__).resolve().parent
    for path in (here.parent, here / "chipbench"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    from chipbench.metrics._scopes import layer_of
    from chipbench_tiny import tiny_cell
    from scoped_hlo import check_step_scopes, instructions, train_step_hlo

    text = train_step_hlo(tiny_cell("train"), one_chip, backend="pallas")
    check_step_scopes(text)
    quant = [op for name, _, op in instructions(text)
             if name.startswith("gam_quant_blocks")]
    assert quant and all(layer_of(op) == "mor_quant" for op in quant)
