"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import E4M3, E5M2
from repro.core.gam import compute_scales
from repro.core.partition import Partition
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fp8_gemm import fp8_gemm
from repro.kernels.gam_quant import (
    VMEM_TILE_BUDGET,
    gam_quant_blocks,
    tile_for,
)
from repro.kernels.ops import gam_quant


def _rand(shape, seed=0, scale=1.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


# ------------------------------------------------------------- gam_quant --
# (1024, 2048) and (2048, 1024) run several grid steps of many blocks;
# (384, 1280) takes a tile that only some block counts divide.
@pytest.mark.parametrize("shape", [(128, 128), (256, 384), (512, 128),
                                   (1024, 2048), (2048, 1024), (384, 1280)])
@pytest.mark.parametrize("block", [(128, 128), (64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("algo", ["gam", "e8m0", "fp32_amax"])
def test_gam_quant_kernel_matches_ref(shape, block, dtype, algo):
    if shape[0] % block[0] or shape[1] % block[1]:
        pytest.skip("kernel requires divisible shapes")
    # hash() of strings is randomized per process; derive seeds stably.
    x = _rand(shape, seed=sum(shape) + sum(block) + len(algo), scale=3.0,
              dtype=dtype)
    part = Partition("block", block)

    from repro.core.gam import split_mantissa_exponent

    g_amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    m_g, _ = split_mantissa_exponent(E4M3.amax / g_amax)
    if algo != "gam":
        m_g = jnp.float32(1.0)

    xq, exp, err, cnt = gam_quant_blocks(
        x, m_g, block=block, q_amax=E4M3.amax, fmt_dtype=E4M3.dtype,
        algo=algo, interpret=True,
    )
    rxq, rexp, rerr, rcnt = kref.gam_quant_ref(x, part, E4M3, algo)

    np.testing.assert_array_equal(
        np.asarray(xq, np.float32), np.asarray(rxq, np.float32)
    )
    np.testing.assert_array_equal(np.asarray(exp), np.asarray(rexp))
    np.testing.assert_allclose(
        np.asarray(err), np.asarray(rerr), rtol=2e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))


# Every operand shape the nemotron3-8b train cell quantizes (8192 tokens,
# d_model 4096, qkv 12288, d_ff 16384, and their transposes).
@pytest.mark.parametrize("shape", [
    (8192, 4096), (8192, 12288), (8192, 16384), (12288, 4096),
    (16384, 4096), (4096, 4096), (4096, 12288), (4096, 16384),
])
def test_gam_quant_tile_rule_engages(shape):
    """The train cell's operands each get a tile of many scale blocks
    whose double-buffered input and output tiles fit the VMEM budget."""
    tm, tk = tile_for(shape, (128, 128), jnp.bfloat16)
    assert shape[0] % tm == 0 and shape[1] % tk == 0
    assert tm % 128 == 0 and tk % 128 == 0
    assert (tm // 128) * (tk // 128) > 1
    assert 2 * 2 * 2 * tm * tk <= VMEM_TILE_BUDGET


@pytest.mark.parametrize("shape, block, dtype, tile", [
    ((128, 128), (128, 128), jnp.bfloat16, (128, 128)),
    # 17 block columns: no whole number of them up to the limit divides
    # but one, and a single block row.
    ((128, 17 * 128), (128, 128), jnp.float32, (128, 128)),
    # Only 5 or 10 block columns divide 20 under the limit of 16.
    ((384, 1280), (64, 64), jnp.float32, (384, 640)),
    # 64-wide blocks only pair up into 128-lane tiles: 34 columns of
    # them give pairs, as 17 is over the limit.
    ((128, 64 * 34), (64, 64), jnp.float32, (128, 128)),
])
def test_gam_quant_tile_rule_falls_back(shape, block, dtype, tile):
    assert tile_for(shape, block, dtype) == tile


def test_gam_quant_no_saturation_property():
    """Kernel output, re-scaled, never exceeds the format amax."""
    for seed in range(3):
        x = _rand((256, 256), seed=seed, scale=10.0**seed)
        xq, exp, _, _ = gam_quant(
            x, block=(128, 128), backend="interpret"
        )
        assert np.all(np.isfinite(np.asarray(xq, np.float32)))
    # amax under E4M3.amax / f32max: the ideal scale overflows f32, and
    # an uncapped Inf scale turns the zero into 0 * Inf = NaN.
    x = _rand((256, 256), seed=3, scale=1e-37).at[0, 0].set(0.0)
    xq = gam_quant(x, block=(128, 128), backend="interpret")[0]
    assert np.all(np.isfinite(np.asarray(xq, np.float32)))


# -------------------------------------------------------------- fp8_gemm --
@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 128, 384),
                                 (128, 256, 256)])
def test_fp8_gemm_matches_ref(mnk):
    M, N, K = mnk
    block = (128, 128, 128)
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    part = Partition("block", (128, 128))
    sa = compute_scales(a, part, E4M3).scale
    sb = compute_scales(b, part, E4M3).scale

    def quantize(x, s, bm, bk):
        xb = x.reshape(x.shape[0] // bm, bm, x.shape[1] // bk, bk)
        xs = xb * s[:, None, :, None]
        return (
            jnp.clip(xs, -E4M3.amax, E4M3.amax)
            .astype(jnp.float8_e4m3fn)
            .reshape(x.shape)
        )

    aq = quantize(a, sa, 128, 128)
    bq = quantize(b, sb, 128, 128)

    out = fp8_gemm(aq, bq, sa, sb, block=block, out_dtype=jnp.float32,
                   interpret=True)
    ref = kref.fp8_gemm_ref(aq, bq, sa, sb, block, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-3
    )
    # And the dequantized GEMM approximates the f32 GEMM (fp8 fidelity).
    exact = np.asarray(a) @ np.asarray(b)
    rel = np.abs(np.asarray(out) - exact) / (np.abs(exact) + 1e-2)
    assert np.median(rel) < 0.1


# ------------------------------------------------------- flash_attention --
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 256, 64), (4, 512, 128),
                                   (1, 128, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(causal, shape, dtype):
    BH, S, d = shape
    q = _rand((BH, S, d), seed=2, dtype=dtype)
    k = _rand((BH, S, d), seed=3, dtype=dtype)
    v = _rand((BH, S, d), seed=4, dtype=dtype)
    out = flash_attention_fwd(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    ref = kref.flash_attention_ref(q, k, v, causal)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=atol,
    )


def test_flash_attention_matches_model_attention():
    """Kernel vs the chunked-XLA model attention (same math, two impls)."""
    from repro.models.attention import flash_attention as xla_flash

    B, S, H, dh = 2, 256, 4, 64
    q = _rand((B, S, H, dh), seed=5, dtype=jnp.float32)
    k = _rand((B, S, H, dh), seed=6, dtype=jnp.float32)
    v = _rand((B, S, H, dh), seed=7, dtype=jnp.float32)
    out_xla = xla_flash(q, k, v, kind="causal", q_chunk=128, k_chunk=128)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, S, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, S, dh)
    out_k = flash_attention_fwd(
        qf, kf, vf, causal=True, block_q=128, block_k=128, interpret=True
    )
    out_k = jnp.moveaxis(out_k.reshape(B, H, S, dh), 1, 2)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_xla), rtol=1e-4, atol=1e-4
    )
