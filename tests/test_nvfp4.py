"""NVFP4 (sub4 recipe) differential suite.

* E2M1 grid snap vs the ``ml_dtypes.float4_e2m1fn`` oracle (bit-exact),
  nibble encode/decode round-trips.
* Pack/unpack round-trips: ``quantize_for_gemm`` payloads decode to the
  fake-quantization output bit-for-bit -- odd shapes, all-zero blocks,
  every scaling algo.
* Backend parity: pallas-interpret vs xla bit-exact for selection,
  packing and the mixed GEMM (including custom_vjp grads via
  ``test_mor_recipes.test_fuse_gemm_parity``'s sub4 rows).
* Serving: a fully-NVFP4 QTensor reaches <= 0.6 B/elt and the qdot
  lowering stays a single ``tpu_custom_call``.

Hypothesis sweeps are importorskip-guarded (conftest convention,
matching ``test_mixed_gemm_props.py``): a missing extra collects as a
skip, never an error.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, hlo_rules
from repro.core import NVFP4, NVFP4_MICRO, MoRPolicy, mor_quantize
from repro.core.formats import (
    cast_to_nvfp4,
    decode_e2m1,
    encode_e2m1,
    round_to_e2m1,
)
from repro.core.mor import quantize_for_gemm
from repro.kernels import ops as kops
from repro.kernels import ref as kref

E2M1_GRID = [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0]


def _rand(shape, seed=0, scale=1.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def _nvfp4_friendly(shape, seed=0, span=9, dtype=jnp.bfloat16):
    """Data the four-way cascade genuinely sends to NVFP4: E2M1-grid
    magnitudes with per-16-element group scales spanning ~2^(2*span)
    (breaks the single per-block E4M3 scale, fine for micro scales).
    span=9 keeps the *realized* micro-group amax ratio around 2^18-2^20
    -- comfortably inside NVFP4_RANGE_RATIO = 12*448/2^-9 ~ 2^21.4 --
    so every block stays NVFP4-eligible (the pathological worst case,
    a lowest-scale group drawing sixteen 0.5s, would need ~(1/7)^16
    luck)."""
    rng = np.random.default_rng(seed)
    r, k = shape
    kp = -(-k // NVFP4_MICRO) * NVFP4_MICRO
    vals = np.asarray(E2M1_GRID[1:])[rng.integers(0, 7, (r, kp))]
    signs = np.where(rng.standard_normal((r, kp)) > 0, 1.0, -1.0)
    gs = np.exp2(
        rng.integers(-span, span + 1, (r, kp // NVFP4_MICRO))
    ).repeat(NVFP4_MICRO, axis=1)
    return jnp.asarray((signs * vals * gs)[:, :k], dtype)


# ------------------------------------------------------------- formats --
def test_round_to_e2m1_matches_ml_dtypes():
    ml_dtypes = pytest.importorskip("ml_dtypes")
    if not hasattr(ml_dtypes, "float4_e2m1fn"):
        pytest.skip("ml_dtypes has no float4_e2m1fn")
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.standard_normal(1 << 14).astype(np.float32) * 4,
        np.asarray([0.0, -0.0, 0.25, -0.25, 0.75, 2.5, 3.5, 5.0, -5.0,
                    6.0, 7.0, 1e6, -1e6, 1e-8], np.float32),
        np.asarray(E2M1_GRID, np.float32),
    ])
    mine = np.asarray(round_to_e2m1(jnp.asarray(x)))
    want = x.astype(ml_dtypes.float4_e2m1fn).astype(np.float32)
    np.testing.assert_array_equal(mine, want)


def test_e2m1_code_roundtrip_all_16():
    codes = jnp.arange(16, dtype=jnp.int32)
    vals = np.asarray(decode_e2m1(codes))
    mags = np.asarray(E2M1_GRID)
    np.testing.assert_array_equal(vals[:8], mags)
    np.testing.assert_array_equal(vals[8:], -mags)
    # encode inverts decode on every non-(-0) grid value.
    back = np.asarray(encode_e2m1(jnp.asarray(vals)))
    back_vals = np.asarray(decode_e2m1(jnp.asarray(back)))
    np.testing.assert_array_equal(back_vals, vals)


def test_cast_to_nvfp4_exact_on_grid_multiples():
    """group_scale * E2M1-grid data with power-of-two micro scales is
    representable exactly (micro scale d = amax/6 is a power of two --
    E4M3-exact)."""
    x = np.zeros((4, 32), np.float32)
    for g in range(2):
        x[:, g * 16 : (g + 1) * 16] = (
            np.asarray(E2M1_GRID * 2)[: 16] * 2.0 ** (4 * g - 2)
        )
    got = np.asarray(cast_to_nvfp4(jnp.asarray(x)))
    np.testing.assert_array_equal(got, x)


def test_cast_to_nvfp4_zero_and_ragged():
    # All-zero input stays zero; non-16-divisible last axes pad
    # internally and slice back.
    for k in (1, 7, 16, 17, 40):
        x = jnp.zeros((3, k), jnp.float32)
        got = cast_to_nvfp4(x)
        assert got.shape == (3, k)
        np.testing.assert_array_equal(np.asarray(got), 0.0)
    x = _rand((5, 23), seed=3)
    assert cast_to_nvfp4(x).shape == (5, 23)


def test_nvfp4_formatspec_two_level_target():
    assert NVFP4.amax == 448.0 * 6.0
    assert NVFP4.bits == 4


# ------------------------------------------------- selection + parity ---
@pytest.mark.parametrize("algo", ["gam", "e8m0", "fp32_amax"])
def test_sub4_select_interpret_matches_xla(algo):
    x = _nvfp4_friendly((256, 384), seed=4)
    y0, s0 = mor_quantize(x, MoRPolicy(recipe="sub4", algo=algo,
                                       backend="xla"))
    y1, s1 = mor_quantize(x, MoRPolicy(recipe="sub4", algo=algo,
                                       backend="interpret"))
    np.testing.assert_array_equal(
        np.asarray(y0, np.float32), np.asarray(y1, np.float32)
    )
    np.testing.assert_allclose(
        np.asarray(s0), np.asarray(s1), rtol=1e-6, atol=1e-7
    )


def test_sub4_selects_nvfp4_where_it_wins():
    """The cascade sends micro-structured wide-range blocks to NVFP4
    and plain gaussian blocks to the fp8 cascade -- the dynamic escape
    hatch static sub-byte assignment lacks."""
    x_nv = _nvfp4_friendly((128, 128), seed=5)
    _, s = mor_quantize(x_nv, MoRPolicy(recipe="sub4", backend="xla"))
    assert float(s[8]) == 1.0  # frac_nvfp4
    assert float(s[9]) == pytest.approx(1.0 / NVFP4_MICRO)
    x_g = _rand((128, 128), seed=6, dtype=jnp.bfloat16)
    _, s = mor_quantize(x_g, MoRPolicy(recipe="sub4", backend="xla"))
    assert float(s[8]) == 0.0
    assert float(s[3]) == 1.0  # gaussian block stays E4M3


@pytest.mark.parametrize("shape", [(256, 384), (100, 130), (31, 47),
                                   (128, 16)])
@pytest.mark.parametrize("algo", ["gam", "e8m0"])
def test_pack_decodes_to_fake_quant_bit_exact(shape, algo):
    """quantize_for_gemm payload lanes (packed nibbles + micro scales)
    decode to the fake-quantization output bit-for-bit, odd shapes
    included (sub4 aligns blocks to (2, 16) and zero-pads)."""
    x = _nvfp4_friendly(shape, seed=sum(shape), span=8)
    pol = MoRPolicy(recipe="sub4", algo=algo, backend="xla")
    y, stats = mor_quantize(x, pol)
    mo, stats2 = quantize_for_gemm(x, pol)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats2))
    np.testing.assert_array_equal(
        np.asarray(mo.dequant(), np.float32), np.asarray(y, np.float32)
    )


def test_pack_all_zero_blocks():
    x = jnp.zeros((128, 128), jnp.bfloat16)
    mo, stats = quantize_for_gemm(x, MoRPolicy(recipe="sub4",
                                               backend="xla"))
    np.testing.assert_array_equal(
        np.asarray(mo.dequant(), np.float32), 0.0
    )
    assert np.isfinite(np.asarray(stats)).all()


def test_sub4_pack_rejects_incapable_block():
    x = _rand((64, 64), dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="even-row"):
        quantize_for_gemm(
            x, MoRPolicy(recipe="sub4", block_shape=(63, 64),
                         backend="xla")
        )


def test_transpose_rejects_nvfp4_pack():
    x = _nvfp4_friendly((128, 128), seed=7)
    mo, _ = quantize_for_gemm(x, MoRPolicy(recipe="sub4", backend="xla"))
    assert (np.asarray(mo.tags) == kref.TAG_NVFP4).any()
    with pytest.raises(AssertionError, match="NVFP4"):
        mo.transpose()


# ------------------------------------------------------- mixed GEMM -----
@pytest.mark.parametrize("compact", [False, True])
def test_mixed_gemm_nvfp4_interpret_matches_xla(compact):
    x = _nvfp4_friendly((128, 256), seed=8)
    w = _nvfp4_friendly((192, 256), seed=9)
    pol = MoRPolicy(recipe="sub4", backend="xla")
    a, _ = quantize_for_gemm(x, pol)
    b, _ = quantize_for_gemm(w, pol)
    if compact:
        a, b = a.compact(), b.compact()
    got = kops.mixed_gemm(a, b, out_dtype=jnp.float32,
                          backend="interpret")
    want = kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mixed_gemm_nvfp4_against_dense_reference():
    """Decoded-operand dense matmul == mixed GEMM (f32 accumulation
    reassociation only)."""
    x = _nvfp4_friendly((64, 128), seed=10, span=4)
    w = _nvfp4_friendly((64, 128), seed=11, span=4)
    pol = MoRPolicy(recipe="sub4", backend="xla")
    a, _ = quantize_for_gemm(x, pol)
    b, _ = quantize_for_gemm(w, pol)
    got = np.asarray(
        kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="xla")
    )
    A = np.asarray(a.dequant(), np.float32)
    B = np.asarray(b.dequant(), np.float32)
    np.testing.assert_allclose(got, A @ B.T, rtol=1e-5, atol=1e-4)


def test_sub4_mor_dot_grads_interpret_match_xla():
    """Acceptance: the fused sub4 training path -- fwd + custom_vjp
    dgrad/wgrad (which re-packs the transposed views; NVFP4 is not
    transpose-invariant) -- is bit-exact between the Pallas kernel
    bodies (interpret) and the XLA reference."""
    from repro.core import mor_dot, new_token, paper_default

    x = _nvfp4_friendly((48, 128), seed=20, span=6)
    w = _nvfp4_friendly((96, 128), seed=21, span=6).T  # (K, N)

    def outputs(backend):
        base = paper_default("sub4")
        pol = base.replace(
            act=base.act.replace(backend=backend),
            weight=base.weight.replace(backend=backend),
            grad=base.grad.replace(backend=backend),
            fuse_gemm=True,
        )

        def loss(xa, wa, tok):
            y, st = mor_dot(xa, wa, tok, pol)
            return jnp.sum(y.astype(jnp.float32) ** 2), (y, st)

        grad_fn = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                     has_aux=True)
        (_, (y, st)), (gx, gw, gtok) = grad_fn(x, w, new_token())
        return y, st, gx, gw, gtok

    y0, st0, gx0, gw0, gt0 = outputs("xla")
    y1, st1, gx1, gw1, gt1 = outputs("interpret")
    for a, b in ((y0, y1), (gx0, gx1), (gw0, gw1)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )
    np.testing.assert_allclose(np.asarray(st0), np.asarray(st1),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(gt0), np.asarray(gt1),
                               rtol=1e-6, atol=1e-7)


# ----------------------------------------------------------- serving ----
def test_fully_nvfp4_qtensor_bytes_per_element():
    """Acceptance: <= 0.6 B/elt on a fully-NVFP4 weight (0.5 B packed
    nibbles + 1/16 B micro scales + compact don't-care lanes + grids)."""
    from repro.serve.quantized import qdot, quantize_weight

    K, N = 2048, 1024
    w = _nvfp4_friendly((N, K), seed=12).T  # (K, N) weight
    qt, info = quantize_weight(
        jnp.asarray(w, jnp.bfloat16), MoRPolicy(recipe="sub4",
                                                backend="xla")
    )
    assert info["frac_nvfp4"] == 1.0
    bpe = qt.nbytes / (K * N)
    assert bpe <= 0.6, bpe
    # And it still serves, bit-exactly across backends.
    x = _rand((4, K), seed=13, dtype=jnp.bfloat16)
    y0 = qdot(x, qt, backend="xla")
    y1 = qdot(x, qt, backend="interpret")
    np.testing.assert_array_equal(
        np.asarray(y0, np.float32), np.asarray(y1, np.float32)
    )


# ------------------------------------------------- TPU cross-lowering ---
def _check_contract(name):
    """Evaluate a registry contract; a failed TPU lowering raises."""
    report = contracts.check(name)
    assert report.ok, report.render()
    return report


def test_sub4_select_kernel_lowers_for_tpu():
    """The fused four-way selection stays one tpu_custom_call
    (``mor_quantize_sub4`` in the contract registry)."""
    _check_contract("mor_quantize_sub4")


def test_sub4_qdot_lowers_to_single_launch():
    """Acceptance: ONE tpu_custom_call per serving GEMM against a
    fully-NVFP4 weight (``qdot_sub4`` in the contract registry), and
    the probe weight really is fully quantized."""
    from repro.serve.quantized import quantize_weight

    w = _nvfp4_friendly((256, 256), seed=15).T
    qt, _ = quantize_weight(
        jnp.asarray(w, jnp.bfloat16), MoRPolicy(recipe="sub4",
                                                backend="xla")
    )
    assert qt.frac_quantized == 1.0
    _check_contract("qdot_sub4")


# Hypothesis property sweeps live in test_nvfp4_props.py behind the
# whole-module importorskip guard (conftest convention).
