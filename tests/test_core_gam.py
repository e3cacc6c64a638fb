"""Unit + property tests for GAM scaling (Algorithm 1)."""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the 'hypothesis' test extra"
)
hnp = pytest.importorskip("hypothesis.extra.numpy")
st = pytest.importorskip("hypothesis.strategies")
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    E4M3,
    E5M2,
    PER_BLOCK_128,
    PER_CHANNEL,
    PER_TENSOR,
    Partition,
    compute_scales,
    split_mantissa_exponent,
)
from repro.core.partition import block_amax


@pytest.fixture(autouse=True)
def _f32_numerics():
    # The GAM mantissa-split tables below assume f32 math; pin it per
    # test instead of mutating global config at import time (MOR004).
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", prev)


PARTS = [PER_TENSOR, PER_BLOCK_128, PER_CHANNEL, Partition("block", (64, 64)),
         Partition("subchannel", sub=32)]


def test_split_mantissa_exponent_roundtrip():
    s = jnp.array([1.0, 0.75, 448.0, 3.1e-5, 1e8, 2.0, 1.9999999], jnp.float32)
    m, e = split_mantissa_exponent(s)
    np.testing.assert_allclose(
        np.asarray(m) * np.exp2(np.asarray(e, np.float64)), np.asarray(s),
        rtol=1e-6,
    )
    assert np.all(np.asarray(m) >= 1.0) and np.all(np.asarray(m) < 2.0)


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("algo", ["gam", "e8m0", "fp32_amax"])
def test_no_saturation_invariant(part, algo):
    """block_amax * scale <= q_amax for every block (the Alg. 1 guarantee)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((256, 384)) * np.exp(rng.uniform(-20, 20, (256, 384))),
        jnp.float32,
    )
    for fmt in (E4M3, E5M2):
        sc = compute_scales(x, part, fmt, algo=algo)
        bmax = block_amax(x, part)
        scaled = np.asarray(bmax) * np.asarray(sc.scale)
        assert np.all(scaled <= fmt.amax * (1 + 1e-6)), (
            f"{algo}/{fmt.name}: max scaled amax {scaled.max()}"
        )


def test_gam_shared_mantissa():
    """Every reconstructed block scale shares the group mantissa m_g."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    sc = compute_scales(x, PER_BLOCK_128, E4M3, algo="gam")
    m, _ = split_mantissa_exponent(sc.scale.reshape(-1))
    np.testing.assert_allclose(
        np.asarray(m), float(sc.group_mantissa), rtol=1e-6
    )


def test_group_amax_preserved_exactly():
    """Per-tensor GAM scale maps the tensor amax to exactly fmt.amax."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    sc = compute_scales(x, PER_TENSOR, E4M3, algo="gam")
    amax_scaled = float(sc.group_amax) * float(sc.scale[0, 0])
    # GAM preserves the full fp32 mantissa of s_g; per-tensor (single block)
    # the reconstruction equals s_g, so amax maps to q_amax exactly.
    np.testing.assert_allclose(amax_scaled, E4M3.amax, rtol=1e-6)


def test_exponent_clamp_edges_no_double_rounding():
    """Regression for the e8m0/gam clamp asymmetry: e_b was clipped to
    [-126, 126] while exp2i supports [-126, 127], so a tiny-amax block
    whose ideal exponent is 127 got its scale needlessly halved (double
    rounding). Both clamp edges must reconstruct exactly and keep the
    no-saturation invariant."""
    from repro.core.gam import exp2i, scales_from_bmax

    # exp2i is exact at both edges of the E8M0 domain.
    e = jnp.asarray([-126, 127], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(exp2i(e), np.float64), [2.0**-126, 2.0**127]
    )

    # Upper edge: bmax = 2^-119 gives ideal s_b = 448 * 2^119 ~ 2^127.8
    # -> e_b = 127 exactly (previously clipped to 126, halving the
    # scale and costing one bit of quantization precision for nothing).
    bmax = jnp.asarray([[2.0**-119, 1.0]], jnp.float32)
    for algo in ("e8m0", "gam"):
        sc = scales_from_bmax(bmax, E4M3, algo)
        assert int(np.asarray(sc.block_exp)[0, 0]) == 127, algo
        scale = np.asarray(sc.scale, np.float64)
        assert np.all(np.isfinite(scale)) and np.all(scale > 0)
        # No-saturation invariant holds at the clamp edge.
        scaled = np.asarray(bmax, np.float64) * scale
        assert np.all(scaled <= E4M3.amax * (1 + 1e-6)), (algo, scaled)
    # e8m0 now reconstructs the full-power scale (the double-rounding
    # fix): 2^127, not 2^126.
    sc = scales_from_bmax(bmax, E4M3, "e8m0")
    assert float(np.asarray(sc.scale)[0, 0]) == 2.0**127

    # Lower edge: the largest finite f32 bmax gives the most negative
    # ideal exponent reachable in-range; the invariant must hold there
    # too (the -126 clamp side is unreachable with finite f32 inputs
    # but exp2i's edge exactness above pins it).
    bmax_lo = jnp.asarray([[3.0e38]], jnp.float32)
    for fmt in (E4M3, E5M2):
        for algo in ("e8m0", "gam"):
            sc = scales_from_bmax(bmax_lo, fmt, algo)
            scaled = np.asarray(bmax_lo, np.float64) * np.asarray(
                sc.scale, np.float64
            )
            assert np.all(scaled <= fmt.amax * (1 + 1e-6)), (fmt.name, algo)


def test_zero_tensor_scales_are_finite():
    x = jnp.zeros((128, 128), jnp.float32)
    for algo in ("gam", "e8m0", "fp32_amax"):
        sc = compute_scales(x, PER_BLOCK_128, E4M3, algo=algo)
        assert np.all(np.isfinite(np.asarray(sc.scale)))


@hypothesis.settings(deadline=None, max_examples=25)
@hypothesis.given(
    data=hnp.arrays(
        np.float32,
        hnp.array_shapes(min_dims=2, max_dims=2, min_side=1, max_side=80),
        elements=st.floats(
            min_value=-(2.0**90), max_value=2.0**90, allow_nan=False, width=32
        ),
    ),
    algo=st.sampled_from(["gam", "e8m0"]),
    kind=st.sampled_from(["tensor", "block", "channel"]),
)
# The smallest normal f32 amax: 448 / amax overflows f32.
@hypothesis.example(
    data=np.full((1, 1), np.finfo(np.float32).tiny, np.float32),
    algo="gam", kind="tensor",
)
def test_property_no_saturation(data, algo, kind):
    part = Partition(kind, (32, 32))
    x = jnp.asarray(data)
    sc = compute_scales(x, part, E4M3, algo=algo)
    bmax = np.asarray(block_amax(x, part), np.float64)
    scale = np.asarray(sc.scale, np.float64)
    assert np.all(bmax * scale <= E4M3.amax * (1 + 1e-6))
    assert np.all(np.isfinite(scale)) and np.all(scale > 0)


# ------------------------------------------------------------------------
# fp8 grid snap: bit arithmetic, bit-identical to the saturating cast.
# ------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [E4M3, E5M2], ids=lambda f: f.name)
def test_round_to_fp8_matches_ml_dtypes(fmt):
    import ml_dtypes

    from repro.core.formats import cast_to_format

    dt = {"e4m3": ml_dtypes.float8_e4m3fn, "e5m2": ml_dtypes.float8_e5m2}[
        fmt.name
    ]
    rng = np.random.default_rng(0)
    n = 1 << 16
    grid = np.arange(256, dtype=np.uint8).view(dt).astype(np.float32)
    grid = np.sort(grid[np.isfinite(grid)])
    mids = (grid[1:] + grid[:-1]) / 2  # ties: round half to even
    x = np.concatenate([
        np.exp2(rng.uniform(-25, 17, n)).astype(np.float32)
        * rng.choice([-1.0, 1.0], n).astype(np.float32),
        grid, mids, np.nextafter(mids, np.inf), np.nextafter(mids, -np.inf),
        np.array([0.0, -0.0, 1e-45, np.inf, -np.inf, np.nan], np.float32),
    ])
    want = np.clip(x, -fmt.amax, fmt.amax).astype(dt).astype(np.float32)
    got = np.asarray(jax.jit(lambda v: cast_to_format(v, fmt))(x))
    same = (got.view(np.uint32) == want.view(np.uint32)) | (
        np.isnan(got) & np.isnan(want)
    )
    assert same.all(), x[~same][:8]
