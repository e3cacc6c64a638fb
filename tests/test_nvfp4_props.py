"""Hypothesis property sweeps for the NVFP4 (sub4) pack/unpack path.

Own module so the whole-module ``importorskip`` guard (conftest
convention: hypothesis is an optional test extra; a missing import must
collect as a skip, not an error) only removes the property sweeps --
the deterministic differential suite lives in ``test_nvfp4.py``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the 'hypothesis' test extra"
)
st = pytest.importorskip("hypothesis.strategies")

from repro.core import MoRPolicy, mor_quantize
from repro.core.formats import round_to_e2m1
from repro.core.mor import quantize_for_gemm

from test_nvfp4 import _nvfp4_friendly

_F32_1E30 = float(np.float32(1e30))


@hypothesis.settings(deadline=None, max_examples=20)
@hypothesis.given(
    m=st.integers(2, 140),
    k=st.integers(16, 300),
    seed=st.integers(0, 2**16),
    span=st.integers(0, 12),
    algo=st.sampled_from(["gam", "e8m0"]),
)
def test_property_pack_roundtrip(m, k, seed, span, algo):
    """Random shapes / group spans: the packed sub4 payload decodes to
    the fake-quant output bit-for-bit (odd shapes, ragged tails and
    all-zero micro-groups included)."""
    x = _nvfp4_friendly((m, k), seed=seed, span=span)
    pol = MoRPolicy(recipe="sub4", algo=algo, backend="xla")
    y, _ = mor_quantize(x, pol)
    mo, _ = quantize_for_gemm(x, pol)
    np.testing.assert_array_equal(
        np.asarray(mo.dequant(), np.float32), np.asarray(y, np.float32)
    )


@hypothesis.settings(deadline=None, max_examples=15)
@hypothesis.given(
    data=st.lists(
        # Bounds must be float32-exact for width=32: 1e30 rounded to f32.
        st.floats(min_value=-_F32_1E30, max_value=_F32_1E30,
                  allow_nan=False, width=32),
        min_size=1, max_size=64,
    )
)
def test_property_e2m1_matches_ml_dtypes(data):
    ml_dtypes = pytest.importorskip("ml_dtypes")
    if not hasattr(ml_dtypes, "float4_e2m1fn"):
        pytest.skip("ml_dtypes has no float4_e2m1fn")
    x = np.asarray(data, np.float32)
    mine = np.asarray(round_to_e2m1(jnp.asarray(x)))
    want = x.astype(ml_dtypes.float4_e2m1fn).astype(np.float32)
    np.testing.assert_array_equal(mine, want)
