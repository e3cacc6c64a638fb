"""Mixed-representation block GEMM: differential suite.

Pins the three lowerings of ``repro.kernels.ops.mixed_gemm`` --
pallas-interpret (real kernel body), the pure-jnp reference, and the
``backend='xla'`` dispatch -- bit-exact against each other across tag
patterns, shapes (including block-non-divisible, handled by the packing
layer's zero padding), and stored dtypes; plus packing round-trips,
serving (QTensor / qdot) round-trips, and TPU cross-lowering
regressions (the acceptance criterion: ONE ``tpu_custom_call`` per
GEMM).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, hlo_rules
from repro.core import MoRPolicy, mor_quantize
from repro.core.mor import quantize_for_gemm
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.mixed_gemm import mixed_gemm_blocks
from repro.kernels.ref import (
    TAG_BF16,
    TAG_E4M3,
    TAG_E5M2,
    MixedOperand,
    decode_mixed_ref,
    pack_mixed,
    passthrough_mixed,
)


def _rand(shape, seed=0, scale=1.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def _tags(pattern: str, nr: int, nk: int, seed: int = 0) -> jnp.ndarray:
    if pattern == "all_e4m3":
        t = np.full((nr, nk), TAG_E4M3)
    elif pattern == "all_e5m2":
        t = np.full((nr, nk), TAG_E5M2)
    elif pattern == "all_bf16":
        t = np.full((nr, nk), TAG_BF16)
    elif pattern == "checkerboard":
        t = np.indices((nr, nk)).sum(0) % 3
    elif pattern == "random":
        t = np.random.default_rng(seed).integers(0, 3, (nr, nk))
    else:
        raise ValueError(pattern)
    return jnp.asarray(t, jnp.int32)


def _pack(shape, pattern, seed, dtype, block=128, scale=2.0):
    x = _rand(shape, seed=seed, scale=scale, dtype=dtype)
    br = min(block, shape[0])
    bk = min(block, shape[1])
    nr, nk = -(-shape[0] // br), -(-shape[1] // bk)
    tags = _tags(pattern, nr, nk, seed)
    return pack_mixed(x, tags, (br, bk), "gam"), x


# --------------------------------------------------- backend equivalence --
@pytest.mark.parametrize(
    "pattern", ["all_e4m3", "all_bf16", "checkerboard", "random"]
)
@pytest.mark.parametrize(
    "mnk", [(128, 128, 128), (256, 128, 384), (100, 96, 130), (64, 257, 200)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mixed_gemm_backends_bit_exact(pattern, mnk, dtype):
    M, N, K = mnk
    seed = sum(mnk) + len(pattern)
    a, _ = _pack((M, K), pattern, seed, dtype)
    b, _ = _pack((N, K), pattern, seed + 1, dtype)
    got_i = kops.mixed_gemm(a, b, out_dtype=jnp.float32,
                            backend="interpret")
    got_x = kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="xla")
    want = kref.mixed_gemm_ref(a, b, jnp.float32)
    assert got_i.shape == (M, N)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want))


def test_mixed_gemm_matches_plain_dot_when_all_bf16():
    """All-passthrough packs must reproduce the dense f32 block matmul."""
    x = _rand((100, 260), seed=3, dtype=jnp.float32)
    w = _rand((96, 260), seed=4, dtype=jnp.float32)
    a = passthrough_mixed(x, (128, 128))
    b = passthrough_mixed(w, (128, 128))
    got = kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="interpret")
    want = np.asarray(x) @ np.asarray(w).T
    # Block-wise K accumulation vs one dense dot: f32 ordering tolerance.
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-4)


def test_mixed_gemm_fp8_fidelity():
    """Quantized blocks approximate the dense product (fp8 fidelity)."""
    a, x = _pack((256, 256), "all_e4m3", 7, jnp.float32)
    b, w = _pack((128, 256), "all_e4m3", 8, jnp.float32)
    got = np.asarray(
        kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="interpret")
    )
    exact = np.asarray(x) @ np.asarray(w).T
    rel = np.abs(got - exact) / (np.abs(exact) + 1e-2)
    assert np.median(rel) < 0.1


@pytest.mark.slow
@pytest.mark.parametrize("pattern", ["random", "checkerboard"])
def test_mixed_gemm_large_shape_interpret(pattern):
    """Training-scale tile grid (8x4x8 blocks) through the real kernel
    body: interpret vs ref bit-exact. Slow lane (--runslow)."""
    a, _ = _pack((1024, 1024), pattern, 31, jnp.bfloat16)
    b, _ = _pack((512, 1024), pattern, 32, jnp.bfloat16)
    got = kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="interpret")
    want = kref.mixed_gemm_ref(a, b, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------ packing contract --
@pytest.mark.parametrize("recipe", ["tensor", "sub2", "sub3", "e4m3", "off"])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_pack_decodes_to_fake_quant_bit_exact(recipe, backend):
    """decode(quantize_for_gemm(x)) == mor_quantize(x) bit-for-bit: the
    payload layout loses nothing relative to the fake-quant path."""
    x = _rand((100, 130), seed=len(recipe), scale=2.5, dtype=jnp.bfloat16)
    pol = MoRPolicy(recipe=recipe, partition="block", backend=backend)
    y, stats = mor_quantize(x, pol)
    mo, stats2 = quantize_for_gemm(x, pol)
    np.testing.assert_allclose(
        np.asarray(stats), np.asarray(stats2), rtol=1e-6, atol=1e-7
    )
    np.testing.assert_array_equal(
        np.asarray(mo.dequant(), np.float32), np.asarray(y, np.float32)
    )


def test_pack_transpose_is_exact_for_square_blocks():
    x = _rand((256, 384), seed=9, dtype=jnp.bfloat16)
    mo, _ = quantize_for_gemm(
        x, MoRPolicy(recipe="sub3", partition="block", backend="xla")
    )
    moT, _ = quantize_for_gemm(
        x.T, MoRPolicy(recipe="sub3", partition="block", backend="xla")
    )
    t = mo.transpose()
    np.testing.assert_array_equal(np.asarray(t.tags), np.asarray(moT.tags))
    np.testing.assert_array_equal(
        np.asarray(t.scales), np.asarray(moT.scales)
    )
    np.testing.assert_array_equal(
        np.asarray(t.payload_q), np.asarray(moT.payload_q)
    )


def test_quantize_for_gemm_rejects_non_block_partitions():
    x = _rand((64, 128), seed=1)
    with pytest.raises(ValueError, match="partition='block'"):
        quantize_for_gemm(x, MoRPolicy(recipe="sub3", partition="channel"))


def test_pack_padding_blocks_contribute_zero():
    """Padded rows/cols must not leak into the product."""
    M, N, K = 100, 96, 130  # pads to 128 / 128 / 256
    a, xa = _pack((M, K), "checkerboard", 11, jnp.float32)
    b, xb = _pack((N, K), "checkerboard", 12, jnp.float32)
    dec_a = np.asarray(decode_mixed_ref(a))
    assert (dec_a[M:] == 0).all() and (dec_a[:, K:] == 0).all()
    got = kops.mixed_gemm(a, b, out_dtype=jnp.float32, backend="interpret")
    want = dec_a[:M, :K].astype(np.float32) @ np.asarray(
        decode_mixed_ref(b)
    )[:N, :K].astype(np.float32).T
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


# ------------------------------------------------------- serving / qdot --
def test_qdot_roundtrip_within_policy_threshold():
    """quantize_params -> sub-tensor QTensor -> qdot vs dense bf16."""
    from repro.serve.quantized import quantize_params

    rng = np.random.default_rng(21)
    params = {
        "proj": jnp.asarray(rng.standard_normal((256, 192)), jnp.bfloat16),
        "tiny": jnp.asarray(rng.standard_normal((4, 4)), jnp.bfloat16),
    }
    pol = MoRPolicy(recipe="sub3", partition="block", backend="xla",
                    threshold=0.045)
    qparams, stats = quantize_params(params, pol, min_size=1024)
    from repro.serve.quantized import QTensor, qdot

    assert isinstance(qparams["proj"], QTensor)
    assert not isinstance(qparams["tiny"], QTensor)  # below min_size
    x = jnp.asarray(rng.standard_normal((16, 256)), jnp.bfloat16)
    y = qdot(x, qparams["proj"], backend="interpret")
    y_dense = (
        x.astype(jnp.float32) @ params["proj"].astype(jnp.float32)
    )
    err = np.abs(
        np.asarray(y, np.float32) - np.asarray(y_dense)
    ) / (np.abs(np.asarray(y_dense)) + 1e-2)
    # Per-element relative error of an fp8-quantized GEMM: bounded by
    # ~sqrt(K)*eps aggregation; the policy threshold bounds the per-
    # element operand error at 4.5%.
    assert np.median(err) < pol.threshold
    # And qdot must agree with the explicit dequantized product.
    y_deq = x.astype(jnp.float32) @ qparams[
        "proj"
    ].mo.dequant().T.astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_deq), rtol=2e-2, atol=2e-1
    )


def test_qtensor_survives_jit_donation():
    from repro.serve.quantized import qdot, quantize_weight

    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)
    qt, _ = quantize_weight(
        w, MoRPolicy(recipe="sub3", partition="block", backend="xla")
    )
    # Round-trip through flatten/unflatten.
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    qt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert qt2.shape == qt.shape and qt2.mo.block == qt.mo.block
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.bfloat16)
    f = jax.jit(
        lambda q, a: qdot(a, q, backend="xla"), donate_argnums=(0,)
    )
    y0 = qdot(x, qt, backend="xla")
    y1 = f(qt2, x)
    np.testing.assert_array_equal(
        np.asarray(y0, np.float32), np.asarray(y1, np.float32)
    )


def test_qtensor_tensor_recipe_accept_reject():
    """The legacy all-or-nothing behaviour survives as recipe='tensor'."""
    from repro.serve.quantized import quantize_weight

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    qt, st = quantize_weight(w, MoRPolicy(recipe="tensor", backend="xla"))
    assert qt.is_quantized and st["quantized"] == 1.0
    assert (np.asarray(qt.tags) == TAG_E4M3).all()
    bad = jnp.asarray(
        np.exp2(rng.uniform(-30, 30, (256, 128))).astype(np.float32)
    )
    qt2, st2 = quantize_weight(
        bad, MoRPolicy(recipe="tensor", backend="xla")
    )
    assert not qt2.is_quantized and st2["quantized"] == 0.0
    assert (np.asarray(qt2.tags) == TAG_BF16).all()


def test_qtensor_sub3_mixes_representations():
    """A weight with per-block heterogeneous ranges actually mixes tags."""
    from repro.serve.quantized import quantize_weight

    rng = np.random.default_rng(5)
    w = np.asarray(rng.standard_normal((256, 256)), np.float32)
    # Block column 1: E5M2-shaped data (wide but in-range log-uniform).
    w[:, 128:] = 2.0 ** rng.uniform(-25.0, 2.0, (256, 128))
    qt, st = quantize_weight(
        jnp.asarray(w), MoRPolicy(recipe="sub3", backend="xla")
    )
    tags = np.asarray(qt.tags)
    assert (tags != tags.flat[0]).any(), f"expected mixed tags, got {tags}"


def test_quantize_params_skips_norm_scales_and_routers():
    """Regression: stacked norm scales are 2-D ('blocks/.../ln1/scale',
    (L, d)) and routers are 3-D -- both must stay dense or the layer
    scan crashes at prefill."""
    from repro.serve.quantized import QTensor, quantize_params

    rng = np.random.default_rng(0)
    params = {
        "blocks": {
            "dense": {
                "ln1": {"scale": jnp.ones((4, 512), jnp.float32)},
                "wqkv": jnp.asarray(
                    rng.standard_normal((4, 128, 384)), jnp.bfloat16
                ),
                "moe": {"router": jnp.ones((4, 128, 8), jnp.float32)},
            }
        },
        "embed": jnp.ones((512, 128), jnp.bfloat16),
    }
    q, stats = quantize_params(
        params, MoRPolicy(recipe="sub3", backend="xla"), min_size=1024
    )
    assert list(stats) == ["blocks/dense/wqkv"]
    assert isinstance(q["blocks"]["dense"]["wqkv"], QTensor)
    assert not isinstance(q["blocks"]["dense"]["ln1"]["scale"], QTensor)
    assert not isinstance(q["blocks"]["dense"]["moe"]["router"], QTensor)
    assert not isinstance(q["embed"], QTensor)


def test_stacked_qtensor_scan_slices_and_matches_dense():
    """A layer-stacked QTensor sliced by lax.scan feeds mor_dot's
    serving path per layer, matching per-layer qdot."""
    from repro.core import mor_dot, new_token, paper_default
    from repro.serve.quantized import (
        qdot,
        quantize_weight,
        quantize_weight_stacked,
    )

    rng = np.random.default_rng(13)
    w3 = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.bfloat16)
    qt, st = quantize_weight_stacked(
        w3, MoRPolicy(recipe="sub3", backend="xla")
    )
    assert qt.is_stacked and st["quantized"] == 1.0
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.bfloat16)
    pol = paper_default("sub3")

    def body(carry, qw):
        y, _ = mor_dot(x, qw, new_token(), pol)
        return carry, y

    _, ys = jax.lax.scan(body, 0, qt)
    for l in range(3):
        qt_l, _ = quantize_weight(
            w3[l], MoRPolicy(recipe="sub3", backend="xla")
        )
        want = qdot(x, qt_l)
        np.testing.assert_allclose(
            np.asarray(ys[l], np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-1,
        )
    # Stacked dequant approximates the dense stack.
    deq = np.asarray(qt.dequant(), np.float32)
    rel = np.abs(deq - np.asarray(w3, np.float32)) / (
        np.abs(np.asarray(w3, np.float32)) + 1e-2
    )
    assert np.median(rel) < 0.05


def test_compact_halves_fully_quantized_storage():
    """A fully-fp8 weight's bf16 buffer collapses to one block: stored
    bytes ~ half of dense bf16 (plus tag/scale metadata)."""
    from repro.serve.quantized import quantize_weight

    rng = np.random.default_rng(17)
    w = jnp.asarray(rng.standard_normal((512, 512)), jnp.bfloat16)
    qt, st = quantize_weight(
        w, MoRPolicy(recipe="e4m3", partition="block", backend="xla")
    )
    assert st["frac_bf16"] == 0.0
    dense = w.size * 2
    assert qt.nbytes < 0.65 * dense, (qt.nbytes, dense)
    # And the compact pack still decodes / multiplies correctly.
    x = jnp.asarray(rng.standard_normal((16, 512)), jnp.bfloat16)
    from repro.serve.quantized import qdot

    y_i = qdot(x, qt, backend="interpret")
    y_x = qdot(x, qt, backend="xla")
    np.testing.assert_array_equal(
        np.asarray(y_i, np.float32), np.asarray(y_x, np.float32)
    )


def test_activation_row_block_decode_shapes():
    """Decode-sized activations (a few rows) must not be padded to a
    full 128-row block on the serving hot path."""
    from repro.kernels.ref import activation_row_block

    assert activation_row_block(4, 128) == 16
    assert activation_row_block(100, 128) == 112
    assert activation_row_block(512, 128) == 128
    from repro.serve.quantized import qdot, quantize_weight

    rng = np.random.default_rng(19)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)
    qt, _ = quantize_weight(
        w, MoRPolicy(recipe="sub3", backend="xla")
    )
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.bfloat16)
    y = qdot(x, qt, backend="interpret")
    want = qdot(x, qt, backend="xla")
    assert y.shape == (4, 128)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(want, np.float32)
    )


# ------------------------------------------------- TPU cross-lowering ----
def _check_contract(name):
    report = contracts.check(name)
    assert report.ok, report.render()
    return report


def test_mixed_gemm_kernel_lowers_for_tpu_single_launch():
    """Acceptance criterion: ONE tpu_custom_call per mixed GEMM."""
    a, _ = _pack((256, 256), "checkerboard", 0, jnp.bfloat16)
    b, _ = _pack((128, 256), "checkerboard", 1, jnp.bfloat16)

    def f(aq, abf, anib, ams, at, asc, bq, bbf, bnib, bms, bt, bsc):
        return mixed_gemm_blocks(
            aq, abf, anib, ams, at, asc, bq, bbf, bnib, bms, bt, bsc,
            block=(128, 128, 128), out_dtype=jnp.bfloat16,
        )

    txt = hlo_rules.tpu_lowering_text(
        f, a.payload_q, a.payload_bf16, a.payload_nib, a.micro_scales,
        a.tags, a.scales,
        b.payload_q, b.payload_bf16, b.payload_nib, b.micro_scales,
        b.tags, b.scales,
    )
    assert hlo_rules.count_custom_calls(txt) == 1
    # The registry's mixed_gemm contract carries the same pin plus the
    # f32-accumulation and payload-taint rules.
    _check_contract("mixed_gemm")


def test_qdot_lowers_to_single_launch():
    """Sub-tensor qdot: the whole serving GEMM is one fused kernel
    (``qdot_sub3`` in the contract registry)."""
    _check_contract("qdot_sub3")


def test_fused_mor_dot_fwd_launch_count():
    """mor_dot(fuse_gemm=True) forward: 2 selection kernels + 1 GEMM
    kernel -- the GEMM itself is a single tpu_custom_call."""
    from repro.core import mor_dot, new_token, paper_default

    p = paper_default("sub3").replace(fuse_gemm=True)
    p = p.replace(
        act=p.act.replace(backend="pallas"),
        weight=p.weight.replace(backend="pallas"),
        grad=p.grad.replace(backend="pallas"),
    )
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((128, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)

    txt = hlo_rules.tpu_lowering_text(
        lambda a, b: mor_dot(a, b, new_token(), p)[0], x, w
    )
    # One fused launch per event: 2 selection events + 1 GEMM, with
    # dedup latitude -- the pin is MOR_DOT_FWD_LAUNCHES in the
    # contract registry (also checked as ``mor_dot_fused_fwd``).
    lo, hi = contracts.MOR_DOT_FWD_LAUNCHES
    assert lo <= hlo_rules.count_custom_calls(txt) <= hi
    _check_contract("mor_dot_fused_fwd")
    _check_contract("mor_dot_fused_grads")
