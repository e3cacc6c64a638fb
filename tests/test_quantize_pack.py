"""One-pass fused quantize-to-payload (ISSUE 5 tentpole) differential
suite.

The pack-emitting variant of the selection kernel must be *byte
identical* to the two-pass oracle (fused select + ``ref.pack_mixed``)
on every lane of the mixed block layout -- payload bytes, BF16 buffer,
packed nibbles, micro-scale bytes, tags and reconstructed GAM scales --
across recipes x scaling algos x odd/padded shapes, plus:

* ``quantize_for_gemm`` still decodes to the fake-quantization output
  bit-for-bit and reports the identical stats vector (one shared
  decision path, now with zero re-derivation).
* The pallas lowering of a sub-tensor ``quantize_for_gemm`` is exactly
  one ``tpu_custom_call`` with no operand-sized XLA packing ops beyond
  what the bare selection already needs (the "no second pass" claim,
  pinned on the TPU cross-lowering).
* 4-device mesh invariance in the ``tests/test_sharded_mor.py`` style:
  shard-local fused packs are bit-identical to the single-device pack.
* Hypothesis sweeps (importorskip-guarded, conftest convention).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, hlo_rules
from repro.core.mor import mor_quantize, quantize_for_gemm
from repro.core.partition import Partition
from repro.core.policy import MoRPolicy
from repro.kernels import ops as kops
from repro.kernels import ref as kref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RECIPES = ("sub2", "sub3", "sub4")
ALGOS = ("gam", "e8m0", "fp32_amax")

PACK_LANES = ("payload_q", "payload_bf16", "payload_nib",
              "micro_scales", "tags", "scales")


def _mixed_tags(shape, seed=0, dtype=jnp.bfloat16):
    """Data engineered so the cascades genuinely mix all four tags:
    normal rows (E4M3), huge-dynamic-range rows (E5M2/BF16), E2M1-grid
    micro-structured rows (NVFP4 under sub4), and an all-zero stripe
    (the zero-block scale guard)."""
    rng = np.random.default_rng(seed)
    m, k = shape
    kp = -(-k // 16) * 16
    x = rng.standard_normal((m, kp))
    q = max(m // 4, 1)
    x[q:2 * q] *= np.exp2(rng.integers(-20, 20, (q, kp)))
    grid = np.array([0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
    mm = grid[rng.integers(0, 7, (q, kp))] * np.exp2(
        rng.integers(-9, 9, (q, kp // 16))
    ).repeat(16, axis=1)
    x[2 * q:3 * q] = mm * np.where(
        rng.standard_normal((q, kp)) > 0, 1.0, -1.0
    )
    x[-max(m // 8, 1):] = 0.0
    return jnp.asarray(x[:, :k], dtype)


def _assert_pack_equal(mo1, mo2, msg=""):
    assert mo1.block == mo2.block and mo1.shape == mo2.shape, msg
    for lane in PACK_LANES:
        a = np.asarray(getattr(mo1, lane))
        b = np.asarray(getattr(mo2, lane))
        if a.dtype == np.dtype(jnp.bfloat16):
            a, b = a.astype(np.float32), b.astype(np.float32)
        np.testing.assert_array_equal(a, b, err_msg=f"{msg} lane={lane}")


# ------------------------------------------------------ kernel parity --
@pytest.mark.parametrize("mode", RECIPES)
@pytest.mark.parametrize("algo", ALGOS)
def test_pack_bit_exact_vs_oracle(mode, algo):
    part = Partition("block", (64, 64), align=(2, 16))
    x = _mixed_tags((256, 128), seed=1)
    mo1, r1 = kref.quantize_pack_ref(x, part, mode, algo)
    mo2, r2 = kops.quantize_pack(x, part, mode, algo,
                                 backend="interpret")
    _assert_pack_equal(mo1, mo2, f"{mode}/{algo}")
    for f in ("sel", "e4_sums", "e5_sums", "counts"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r1, f)), np.asarray(getattr(r2, f)),
            err_msg=f"{mode}/{algo} {f}",
        )
    if mode == "sub4":
        np.testing.assert_array_equal(
            np.asarray(r1.nv_sums), np.asarray(r2.nv_sums)
        )
    # Real quantization never materializes the fake-quant output.
    assert r1.y is None and r2.y is None


@pytest.mark.parametrize(
    "shape", [(64, 64), (200, 100), (30, 18), (128, 192), (2, 16)]
)
def test_pack_odd_and_padded_shapes(shape):
    """Block-non-divisible operands pad inside the kernel path exactly
    like the oracle (zeros pack to zero bytes under the group-amax
    scale guard)."""
    part = Partition("block", (64, 64), align=(2, 16))
    x = _mixed_tags(shape, seed=2)
    for mode in RECIPES:
        mo1, _ = kref.quantize_pack_ref(x, part, mode, "gam")
        mo2, _ = kops.quantize_pack(x, part, mode, "gam",
                                    backend="interpret")
        _assert_pack_equal(mo1, mo2, f"{shape} {mode}")


def test_pack_all_zero_and_f32():
    part = Partition("block", (64, 64), align=(2, 16))
    for mode in RECIPES:
        z = jnp.zeros((128, 128), jnp.bfloat16)
        _assert_pack_equal(
            kref.quantize_pack_ref(z, part, mode, "gam")[0],
            kops.quantize_pack(z, part, mode, "gam",
                               backend="interpret")[0],
            f"zero {mode}",
        )
        xf = _mixed_tags((128, 64), seed=3, dtype=jnp.float32)
        _assert_pack_equal(
            kref.quantize_pack_ref(xf, part, mode, "gam")[0],
            kops.quantize_pack(xf, part, mode, "gam",
                               backend="interpret")[0],
            f"f32 {mode}",
        )


# -------------------------------------------------- recipe-level glue --
@pytest.mark.parametrize("recipe",
                         ("sub2", "sub3", "sub4", "tensor", "e4m3"))
def test_quantize_for_gemm_decode_and_stats(recipe):
    """The one-pass path keeps the two invariants of the shared
    decision path: identical stats vector to mor_quantize, and a pack
    that decodes to the fake-quant output bit-for-bit."""
    x = _mixed_tags((256, 128), seed=4)
    pol = MoRPolicy(recipe=recipe, partition="block", block_shape=(64, 64))
    y, s1 = mor_quantize(x, pol)
    mo, s2 = quantize_for_gemm(x, pol)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(mo.dequant(), np.float32)
    )


def test_quantize_for_gemm_backend_parity():
    """interpret (kernel body) vs xla (two-pass oracle) pack equality
    through the public recipe entry point."""
    x = _mixed_tags((192, 192), seed=5)
    for recipe in RECIPES:
        pol = MoRPolicy(recipe=recipe, partition="block",
                        block_shape=(64, 64))
        mo_i, s_i = quantize_for_gemm(x, pol.replace(backend="interpret"))
        mo_x, s_x = quantize_for_gemm(x, pol.replace(backend="xla"))
        _assert_pack_equal(mo_i, mo_x, recipe)
        np.testing.assert_array_equal(np.asarray(s_i), np.asarray(s_x))


def test_pack_has_nvfp4_hint():
    """The static hint the GEMM kernel keys its NVFP4 decode on: sub4
    packs carry it, three-way packs do not, and compact() refines it to
    the concrete truth."""
    x = _mixed_tags((128, 128), seed=6)
    mo3, _ = quantize_for_gemm(
        x, MoRPolicy(recipe="sub3", partition="block")
    )
    assert mo3.has_nvfp4 is False
    mo4, _ = quantize_for_gemm(
        x, MoRPolicy(recipe="sub4", partition="block")
    )
    assert mo4.has_nvfp4 is True
    # A sub4 pack whose blocks all fell through to other formats
    # compacts down to has_nvfp4=False (drops the dead decode).
    ones, _ = quantize_for_gemm(
        jnp.ones((128, 128), jnp.bfloat16),
        MoRPolicy(recipe="sub4", partition="block"),
    )
    c = ones.compact()
    assert c.has_nvfp4 == bool(
        (np.asarray(ones.tags) == kref.TAG_NVFP4).any()
    )
    assert kref.passthrough_mixed(x, (64, 64)).has_nvfp4 is False


# ------------------------------------------------------- HLO contract --
@pytest.mark.parametrize("recipe", ("sub3", "sub4"))
def test_pack_single_launch_no_xla_pack_pass(recipe):
    """quantize_for_gemm on the pallas backend is one tpu_custom_call,
    and packing adds *zero* operand-sized XLA ops over the bare
    selection (the old lowering re-blocked, re-scaled and re-cast the
    whole operand in XLA after the select). The pins live in the
    contract registry -- this test, bench_kernels and CI's lint job
    all evaluate the same ``quantize_pack_*`` contract."""
    report = contracts.check(f"quantize_pack_{recipe}")
    assert report.ok, report.render()

    # The two-pass oracle really is a multi-pass XLA program (sanity
    # check that the counter can see what we claim to have removed).
    part = Partition("block", (128, 128), align=(2, 16))
    x = jnp.zeros((256, 256), jnp.bfloat16)

    def two_pass(a):
        r = kops.mor_select(a, part, recipe, "gam", backend="pallas")
        return kref.pack_mixed(
            a, r.sel, (128, 128), "gam", group_amax=r.group_amax,
            with_nvfp4=(recipe == "sub4"),
        )

    def select_only(a):
        return kops.mor_select(
            a, part, recipe, "gam", backend="pallas"
        ).y

    legacy_txt = hlo_rules.tpu_lowering_text(two_pass, x)
    sel_txt = hlo_rules.tpu_lowering_text(select_only, x)
    assert (hlo_rules.operand_sized_ops(legacy_txt, x.shape)
            > hlo_rules.operand_sized_ops(sel_txt, x.shape))


def test_gemm_tile_for_heuristic():
    """Autotune resolution: explicit tile > table > heuristic (cache
    when it fits, wider-bn sweep when it would not)."""
    from repro.kernels.ops import GemmTile, gemm_tile_for

    explicit = GemmTile(decode_cache=False, bn_mult=2)
    assert gemm_tile_for(2, 4, 2, (128, 128, 128), explicit) == explicit
    # Small K: cache fits.
    assert gemm_tile_for(2, 4, 8, (128, 128, 128)) == GemmTile(True, 1)
    # Huge K: falls back to the wider-bn sweep.
    big = gemm_tile_for(2, 4, 512, (128, 128, 128))
    assert big.decode_cache is False and big.bn_mult == 4
    # Single N tile: nothing to amortize.
    assert gemm_tile_for(2, 1, 8, (128, 128, 128)) == GemmTile(False, 1)
    # Registered table entry wins over the heuristic.
    from repro.kernels.ops import _GEMM_TILE_TABLE, register_gemm_tile

    try:
        register_gemm_tile(3, 3, 3, GemmTile(False, 3))
        assert gemm_tile_for(3, 3, 3, (128, 128, 128)) == GemmTile(False, 3)
    finally:
        _GEMM_TILE_TABLE.pop((3, 3, 3), None)


@pytest.mark.parametrize("recipe", ("sub3", "sub4"))
def test_gemm_decode_amortized_tiles_bit_exact(recipe):
    """Every decode-amortization tile (k-keyed cache, wider-bn sweep,
    both composed) reproduces the reference GEMM bit-for-bit."""
    from repro.kernels.ops import GemmTile

    pol = MoRPolicy(recipe=recipe, partition="block",
                    block_shape=(64, 64), backend="interpret")
    a = _mixed_tags((128, 128), seed=7)
    b = _mixed_tags((256, 128), seed=8)
    amo, _ = quantize_for_gemm(a, pol)
    bmo, _ = quantize_for_gemm(b, pol)
    want = np.asarray(kref.mixed_gemm_ref(amo, bmo), np.float32)
    for tile in (GemmTile(False, 1), GemmTile(True, 1),
                 GemmTile(False, 2), GemmTile(False, 4),
                 GemmTile(True, 2), None):
        got = kops.mixed_gemm(amo, bmo, backend="interpret", tile=tile)
        np.testing.assert_array_equal(
            want, np.asarray(got, np.float32),
            err_msg=f"{recipe} {tile}",
        )


def test_pack_kernel_mosaic_lowers():
    """Pack-emitting kernel stays Mosaic-lowerable (TPU cross-lowering
    regression, matching test_mor_select's select-mode guard)."""
    from repro.kernels.mor_select import mor_select_blocks

    x = jnp.zeros((256, 256), jnp.bfloat16)
    for mode in RECIPES:
        f = lambda a: mor_select_blocks(  # noqa: E731
            a, jnp.ones((3,), jnp.float32), jnp.float32(1.0),
            mode=mode, emit="pack",
        )
        txt = hlo_rules.tpu_lowering_text(f, x)
        assert hlo_rules.count_custom_calls(txt) == 1, mode


# ------------------------------------------------------- 4-device mesh --
def _run_mesh(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
    )
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_fused_pack_invariance():
    """Shard-local fused packs on a 4-device mesh are bit-identical to
    the single-device pack for every sub-tensor recipe (the allreduced
    group amax reaches the in-kernel scale guard and micro scales)."""
    out = _run_mesh("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.policy import MoRPolicy
    from repro.core.mor import quantize_for_gemm
    from repro.core.collectives import shard_map_unchecked

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ('data',))
    r = np.random.default_rng(0)
    base = r.standard_normal((256, 128)) * np.exp2(
        r.integers(-12, 12, (256, 128)))
    x = jnp.asarray(base, jnp.bfloat16)

    for recipe in ('sub2', 'sub3', 'sub4'):
        for algo in ('gam', 'e8m0'):
            pol = MoRPolicy(recipe=recipe, partition='block',
                            block_shape=(64, 64), algo=algo)
            pol_sh = pol.replace(mesh_axes=('data',))
            mo1, s1 = jax.jit(lambda a: quantize_for_gemm(a, pol))(x)

            def gbody(a):
                mo, s = quantize_for_gemm(a, pol_sh)
                return (mo.payload_q, mo.payload_bf16, mo.payload_nib,
                        mo.micro_scales, mo.tags, mo.scales), s
            sh = P('data', None)
            (pq, pbf, nib, ms, t, sc), s2 = jax.jit(shard_map_unchecked(
                gbody, mesh, P('data', None),
                ((sh, sh, sh, sh, sh, sh), P())))(x)
            np.testing.assert_array_equal(np.asarray(mo1.tags),
                                          np.asarray(t))
            np.testing.assert_array_equal(np.asarray(mo1.scales),
                                          np.asarray(sc))
            np.testing.assert_array_equal(np.asarray(mo1.payload_q),
                                          np.asarray(pq))
            np.testing.assert_array_equal(
                np.asarray(mo1.payload_bf16, np.float32),
                np.asarray(pbf, np.float32))
            if recipe == 'sub4':
                np.testing.assert_array_equal(
                    np.asarray(mo1.payload_nib), np.asarray(nib))
                np.testing.assert_array_equal(
                    np.asarray(mo1.micro_scales), np.asarray(ms))
            cols = [0, 2, 3, 4, 5, 6, 7, 8, 9]
            np.testing.assert_array_equal(
                np.asarray(s1)[cols], np.asarray(s2)[cols])
            print('OK', recipe, algo)
    """)
    assert out.count("OK") == 6, out


# -------------------------------------------------- hypothesis sweeps --
def test_pack_parity_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    part = Partition("block", (32, 32), align=(2, 16))

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        m=st.integers(2, 80),
        k=st.integers(16, 96),
        mode=st.sampled_from(RECIPES),
        algo=st.sampled_from(ALGOS),
    )
    def run(seed, m, k, mode, algo):
        x = _mixed_tags((m, k), seed=seed)
        mo1, r1 = kref.quantize_pack_ref(x, part, mode, algo)
        mo2, r2 = kops.quantize_pack(x, part, mode, algo,
                                     backend="interpret")
        _assert_pack_equal(mo1, mo2, f"{seed} {m}x{k} {mode} {algo}")
        np.testing.assert_array_equal(np.asarray(r1.sel),
                                      np.asarray(r2.sel))

    run()
