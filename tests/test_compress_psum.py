"""Compressed cross-pod psum edge cases (satellite 3).

:func:`repro.optim.compress.make_pod_compressed_psum` with a MoRPolicy
ships real mixed-layout payloads across the pod axis. Pinned here:

* **Degenerate single pod** (``axis_name=None``): the collective
  reduces to a local pack/decode round-trip, bit-exact against the
  fake-quantization reference -- the numerics are testable without a
  mesh, and a 1-pod mesh costs nothing over the local path.
* **Uneven leaves**: shapes that don't divide the 128x128 block grid
  (odd 2-D, vectors, scalars) round-trip at their original shape with
  the same per-block error bound as aligned ones.
* **Outlier witness**: one huge gradient entry destroys the *flat*
  per-tensor E4M3 path's scale for every other element; the per-block
  MoR path isolates the outlier in its own block. This is the test
  that says why the payload machinery is worth shipping.
* **Validation**: the pod axis may appear in neither
  ``policy.mesh_axes`` nor ``inner_axes`` (pods hold independent
  partial sums, not shards of one tensor).
* **4-device (pod x data) identity** (subprocess, 2x2 mesh): with
  ``inner_axes=('data',)`` each shard's pack is bit-identical to the
  single-device pack of its whole pod gradient (PR-3 allreduced group
  amax), and the decoded cross-pod sum equals the single-device
  reference exactly.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mor import mor_quantize
from repro.core.policy import MoRPolicy
from repro.optim.compress import leaf2d, make_pod_compressed_psum

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _xla(recipe, **kw):
    return MoRPolicy(recipe=recipe, backend="xla", **kw)


# --------------------------------------------------- degenerate 1 pod --
@pytest.mark.parametrize("recipe", ("sub2", "sub3", "sub4"))
def test_single_pod_is_local_roundtrip(recipe):
    """axis_name=None: psum(g) == fake-quant of the bf16 2-D view --
    exactly one pack+decode, no collective, bit-exact vs the shared
    decision path."""
    pol = _xla(recipe)
    psum = make_pod_compressed_psum(axis_name=None, policy=pol)
    r = np.random.default_rng(0)
    g = jnp.asarray(
        r.standard_normal((256, 128))
        * np.exp2(r.integers(-10, 10, (256, 128))),
        jnp.float32,
    )
    out = jax.jit(psum)(g)
    ref2d, _ = mor_quantize(leaf2d(g).astype(jnp.bfloat16), pol)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref2d.astype(jnp.float32)))
    assert out.shape == g.shape and out.dtype == g.dtype


def test_single_pod_legacy_flat_path():
    """policy=None keeps the legacy flat per-tensor E4M3 semantics."""
    psum = make_pod_compressed_psum(axis_name=None, policy=None)
    g = jnp.asarray(np.random.default_rng(1).standard_normal((64, 64)),
                    jnp.float32)
    out = jax.jit(psum)(g)
    rel = float(jnp.linalg.norm(out - g) / jnp.linalg.norm(g))
    assert out.shape == g.shape
    assert rel < 0.05, rel


# ------------------------------------------------------ uneven leaves --
@pytest.mark.parametrize("shape", [(100, 70), (1, 300), (37,), ()])
def test_uneven_leaf_shapes_roundtrip(shape):
    """Leaves that don't divide the block grid (or aren't 2-D at all)
    ship through the compressed collective at their original shape."""
    pol = _xla("sub3")
    psum = make_pod_compressed_psum(axis_name=None, policy=pol)
    r = np.random.default_rng(2)
    g = jnp.asarray(r.standard_normal(shape), jnp.float32)
    out = jax.jit(psum)(g)
    assert out.shape == g.shape
    err = float(jnp.max(jnp.abs(out - g)))
    amax = float(jnp.max(jnp.abs(g))) if g.size else 0.0
    # bf16 cast + worst fp8 arm: comfortably under one E5M2 step.
    assert err <= amax * 2.0 ** -2 + 1e-6, (shape, err, amax)


# ---------------------------------------------------- outlier witness --
def test_witness_flat_e4m3_vs_mor_on_outliers():
    """One 1e4 outlier in a ~1e-2 gradient: flat E4M3 spends its only
    scale on the outlier and flattens everything else; per-block MoR
    keeps every non-outlier block at fp8 fidelity."""
    r = np.random.default_rng(3)
    g_np = (r.standard_normal((256, 128)) * 1e-2).astype(np.float32)
    g_np[17, 5] = 1e4  # one outlier block
    g = jnp.asarray(g_np)

    flat = make_pod_compressed_psum(axis_name=None, policy=None)
    mor = make_pod_compressed_psum(axis_name=None, policy=_xla("sub3"))
    out_flat = jax.jit(flat)(g)
    out_mor = jax.jit(mor)(g)

    # Error over everything *except* the outlier's own 128x128 block.
    mask = np.ones_like(g_np, bool)
    mask[0:128, 0:128] = False
    ref = g_np[mask]
    rel_flat = float(np.linalg.norm(np.asarray(out_flat)[mask] - ref)
                     / np.linalg.norm(ref))
    rel_mor = float(np.linalg.norm(np.asarray(out_mor)[mask] - ref)
                    / np.linalg.norm(ref))
    # Flat: the scale 448/1e4 leaves ~1e-2 values with ~100% error.
    assert rel_flat > 0.5, rel_flat
    assert rel_mor < 0.05, rel_mor
    assert rel_mor < rel_flat / 10


# -------------------------------------------------------- validation --
def test_pod_axis_must_not_be_inner():
    with pytest.raises(ValueError):
        make_pod_compressed_psum(
            "pod", policy=_xla("sub3"), inner_axes=("pod",))
    with pytest.raises(ValueError):
        make_pod_compressed_psum(
            "pod", policy=_xla("sub3", mesh_axes=("pod",)))


# ------------------------------------------------ 4-device pod x data --
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


def test_pod_psum_bit_identical_to_single_device():
    """2x2 (pod, data) mesh: every data shard of a pod packs
    bit-identical payload/tags/scales to a single-device pack of the
    full pod gradient, and the decoded cross-pod sum is exactly the
    single-device reference (same pods, same f32 sum order)."""
    out = _run_mesh("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.collectives import shard_map_unchecked
    from repro.core.mor import quantize_for_gemm
    from repro.core.policy import MoRPolicy
    from repro.optim.compress import leaf2d, make_pod_compressed_psum

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ('pod', 'data'))
    r = np.random.default_rng(0)
    G = r.standard_normal((2, 256, 128)) * np.exp2(
        r.integers(-12, 12, (2, 256, 128)))
    G = jnp.asarray(G, jnp.float32)  # [pod, rows, cols] partial sums

    for recipe in ('sub3', 'sub4'):
        pol = MoRPolicy(recipe=recipe, backend='xla')
        psum = make_pod_compressed_psum(
            'pod', policy=pol, inner_axes=('data',))
        pol_sh = pol.replace(mesh_axes=('data',))

        def body(a):  # a: (1, 128, 128) -- one pod's data shard
            g_local = a[0]
            mo, _ = quantize_for_gemm(
                leaf2d(g_local).astype(jnp.bfloat16), pol_sh)
            return (psum(g_local)[None],
                    (mo.payload_q[None], mo.tags[None],
                     mo.scales[None]))
        sh = P('pod', 'data', None)
        out, (pq, tags, scales) = jax.jit(shard_map_unchecked(
            body, mesh, sh, (sh, (sh, sh, sh))))(G)

        # Single-device reference: pack each pod's full gradient.
        refs = []
        for i in range(2):
            moi, _ = jax.jit(lambda a: quantize_for_gemm(
                leaf2d(a).astype(jnp.bfloat16), pol))(G[i])
            refs.append(moi)
            np.testing.assert_array_equal(
                np.asarray(moi.payload_q), np.asarray(pq[i]),
                err_msg=f'{recipe}:payload_q:pod{i}')
            np.testing.assert_array_equal(
                np.asarray(moi.tags), np.asarray(tags[i]),
                err_msg=f'{recipe}:tags:pod{i}')
            np.testing.assert_array_equal(
                np.asarray(moi.scales), np.asarray(scales[i]),
                err_msg=f'{recipe}:scales:pod{i}')

        want = (refs[0].dequant().astype(jnp.float32)
                + refs[1].dequant().astype(jnp.float32))
        for i in range(2):  # both pods hold the identical sum
            np.testing.assert_array_equal(
                np.asarray(out[i]), np.asarray(want),
                err_msg=f'{recipe}:sum:pod{i}')
        print('OK', recipe)
    """)
    assert out.count("OK") == 2, out
