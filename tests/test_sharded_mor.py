"""Sharded-vs-single-device MoR invariance suite (ISSUE 3 tentpole).

The contract (docs/sharding.md): quantizing a block-aligned shard inside
``shard_map`` with ``MoRPolicy.mesh_axes`` set produces *bit-identical*
per-block tags, GAM scales, payload bytes and decision stats to the
single-device run, for every recipe; ``mor_dot`` fwd/dgrad/wgrad and the
sharded mixed GEMM match within f32-accumulation-order tolerance. The
only quantity allowed to drift is the *reported* ``rel_err`` scalar
(stats[1]): an f32 sum whose association differs across shardings.

Multi-device tests run in a subprocess with 4 forced host devices
(the main pytest process must keep seeing 1 device); spec-derivation
tests run in-process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Stats-vector columns that must be bit-identical under sharding:
# decision, amax, frac_e4m3, frac_e5m2, frac_bf16, nonzero_frac, m_g,
# frac_nvfp4, micro_scale_bpe (layout v2).
# Column 1 (rel_err) is an f32 sum -> association drifts ~1 ulp.
EXACT_COLS = "[0, 2, 3, 4, 5, 6, 7, 8, 9]"


def _run(code: str, devices: int = 4) -> str:
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


_PRELUDE = f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.policy import MoRPolicy, MoRDotPolicy, with_mesh_axes
    from repro.core.mor import mor_quantize, quantize_for_gemm
    from repro.core.linear import mor_dot, new_token
    from repro.core.collectives import shard_map_unchecked
    from repro.kernels import ops as kops

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ('data',))
    EXACT = {EXACT_COLS}

    def check_stats(s1, s2):
        s1, s2 = np.asarray(s1), np.asarray(s2)
        np.testing.assert_array_equal(s1[..., EXACT], s2[..., EXACT])
        np.testing.assert_allclose(s1[..., 1], s2[..., 1],
                                   rtol=2e-6, atol=1e-7)
"""


def test_quantize_invariance_all_recipes():
    """Bit-identical y/tags/scales/payloads + stats rows on a forced
    4-device mesh, across every recipe and scaling algo."""
    out = _run(_PRELUDE + """
    r = np.random.RandomState(0)
    # High dynamic range so sub3 genuinely mixes all three tags.
    base = r.randn(256, 128) * np.exp(r.randn(256, 128))
    x = jnp.asarray(base, jnp.bfloat16)

    cases = [(rec, 'gam', 0.045) for rec in
             ('tensor', 'sub2', 'sub3', 'sub4', 'e4m3')]
    cases += [('sub3', 'e8m0', 0.045), ('sub3', 'fp32_amax', 0.045),
              ('sub4', 'e8m0', 0.045),  # NVFP4 micro scales, ablation
              ('tensor', 'gam', 0.0),   # forced reject branch
              ('off', 'gam', 0.045)]    # passthrough stats
    for recipe, algo, th in cases:
        pol = MoRPolicy(recipe=recipe, partition='block',
                        block_shape=(64, 64), algo=algo, threshold=th)
        pol_sh = pol.replace(mesh_axes=('data',))
        y1, s1 = jax.jit(lambda a: mor_quantize(a, pol))(x)

        def body(a):
            y, s = mor_quantize(a, pol_sh)
            return y, s
        y2, s2 = jax.jit(shard_map_unchecked(
            body, mesh, P('data', None), (P('data', None), P())))(x)
        np.testing.assert_array_equal(
            np.asarray(y1, np.float32), np.asarray(y2, np.float32))
        check_stats(s1, s2)

        if recipe == 'off':
            # Passthrough packs are compact by construction: the
            # single don't-care fp8 block is replicated, not sharded,
            # so there is no assembled payload to compare.
            continue
        mo1, _ = jax.jit(lambda a: quantize_for_gemm(a, pol))(x)

        def gbody(a):
            mo, s = quantize_for_gemm(a, pol_sh)
            return (mo.payload_q, mo.payload_bf16, mo.payload_nib,
                    mo.micro_scales, mo.tags, mo.scales), s
        sh = P('data', None)
        (pq2, pb2, nib2, ms2, t2, sc2), _ = jax.jit(shard_map_unchecked(
            gbody, mesh, P('data', None),
            ((sh, sh, sh, sh, sh, sh), P())))(x)
        np.testing.assert_array_equal(np.asarray(mo1.tags), np.asarray(t2))
        np.testing.assert_array_equal(
            np.asarray(mo1.scales), np.asarray(sc2))
        np.testing.assert_array_equal(
            np.asarray(mo1.payload_q), np.asarray(pq2))
        np.testing.assert_array_equal(
            np.asarray(mo1.payload_bf16, np.float32),
            np.asarray(pb2, np.float32))
        if recipe == 'sub4':
            # Sub-byte lanes: packed nibbles + E4M3 micro-scale bytes
            # are bit-identical too (micro scales derive from the
            # allreduced group amax + shard-local block data). Other
            # recipes carry compact don't-care lanes the out-spec
            # concatenation mangles harmlessly -- nothing to compare.
            np.testing.assert_array_equal(
                np.asarray(mo1.payload_nib), np.asarray(nib2))
            np.testing.assert_array_equal(
                np.asarray(mo1.micro_scales), np.asarray(ms2))
        print('RECIPE OK', recipe, algo, th)
    print('ALL OK')
    """)
    assert "ALL OK" in out


def test_mor_dot_invariance_fused_and_fake():
    """mor_dot fwd/dgrad/wgrad on a batch-sharded mesh match the
    single-device run: y/dx bit-exact (row-partitioned GEMMs, same
    contraction order), dw within bf16 psum-reassociation tolerance,
    stats rows bit-identical (except the rel_err f32 sum)."""
    out = _run(_PRELUDE + """
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(256, 128), jnp.bfloat16)
    w = jnp.asarray(r.randn(128, 64), jnp.bfloat16)
    dy = jnp.asarray(r.randn(256, 64), jnp.bfloat16)

    def run(xx, ww, d, p):
        def f(a, b, t):
            return mor_dot(a, b, t, p)
        (y, st), vjp = jax.vjp(f, xx, ww, new_token())
        dx, dw, dtok = vjp((d, jnp.zeros_like(st)))
        return y, st, dx, dw, dtok

    for recipe in ('tensor', 'sub3'):
        for fuse in (False, True):
            pol = MoRPolicy(recipe=recipe, partition='block',
                            block_shape=(64, 64))
            dp = MoRDotPolicy(act=pol, weight=pol, grad=pol,
                              fuse_gemm=fuse)
            dp_sh = with_mesh_axes(dp, ('data',))
            y1, st1, dx1, dw1, dt1 = jax.jit(
                lambda a, b, d: run(a, b, d, dp))(x, w, dy)

            def body(a, d, b):
                y, st, dx, dw, dtok = run(a, b, d, dp_sh)
                return y, st, dx, jax.lax.psum(dw, 'data'), dtok
            sm = shard_map_unchecked(
                body, mesh,
                in_specs=(P('data', None), P('data', None),
                          P(None, None)),
                out_specs=(P('data', None), P(), P('data', None),
                           P(None, None), P()))
            y2, st2, dx2, dw2, dt2 = jax.jit(sm)(x, dy, w)

            np.testing.assert_array_equal(
                np.asarray(y1, np.float32), np.asarray(y2, np.float32))
            np.testing.assert_array_equal(
                np.asarray(dx1, np.float32), np.asarray(dx2, np.float32))
            # wgrad: f32-accum over 256 rows vs psum of 4 bf16 partials.
            np.testing.assert_allclose(
                np.asarray(dw1, np.float32), np.asarray(dw2, np.float32),
                rtol=3e-2, atol=2e-1)
            check_stats(st1, st2)
            check_stats(dt1, dt2)
            print('DOT OK', recipe, 'fuse' if fuse else 'fake')
    print('ALL OK')
    """)
    assert "ALL OK" in out


def test_sharded_mixed_gemm_row_col_contract():
    """kops.sharded_mixed_gemm against the single-device kernel: row-
    and col-sharded lanes are bit-exact (pure spatial partitioning);
    the contraction-sharded lane psums f32 partials (1-ulp tolerance
    after the bf16 cast)."""
    out = _run(_PRELUDE + """
    from repro.kernels.ref import passthrough_mixed
    r = np.random.RandomState(2)
    pol = MoRPolicy(recipe='sub3', partition='block',
                    block_shape=(64, 64))
    w = jnp.asarray(r.randn(256, 256) * np.exp(r.randn(256, 256)),
                    jnp.bfloat16)
    x = jnp.asarray(r.randn(256, 256), jnp.bfloat16)
    mo, _ = quantize_for_gemm(w, pol)       # (N, K) view, 4x4 grid
    a = passthrough_mixed(x, (64, 64))
    ref = np.asarray(kops.mixed_gemm(a, mo), np.float32)

    for kw in (dict(row_axis='data'), dict(col_axis='data'),
               dict(contract_axis='data')):
        got = np.asarray(
            kops.sharded_mixed_gemm(a, mo, mesh=mesh, **kw), np.float32)
        if 'contract_axis' in kw:
            np.testing.assert_allclose(got, ref, rtol=1.6e-2, atol=1e-2)
        else:
            np.testing.assert_array_equal(got, ref)
        print('GEMM OK', kw)
    print('ALL OK')
    """)
    assert "ALL OK" in out


@pytest.mark.slow
def test_engine_tensor_parallel_qtensor():
    """Engine with a (1, 2) mesh: QTensor leaves device_put per the
    Megatron rules (payload/tags/scales together) and generation still
    runs end to end through the mixed GEMM path."""
    out = _run("""
    import os
    os.environ['REPRO_KERNEL_INTERPRET'] = '0'  # GSPMD-friendly xla refs
    import dataclasses
    import jax, numpy as np
    from repro.configs import get_config, reduced
    from repro.core import BF16_BASELINE, MoRPolicy
    from repro.launch.mesh import make_local_mesh
    from repro.serve.engine import Engine, Request
    from repro.serve.quantized import QTensor

    cfg = dataclasses.replace(reduced(get_config('llama3-8b')),
                              vocab=256, d_model=64, n_heads=4,
                              n_kv=2, head_dim=16)
    from repro.models import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_local_mesh(data=1, model=2)
    eng = Engine(cfg, BF16_BASELINE, params,
                 quantize=MoRPolicy(recipe='sub3'),
                 quantize_min_size=4096, mesh=mesh)
    qleaves = [l for l in jax.tree.leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(l, QTensor)]
    assert qleaves, 'no QTensor leaves'
    eng.submit(Request(rid=0, prompt=np.arange(5) % 256, max_tokens=4))
    steps = 0
    while eng.step() and steps < 32:
        steps += 1
    done = [r for r in eng.slot_req if r is None]
    print('ENGINE OK', len(qleaves))
    """, devices=2)
    assert "ENGINE OK" in out


# ---------------------------------------------------------------------
# In-process spec derivation (single device, tier-1 fast).
# ---------------------------------------------------------------------


def test_mixed_operand_pspec_compact_replicated():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ref import passthrough_mixed
    from repro.sharding.rules import mixed_operand_pspec

    a = passthrough_mixed(jnp.ones((128, 128), jnp.bfloat16), (64, 64))
    pq, pbf, nib, ms, tags, scales = mixed_operand_pspec(a, rows="data")
    assert pq == P(None, None)  # compact fp8 buffer: replicated
    assert pbf == P("data", None)
    # Passthrough packs carry compact (don't-care) sub-byte lanes:
    # replicated like any compact buffer.
    assert nib == P(None, None) and ms == P(None, None)
    assert tags == P("data", None) and scales == P("data", None)


def test_qtensor_pspec_from_dense_transposes():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import MoRPolicy
    from repro.serve.quantized import quantize_weight
    from repro.sharding.rules import qtensor_pspec_from_dense

    w = jnp.ones((256, 128), jnp.bfloat16)  # (K, N)
    qt, _ = quantize_weight(w, MoRPolicy(recipe="e4m3"))
    # lm_head-style dense rule: shard N ('model'); quant view is (N, K),
    # so the mixed leaves shard their *rows* over 'model'.
    spec = qtensor_pspec_from_dense(qt, P(None, "model"))
    assert spec.mo.tags == P("model", None)
    assert spec.mo.scales == P("model", None)
    assert spec.mo.payload_q == P("model", None)
    # all-fp8 weight: the bf16 dual buffer is compact -> replicated
    assert spec.mo.payload_bf16 == P(None, None)
    assert spec.stats == P(None)
    # row-parallel dense rule: contraction blocks shard instead.
    spec2 = qtensor_pspec_from_dense(qt, P("model", None))
    assert spec2.mo.tags == P(None, "model")


def test_qtensor_pspec_mesh_demotion():
    """A mesh axis that does not divide the block grid is demoted to
    replicated -- quantized leaves shard in whole blocks or not at all.
    (Only ``mesh.shape`` is consulted, so a shape stand-in suffices to
    model meshes larger than this host.)"""
    import types

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import MoRPolicy
    from repro.serve.quantized import quantize_weight
    from repro.sharding.rules import qtensor_pspec_from_dense

    w = jnp.ones((256, 128), jnp.bfloat16)  # view (128, 256): 1x2 grid
    qt, _ = quantize_weight(w, MoRPolicy(recipe="e4m3"))
    mesh1 = types.SimpleNamespace(shape={"data": 1, "model": 1})
    spec = qtensor_pspec_from_dense(qt, P(None, "model"), mesh1)
    assert spec.mo.tags == P("model", None)  # 1 divides everything

    # grid rows = 1, model axis size 2 -> demoted to replicated.
    mesh2 = types.SimpleNamespace(shape={"data": 1, "model": 2})
    spec2 = qtensor_pspec_from_dense(qt, P(None, "model"), mesh2)
    assert spec2.mo.tags == P(None, None)
    # contraction grid (2 blocks) divides 2 -> row-parallel stays.
    spec3 = qtensor_pspec_from_dense(qt, P("model", None), mesh2)
    assert spec3.mo.tags == P(None, "model")


def test_quantized_param_specs_tree():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.core import MoRPolicy
    from repro.serve.quantized import quantize_weight, quantize_weight_stacked
    from repro.sharding.rules import quantized_param_specs

    cfg = reduced(get_config("llama3-8b"))
    qw, _ = quantize_weight(
        jnp.ones((256, 128), jnp.bfloat16), MoRPolicy(recipe="e4m3")
    )
    qs, _ = quantize_weight_stacked(
        jnp.ones((2, 256, 128), jnp.bfloat16), MoRPolicy(recipe="e4m3")
    )
    params = {
        "lm_head": qw,
        "blocks": {"wo": qs, "ln1": {"scale": jnp.ones((2, 64))}},
    }
    specs = quantized_param_specs(cfg, params)
    # lm_head (d, V) -> dense P(None, 'model') -> view rows sharded.
    assert specs["lm_head"].mo.tags == P("model", None)
    # wo row-parallel P('model', None) -> contraction blocks sharded,
    # stacked lead axis unsharded.
    assert specs["blocks"]["wo"].mo.tags == P(None, None, "model")
    # norm scales stay on the dense replicated rule.
    assert specs["blocks"]["ln1"]["scale"] == P(None, None)
