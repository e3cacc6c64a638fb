"""MoR-compressed training state: the differential trajectory harness.

The PR-8 tentpole: gradients ('mor'/'mor_ef'), Adam moments
(PackedMoment leaves) and the cross-pod collective all flow through the
*real* per-block selection machinery. This suite pins the training-
level contract:

* **Differential trajectories** -- N steps of the reduced llama config
  under {dense f32, legacy fp8, MoR grads + EF, MoR moments, all-on},
  identical batch stream: every compressed run's final loss stays
  within a pinned tolerance of the dense run, and the dense run itself
  learned (so the tolerance is not vacuous). Tier-1 runs N=50; the
  ``--runslow`` lane re-runs the two extreme modes at N=200.
* **Error feedback** -- the residual norm is bounded and non-increasing
  in trend (last-quarter mean <= first-quarter mean x 1.05): EF absorbs
  per-step quantization error instead of accumulating it.
* **grad_accum invariance** extends to the compressed state: splitting
  the batch into 4 microbatches leaves loss, optimizer-event stats and
  the EF norm invariant (the stats-contract guarantee, now including
  event_kind > 0 rows).
* **Bytes-per-param budget** -- packed moments at the 1024x1024 leaf
  scale cost <= 1.05 B/param when fully-fp8 and <= 0.65 B/param for a
  fully-NVFP4 sub4 second moment, asserted on both the logical
  (stats-lane) and physical (post-``compact()`` HBM bytes) number.
* **Signature pinning** -- ``compress_decompress_grads`` returns
  ``(grads, ef_state)`` for *every* mode (satellite 1: the pre-PR-8
  'fp8' mode returned a bare tree and callers mis-assigned the tuple).
* **Sharding** -- ``opt_state_specs`` mirrors the OptState pytree
  (PackedMoment leaves included) so the compressed state ZeRO-shards.
* **Mesh invariance** -- a 4-device data-sharded ``encode_moment``
  emits bit-identical payloads/tags/scales to the single-device pack
  (the PR-3 allreduced-group-amax path, subprocess like
  tests/test_quantize_pack.py).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mor import (
    STAT_EVENT_KIND,
    STAT_FRAC_E4M3,
    STAT_FRAC_E5M2,
    STAT_FRAC_NVFP4,
)
from repro.core.policy import MoRPolicy
from repro.optim.compress import (
    GRAD_COMPRESS_MODES,
    compress_decompress_grads,
    ef_init,
)
from repro.optim.moments import (
    MomentPolicy,
    PackedMoment,
    encode_moment,
    decode_moment,
    logical_bytes_per_param,
    physical_bytes_per_param,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _xla(recipe, **kw):
    return MoRPolicy(recipe=recipe, backend="xla", **kw)


# Second moment under the wide-range threshold (squared grads).
_MOMENTS = MomentPolicy(m=_xla("sub3"), v=_xla("sub3", threshold=0.02))

MODES = {
    "dense": dict(),
    "fp8": dict(compress="fp8"),
    "mor_grads": dict(compress="mor_ef"),
    "mor_moments": dict(moments=_MOMENTS),
    "all_on": dict(compress="mor_ef", moments=_MOMENTS),
}


def _run_trajectory(steps, compress="none", moments=None, grad_accum=1,
                    batch_seed=7, constant_batch=False):
    """N jitted train steps on the reduced llama config; returns
    (losses, ef_norms, last_metrics). The batch stream is a fixed
    function of ``batch_seed`` so different modes see identical data."""
    from repro.configs import get_config, reduced
    from repro.core import paper_default
    from repro.models import init_params
    from repro.optim import AdamWConfig, init_opt_state
    from repro.train import TrainConfig, make_train_step

    cfg = dataclasses.replace(reduced(get_config("llama3-8b")), vocab=64)
    pol = paper_default("sub3")
    pol = pol.replace(
        act=pol.act.replace(backend="xla"),
        weight=pol.weight.replace(backend="xla"),
        grad=pol.grad.replace(backend="xla"),
    )
    tcfg = TrainConfig(
        optimizer=AdamWConfig(peak_lr=1e-3, final_lr=1e-4,
                              warmup_steps=5, total_steps=steps),
        grad_accum=grad_accum,
        compress_grads=compress,
        grad_policy=_xla("sub3"),
        moments=moments,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, moments=moments,
                         ef=compress.endswith("_ef"))
    step = jax.jit(make_train_step(cfg, pol, tcfg))
    rng = np.random.default_rng(batch_seed)
    losses, efs, metrics = [], [], None
    for _ in range(steps):
        if constant_batch:
            # One row repeated: every microbatch slice is identical, so
            # metrics must be invariant to the grad_accum split.
            row_t = rng.integers(0, 64, (1, 32))
            row_l = rng.integers(0, 64, (1, 32))
            t = np.repeat(row_t, 4, axis=0)
            l = np.repeat(row_l, 4, axis=0)
        else:
            t = rng.integers(0, 64, (4, 32))
            l = rng.integers(0, 64, (4, 32))
        batch = {"tokens": jnp.asarray(t, jnp.int32),
                 "labels": jnp.asarray(l, jnp.int32)}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
        if "ef_norm" in metrics:
            efs.append(float(metrics["ef_norm"]))
    return losses, efs, metrics


@pytest.fixture(scope="module")
def traj50():
    """All five 50-step trajectories on the identical batch stream."""
    return {name: _run_trajectory(50, **kw) for name, kw in MODES.items()}


# ----------------------------------------------- differential trajectory --
def test_loss_drift_within_tolerance(traj50):
    """Every compressed mode's final loss (mean of the last 10 steps)
    stays within 0.01 of the dense-f32 run on the same batches --
    observed drift is ~5e-4, the tolerance leaves ~20x headroom without
    admitting a diverged run (the dense loss only moves ~6e-3 total at
    this scale)."""
    dense = np.mean(traj50["dense"][0][-10:])
    for name in ("fp8", "mor_grads", "mor_moments", "all_on"):
        final = np.mean(traj50[name][0][-10:])
        assert abs(final - dense) <= 0.01, (name, final, dense)


def test_dense_run_learned(traj50):
    """The tolerance above is anchored: the dense run's loss decreased,
    so 'within tolerance of dense' is not satisfied by divergence."""
    losses = traj50["dense"][0]
    assert np.mean(losses[-10:]) < losses[0], (losses[0], losses[-10:])


def test_compressed_runs_report_opt_stats(traj50):
    """The optimizer-event stats surface in metrics for every mode that
    compresses state, and the logical payload cost they report is in
    the fp8 regime (payload <= bf16's 2 B/param, > NVFP4's floor)."""
    for name in ("mor_grads", "mor_moments", "all_on"):
        m = traj50[name][2]
        assert "opt_payload_bpe" in m, name
        bpe = float(m["opt_payload_bpe"])
        assert 0.5 < bpe <= 2.0, (name, bpe)
    assert "opt_payload_bpe" not in traj50["dense"][2]
    # Legacy fp8 bypasses the stats machinery by construction.
    assert "opt_payload_bpe" not in traj50["fp8"][2]


def test_ef_norm_bounded_and_non_increasing(traj50):
    """EF residual norms: bounded (no drift across steps -- that is the
    whole point of error feedback) and non-increasing in trend."""
    for name in ("mor_grads", "all_on"):
        efs = traj50[name][1]
        assert len(efs) == 50, name
        assert max(efs) < 0.1, (name, max(efs))  # observed ~0.032
        q = len(efs) // 4
        first, last = np.mean(efs[:q]), np.mean(efs[-q:])
        assert last <= first * 1.05, (name, first, last)


@pytest.mark.slow
def test_loss_drift_200_steps():
    """The N=200 slow-lane variant on the extreme modes."""
    dense, _, _ = _run_trajectory(200)
    assert np.mean(dense[-10:]) < dense[0]
    all_on, efs, _ = _run_trajectory(200, compress="mor_ef",
                                     moments=_MOMENTS)
    assert abs(np.mean(all_on[-10:]) - np.mean(dense[-10:])) <= 0.02
    q = len(efs) // 4
    assert np.mean(efs[-q:]) <= np.mean(efs[:q]) * 1.05
    assert max(efs) < 0.1


# --------------------------------------------------- grad_accum extension --
def test_grad_accum_invariance_compressed_state():
    """Splitting the batch into 4 microbatches leaves the compressed-
    state metrics invariant: the stats-contract guarantee extends to
    the optimizer-event rows, moment byte costs and the EF norm."""
    _, _, m1 = _run_trajectory(1, compress="mor_ef", moments=_MOMENTS,
                               grad_accum=1, constant_batch=True)
    _, _, m4 = _run_trajectory(1, compress="mor_ef", moments=_MOMENTS,
                               grad_accum=4, constant_batch=True)
    # Structural metrics -- per-block decisions and the byte costs they
    # imply -- are exactly invariant: the accumulated gradient differs
    # from the unsplit one only by accumulation rounding, far below any
    # decision threshold.
    for key in ("loss", "opt_frac_bf16", "opt_payload_bpe",
                "moment_bpe_m", "moment_bpe_v",
                "fwd_frac_bf16", "bwd_frac_bf16"):
        a, b = float(m1[key]), float(m4[key])
        assert a == pytest.approx(b, rel=1e-5, abs=1e-6), (key, a, b)
    # Value metrics of the quantization error itself are only as
    # invariant as the accumulated gradient is bitwise stable: summing
    # g/4 four times perturbs elements near rounding boundaries, so the
    # residual norms see ~1e-3 relative jitter (not drift -- jitter).
    for key in ("opt_rel_err", "ef_norm"):
        a, b = float(m1[key]), float(m4[key])
        assert a == pytest.approx(b, rel=1e-2, abs=1e-6), (key, a, b)


# ------------------------------------------------------ signature pinning --
def test_compress_decompress_signature_all_modes():
    """(grads, ef_state) for *every* mode -- the pre-PR-8 'fp8' mode
    returned a bare tree and 'fp8_ef' a tuple, and the caller that
    forgot which was which silently trained on a tuple."""
    g = {"w": jnp.ones((8, 8)), "b": jnp.ones((8,))}
    for mode in GRAD_COMPRESS_MODES:
        ef = ef_init(g) if mode.endswith("_ef") else None
        out = compress_decompress_grads(
            g, mode, ef, policy=_xla("sub3"))
        assert isinstance(out, tuple) and len(out) == 2, mode
        new_g, new_e = out
        assert jax.tree.structure(new_g) == jax.tree.structure(g), mode
        if mode.endswith("_ef"):
            assert jax.tree.structure(new_e) == jax.tree.structure(g)
        else:
            assert new_e is None, mode


def test_compress_grads_rejects_bad_mode_and_missing_ef():
    from repro.optim.compress import compress_grads

    g = {"w": jnp.ones((4, 4))}
    with pytest.raises(ValueError):
        compress_grads(g, "gzip")
    with pytest.raises(ValueError):
        compress_grads(g, "mor_ef", ef_state=None)


# -------------------------------------------------- bytes-per-param budget --
def _nvfp4_exact(shape, seed=3):
    """Values exactly on the E2M1 grid times power-of-two micro scales
    shared by each 16-element group: the sub4 cascade sends every block
    to the NVFP4 arm."""
    rng = np.random.default_rng(seed)
    m, k = shape
    grid = np.array([0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
    micro = np.exp2(rng.integers(-6, 6, (m, k // 16)).astype(np.float64))
    x = grid[rng.integers(0, 7, (m, k))] * np.repeat(micro, 16, axis=1)
    return jnp.asarray(x, jnp.float32)


def test_moment_budget_fully_fp8():
    """A 1024x1024 all-E4M3 moment leaf costs <= 1.05 B/param, logical
    (stats lane + block metadata) and physical (post-compact HBM)."""
    x = jnp.ones((1024, 1024), jnp.float32)  # exact under GAM E4M3
    pm = encode_moment(x, _xla("sub3"), kind=2.0)
    # Every block lands on an fp8 arm (ones are exact in both; the
    # dynamic-range gate picks which) -- 1 B/param payload either way.
    assert float(pm.stats[STAT_FRAC_E4M3] + pm.stats[STAT_FRAC_E5M2]) == 1.0
    logical = float(logical_bytes_per_param(pm))
    physical = physical_bytes_per_param(pm)
    assert logical <= 1.05, logical
    assert physical <= 1.05, physical
    # Round-trip at this scale is exact: ones are representable.
    np.testing.assert_array_equal(np.asarray(decode_moment(pm)),
                                  np.asarray(x))


def test_moment_budget_fully_nvfp4_sub4():
    """A fully-NVFP4 sub4 second moment costs <= 0.65 B/param."""
    x = _nvfp4_exact((1024, 1024))
    pm = encode_moment(x, _xla("sub4"), kind=3.0)
    assert float(pm.stats[STAT_FRAC_NVFP4]) == 1.0  # every block NVFP4
    assert float(logical_bytes_per_param(pm)) <= 0.65
    assert physical_bytes_per_param(pm) <= 0.65


def test_moment_event_kind_stamped():
    from repro.core import EVENT_MOMENT_M, EVENT_MOMENT_V
    from repro.optim import init_opt_state

    params = {"w": jnp.ones((256, 128)), "scale": jnp.ones((64,))}
    opt = init_opt_state(params, moments=_MOMENTS)
    assert isinstance(opt.m["w"], PackedMoment)
    assert isinstance(opt.v["w"], PackedMoment)
    # min_leaf floor: small leaves stay dense f32.
    assert isinstance(opt.m["scale"], jnp.ndarray)
    assert float(opt.m["w"].stats[STAT_EVENT_KIND]) == EVENT_MOMENT_M
    assert float(opt.v["w"].stats[STAT_EVENT_KIND]) == EVENT_MOMENT_V


# ------------------------------------------------------------ sharding --
def test_opt_state_specs_matches_compressed_state():
    """The spec tree mirrors the OptState pytree with PackedMoment
    leaves and the EF residual, so the compressed state ZeRO-shards
    like the dense one did."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.optim import init_opt_state
    from repro.sharding import rules as _rules

    cfg = dataclasses.replace(reduced(get_config("llama3-8b")), vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, moments=_MOMENTS, ef=True)
    specs = _rules.opt_state_specs(cfg, opt)
    is_p = lambda x: isinstance(x, P)
    assert jax.tree.structure(opt) == jax.tree.structure(
        specs, is_leaf=is_p)
    assert specs.step == P()
    # A packed moment leaf's spec is PackedMoment-shaped with P leaves.
    packed_specs = [
        s for s in jax.tree.leaves(
            specs.m, is_leaf=lambda x: isinstance(x, PackedMoment))
        if isinstance(s, PackedMoment)
    ]
    assert packed_specs, "no packed moment leaves in the spec tree"
    for s in packed_specs:
        assert isinstance(s.mo.tags, P) and isinstance(s.stats, P)
    # EF residual shards like the master weights.
    assert jax.tree.structure(specs.ef) == jax.tree.structure(
        specs.master, is_leaf=is_p)


# ----------------------------------------------------- 4-device identity --
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


def test_packed_moment_mesh_bit_identity():
    """encode_moment on a 4-device data-sharded mesh emits bit-identical
    payload bytes, tags and GAM scales to the single-device pack: the
    PR-3 allreduced group amax reaches the moment encoder, so a sharded
    optimizer state is byte-for-byte the unsharded one."""
    out = _run_mesh("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.collectives import shard_map_unchecked
    from repro.core.policy import MoRPolicy
    from repro.optim.moments import encode_moment

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ('data',))
    r = np.random.default_rng(0)
    base = r.standard_normal((512, 128)) * np.exp2(
        r.integers(-12, 12, (512, 128)))
    x = jnp.asarray(base, jnp.float32)

    for recipe in ('sub3', 'sub4'):
        pol = MoRPolicy(recipe=recipe, backend='xla')
        pm1 = jax.jit(
            lambda a: encode_moment(a, pol, kind=2.0))(x)

        pol_sh = pol.replace(mesh_axes=('data',))

        def body(a):
            pm = encode_moment(a, pol_sh, kind=2.0)
            mo = pm.mo
            return (mo.payload_q, mo.payload_bf16, mo.payload_nib,
                    mo.micro_scales, mo.tags, mo.scales), pm.stats
        sh = P('data', None)
        lanes, s2 = jax.jit(shard_map_unchecked(
            body, mesh, P('data', None),
            ((sh, sh, sh, sh, sh, sh), P())))(x)
        mo1 = pm1.mo
        # nib/micro lanes are compact don't-care buffers without the
        # NVFP4 arm; byte-compare them only where they are live.
        live = (('payload_q', mo1.payload_q, lanes[0]),
                ('payload_bf16', mo1.payload_bf16, lanes[1]),
                ('tags', mo1.tags, lanes[4]),
                ('scales', mo1.scales, lanes[5]))
        if recipe == 'sub4':
            live += (('payload_nib', mo1.payload_nib, lanes[2]),
                     ('micro_scales', mo1.micro_scales, lanes[3]))
        for name, a, b in live:
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f'{recipe}:{name}')
        cols = [0, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
        np.testing.assert_array_equal(
            np.asarray(pm1.stats)[cols], np.asarray(s2)[cols],
            err_msg=recipe)
        print('OK', recipe)
    """)
    assert out.count("OK") == 2, out
