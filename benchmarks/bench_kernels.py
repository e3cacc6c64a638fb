"""Kernel microbenchmarks: wall time per call on this host (CPU: the jnp
reference / interpret paths; on a TPU host the same harness times the
Pallas kernels) + derived bandwidth.

``--smoke`` runs a reduced matrix (CI lane); ``--json PATH`` writes the
rows as a machine-readable artifact conforming to the frozen
``repro.bench_kernels`` schema (``benchmarks/schema.py``, documented
in ``benchmarks/README.md``).

The sharded lane (``kernel/*_sharded_*`` rows) runs in this process
when it sees >= 4 devices (on a CPU host: ``XLA_FLAGS=
--xla_force_host_platform_device_count=4``); otherwise it emits a
``kernel/gemm_sharded_skipped`` row saying it did not run.
``--no-sharded`` skips it.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.analysis.hlo_rules import (
    count_custom_calls,
    operand_sized_ops,
    tpu_lowering_text,
)
from repro.core import E4M3, E5M2, PER_BLOCK_128, MoRPolicy, mor_quantize
from repro.core.formats import cast_to_format
from repro.core.gam import scales_from_bmax
from repro.core.metrics import E5M2_RANGE_RATIO
from repro.core.mor import (
    STAT_FRAC_NVFP4,
    STAT_PAYLOAD_BPE,
    quantize_for_gemm,
)
from repro.core.partition import Partition, from_blocks, to_blocks
from repro.kernels import ref as kref
from repro.kernels.ops import (
    gam_quant,
    mixed_gemm,
    mor_select,
    sharded_mixed_gemm,
)
from repro.kernels.ref import passthrough_mixed
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh

from .common import csv_row
from .schema import make_artifact


def _time(fn, *args, iters=10):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def _hlo_stats(fn, x, *args):
    """(HBM-traffic bytes, operand-sized instruction count) of jit(fn).

    The instruction count is the number of optimized (post-fusion) HLO
    instructions whose text mentions the operand's shape -- i.e. how many
    times XLA touches an operand-sized buffer: the 'pass count'.
    """
    txt = jax.jit(fn).lower(x, *args).compile().as_text()
    shape_tok = f"[{x.shape[0]},{x.shape[1]}]"
    passes = sum(
        1
        for ln in txt.splitlines()
        if shape_tok in ln and "= " in ln and "parameter(" not in ln
    )
    return analyze_hlo(txt).bytes, passes


def _tpu_kernel_launches(fn, x):
    """Fused-kernel launch count in the TPU cross-lowering of jit(fn)
    (repro.analysis.hlo_rules; no TPU needed)."""
    return count_custom_calls(tpu_lowering_text(fn, x))


def _three_pass_sub3(x2d):
    """The pre-refactor sub3 lowering: three full passes over the operand
    (E4M3 quant+err, E5M2 quant+err, abs/min/max Eq. 4 range pass).
    Kept here verbatim as the fused-select benchmark baseline."""
    part = PER_BLOCK_128

    def quant_err(xb, fmt):
        bmax = jnp.max(jnp.abs(xb), axis=(2, 3)).astype(jnp.float32)
        scales = scales_from_bmax(bmax, fmt, "gam")
        s = scales.scale[:, :, None, None]
        xqb = (cast_to_format(xb.astype(jnp.float32) * s, fmt) / s).astype(
            xb.dtype
        )
        xf = xb.astype(jnp.float32)
        nz = xf != 0.0
        err = jnp.where(
            nz,
            jnp.abs((xf - xqb.astype(jnp.float32)) / jnp.where(nz, xf, 1.0)),
            0.0,
        )
        return xqb, jnp.sum(err, (2, 3)), jnp.sum(nz, (2, 3))

    xb = to_blocks(x2d, part)
    q4b, e4, n = quant_err(xb, E4M3)                    # pass 1
    q5b, e5, _ = quant_err(xb, E5M2)                    # pass 2
    m1 = e4 < e5
    xabs = jnp.abs(xb)                                  # pass 3
    bmax = jnp.max(xabs, axis=(2, 3)).astype(jnp.float32)
    big = jnp.asarray(jnp.finfo(xb.dtype).max, xb.dtype)
    bmin = jnp.min(jnp.where(xb != 0, xabs, big), axis=(2, 3)).astype(
        jnp.float32
    )
    anynz = n > 0
    ratio = jnp.where(anynz, bmax / jnp.where(anynz, bmin, 1.0), 1.0)
    use5 = jnp.logical_and(jnp.logical_not(m1), ratio < E5M2_RANGE_RATIO)
    y = from_blocks(
        jnp.where(m1[:, :, None, None], q4b,
                  jnp.where(use5[:, :, None, None], q5b, xb)),
        x2d.shape,
    )
    return y


def _legacy_dequant_matmul(x2d, mo):
    """The pre-mixed-GEMM serving lowering, frozen as the baseline: fully
    materialize the dequantized bf16 weight, then a dense bf16 matmul.
    The per-block representation decisions are erased before the dot."""
    w = mo.dequant()
    return jnp.dot(
        x2d, w.T.astype(x2d.dtype), preferred_element_type=jnp.float32
    ).astype(x2d.dtype)


def _nvfp4_friendly(rng, shape, span=9):
    """Micro-structured data the sub4 cascade sends to NVFP4: E2M1-grid
    magnitudes under per-16-element group scales (see docs/numerics.md
    -- NVFP4 wins exactly where one per-block E4M3 scale underflows)."""
    r, k = shape
    grid = np.array([0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
    vals = grid[rng.integers(0, len(grid), (r, k))]
    signs = np.where(rng.standard_normal((r, k)) > 0, 1.0, -1.0)
    gs = np.exp2(rng.integers(-span, span + 1, (r, k // 16))).repeat(
        16, axis=1
    )
    return jnp.asarray(signs * vals * gs, jnp.bfloat16)


def _bench_nvfp4_gemm(rows, rng, smoke: bool):
    """The sub4 (NVFP4) serving lane: a fully-NVFP4 weight's packed
    4-bit payload through the mixed GEMM vs the legacy dequant+matmul,
    with the bytes/element of the pack and the fused launch count --
    the ``kernel/gemm_nvfp4_*`` rows the v2 schema contract names."""
    M, N, K = (256, 512, 512) if smoke else (512, 1024, 1024)
    pol = MoRPolicy(recipe="sub4", partition="block", backend="xla")
    w = _nvfp4_friendly(rng, (N, K))
    mo, stats = quantize_for_gemm(w, pol)
    mo = mo.compact()
    bpe = sum(
        l.size * l.dtype.itemsize
        for l in (mo.payload_q, mo.payload_bf16, mo.payload_nib,
                  mo.micro_scales, mo.tags, mo.scales)
    ) / (N * K)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    bk = mo.block[1]

    def legacy(a, m=mo):
        return _legacy_dequant_matmul(a, m)

    def fused_xla(a, m=mo, bk=bk):
        return mixed_gemm(passthrough_mixed(a, (bk, bk)), m,
                          backend="xla")

    def fused_pallas(a, m=mo, bk=bk):
        return mixed_gemm(passthrough_mixed(a, (bk, bk)), m,
                          backend="pallas")

    iters = 3 if smoke else 10
    us_l = _time(jax.jit(legacy), x, iters=iters)
    us_f = _time(jax.jit(fused_xla), x, iters=iters)
    launches = _tpu_kernel_launches(fused_pallas, x)
    tag = f"{M}x{N}x{K}"
    rows.append(csv_row(
        f"kernel/gemm_nvfp4_xla_{tag}", us_f,
        f"frac_nvfp4={float(stats[STAT_FRAC_NVFP4]):.2f};"
        f"weight_bytes_per_elt={bpe:.3f};"
        f"us_legacy_dequant={us_l:.1f}",
    ))
    rows.append(csv_row(
        f"kernel/gemm_nvfp4_pallas_{tag}", 0.0,
        f"tpu_kernel_launches={launches};"
        f"weight_bytes_per_elt={bpe:.3f}",
    ))


def _bench_mixed_gemm(rows, rng, smoke: bool, recipe: str = "sub3"):
    """Mixed-representation GEMM vs legacy dequantize-then-matmul:
    wall time + HLO bytes + operand-pass counts (xla lowerings) and
    fused-kernel launch counts (TPU cross-lowering)."""
    sizes = ((512, 512, 512),) if smoke else (
        (512, 512, 512), (1024, 1024, 1024)
    )
    pol = MoRPolicy(recipe=recipe, partition="block", backend="xla")
    for M, N, K in sizes:
        x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
        w = (_nvfp4_friendly(rng, (N, K)) if recipe == "sub4"
             else jnp.asarray(rng.standard_normal((N, K)), jnp.bfloat16))
        mo, _ = quantize_for_gemm(w, pol)
        bk = mo.block[1]

        def legacy(a, m=mo):
            return _legacy_dequant_matmul(a, m)

        def fused_xla(a, m=mo, bk=bk):
            return mixed_gemm(
                passthrough_mixed(a, (bk, bk)), m, backend="xla"
            )

        def fused_pallas(a, m=mo, bk=bk):
            return mixed_gemm(
                passthrough_mixed(a, (bk, bk)), m, backend="pallas"
            )

        iters = 3 if smoke else 10
        us_l = _time(jax.jit(legacy), x, iters=iters)
        us_f = _time(jax.jit(fused_xla), x, iters=iters)
        by_l, ps_l = _hlo_stats(legacy, x)
        by_f, ps_f = _hlo_stats(fused_xla, x)
        launches = _tpu_kernel_launches(fused_pallas, x)
        tag = f"{M}x{N}x{K}"
        rows.append(
            csv_row(f"kernel/gemm_legacy_dequant_{tag}", us_l,
                    f"hbm_bytes={by_l:.0f};operand_passes={ps_l}")
        )
        rows.append(
            csv_row(f"kernel/gemm_mixed_xla_{tag}", us_f,
                    f"hbm_bytes={by_f:.0f};operand_passes={ps_f};"
                    f"bytes_vs_legacy={by_f / max(by_l, 1):.2f}x")
        )
        rows.append(
            csv_row(f"kernel/gemm_mixed_pallas_{tag}", 0.0,
                    f"tpu_kernel_launches={launches};"
                    f"legacy_operand_passes={ps_l}")
        )

    # Interpret-mode run of the real kernel body (small, CPU-feasible).
    x = jnp.asarray(rng.standard_normal((256, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.bfloat16)
    mo, _ = quantize_for_gemm(w, pol)
    us = _time(
        lambda a: mixed_gemm(
            passthrough_mixed(a, (128, 128)), mo, backend="interpret"
        ),
        x, iters=3,
    )
    rows.append(
        csv_row("kernel/gemm_mixed_interp_256", us, "mode=interpret")
    )


def _bench_quantize_pack(rows, rng, smoke: bool):
    """One-pass fused quantize-to-payload vs the two-pass lowering it
    replaced (fused select + XLA re-pack), per recipe.

    The structural story lives in the TPU cross-lowering counts: the
    fused path must be exactly **one** ``tpu_custom_call`` with **zero**
    operand-sized XLA ops beyond what the bare selection kernel already
    needs (the global-amax reduce; + the micro-amax segment reduce for
    sub4) -- both asserted here so the CI bench smoke fails loudly if
    packing ever grows an XLA pass again. Wall rows time the xla
    lowerings (CPU hosts); the ``kernel/quantize_pack_fused_*`` /
    ``_twopass_*`` row pair is the perf-trajectory contract consumed by
    ``benchmarks/compare.py``.
    """
    from repro.core.partition import Partition
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    sizes = ((1024, 1024),) if smoke else ((1024, 1024), (4096, 1024))
    for recipe in ("sub3", "sub4"):
        part = Partition("block", (128, 128), align=(2, 16))
        pol = MoRPolicy(recipe=recipe, partition="block", backend="xla")
        pol_pl = pol.replace(backend="pallas")
        for mkn in sizes:
            x = (_nvfp4_friendly(rng, mkn) if recipe == "sub4"
                 else jnp.asarray(rng.standard_normal(mkn), jnp.bfloat16))

            def fused(a, pol=pol):
                mo, stats = quantize_for_gemm(a, pol)
                return mo.payload_q, mo.payload_bf16, stats

            def two_pass(a, recipe=recipe, part=part):
                r = kops.mor_select(a, part, recipe, "gam",
                                    backend="xla")
                mo = kref.pack_mixed(
                    a, r.sel, (128, 128), "gam",
                    group_amax=r.group_amax,
                    with_nvfp4=(recipe == "sub4"),
                )
                return mo.payload_q, mo.payload_bf16

            iters = 3 if smoke else 10
            us_f = _time(jax.jit(fused), x, iters=iters)
            us_2 = _time(jax.jit(two_pass), x, iters=iters)

            def fused_pl(a, pol=pol_pl):
                mo, stats = quantize_for_gemm(a, pol)
                return mo.payload_q, mo.payload_bf16, stats

            def select_pl(a, recipe=recipe, part=part):
                return kops.mor_select(a, part, recipe, "gam",
                                       backend="pallas").y

            def two_pass_pl(a, recipe=recipe, part=part):
                r = kops.mor_select(a, part, recipe, "gam",
                                    backend="pallas")
                mo = kref.pack_mixed(
                    a, r.sel, (128, 128), "gam",
                    group_amax=r.group_amax,
                    with_nvfp4=(recipe == "sub4"),
                )
                return mo.payload_q, mo.payload_bf16

            txt_f = tpu_lowering_text(fused_pl, x)
            launches = count_custom_calls(txt_f)
            ops_f = operand_sized_ops(txt_f, x.shape)
            ops_sel = operand_sized_ops(
                tpu_lowering_text(select_pl, x), x.shape
            )
            ops_2 = operand_sized_ops(
                tpu_lowering_text(two_pass_pl, x), x.shape
            )
            pack_ops = ops_f - ops_sel
            # The acceptance pins live in the contract registry
            # (repro.analysis.contracts): one fused launch, zero
            # operand-sized XLA packing ops on top of selection.
            lo, hi = contracts.SINGLE_LAUNCH
            if not lo <= launches <= hi:
                raise AssertionError(
                    f"quantize_pack {recipe} {mkn}: {launches} "
                    f"launches outside {contracts.SINGLE_LAUNCH}"
                )
            if pack_ops > contracts.MAX_PACK_OPS_OVER_SELECT:
                raise AssertionError(
                    f"quantize_pack {recipe} {mkn}: {pack_ops} "
                    "operand-sized packing op(s) over bare "
                    "selection (max "
                    f"{contracts.MAX_PACK_OPS_OVER_SELECT})"
                )
            pack_ops = max(pack_ops, 0)
            twopass_pack_ops = ops_2 - ops_sel
            # No wall "speedup" field on purpose: on the xla backend
            # the fused entry point IS the two-pass reference, so the
            # walls only track host drift. The fusion's win is the
            # structural pair (tpu_kernel_launches, tpu_pack_ops) from
            # the TPU cross-lowering, which IS host-independent.
            tag = f"{recipe}_{mkn[0]}x{mkn[1]}"
            rows.append(csv_row(
                f"kernel/quantize_pack_twopass_{tag}", us_2,
                f"tpu_pack_ops={twopass_pack_ops};"
                "lowering=select_kernel_plus_xla_pack",
            ))
            rows.append(csv_row(
                f"kernel/quantize_pack_fused_{tag}", us_f,
                f"tpu_kernel_launches={launches};"
                f"tpu_pack_ops={pack_ops};"
                "lowering=one_pass_kernel",
            ))


def _bench_gemm_decode_reuse(rows, rng, smoke: bool):
    """Decode-amortization lanes: the autotuned tile per bench shape
    (``kernel/gemm_autotune_*``) and an interpret-mode wall comparison
    of the k-keyed decode cache / wider-bn sweep against the naive
    revisiting grid (``kernel/gemm_decode_reuse_*``). Interpret mode
    runs the real kernel body, so the decode-count difference is what
    the wall clock sees on CPU."""
    from repro.kernels.ops import GemmTile, gemm_tile_for

    shapes = (((512, 512, 512), (128, 128, 128)),
              ((256, 65536, 256), (128, 128, 128)))
    for (M, N, K), blk in shapes:
        n_m, n_n, n_k = M // blk[0], N // blk[1], K // blk[2]
        t = gemm_tile_for(n_m, n_n, n_k, blk)
        from repro.kernels.mixed_gemm import decode_cache_bytes
        rows.append(csv_row(
            f"kernel/gemm_autotune_{M}x{N}x{K}", 0.0,
            f"decode_cache={int(bool(t.decode_cache))};"
            f"bn_mult={t.bn_mult};"
            f"cache_bytes={decode_cache_bytes(n_k, blk[0], blk[2])};"
            f"grid={n_m}x{n_n}x{n_k}",
        ))

    # Interpret-mode decode-reuse wall clock (small, CPU-feasible).
    pol = MoRPolicy(recipe="sub4", partition="block", backend="xla")
    w = _nvfp4_friendly(rng, (512, 256))
    mo, _ = quantize_for_gemm(w, pol)
    x = jnp.asarray(rng.standard_normal((128, 256)), jnp.bfloat16)

    def run(tile):
        return _time(
            lambda a: mixed_gemm(passthrough_mixed(a, (128, 128)), mo,
                                 backend="interpret", tile=tile),
            x, iters=2,
        )

    us_naive = run(GemmTile(decode_cache=False, bn_mult=1))
    us_cache = run(GemmTile(decode_cache=True, bn_mult=1))
    us_wide = run(GemmTile(decode_cache=False, bn_mult=4))
    rows.append(csv_row(
        "kernel/gemm_decode_reuse_interp_128x512x256", us_cache,
        f"us_naive={us_naive:.1f};us_bn_mult4={us_wide:.1f};"
        f"a_decodes_naive={(512 // 128) * (256 // 128)};"
        f"a_decodes_cached={256 // 128}",
    ))


def _bench_optim_state(rows, rng, smoke: bool):
    """Compressed training-state lane (the rows the v4 schema names).

    * ``kernel/grad_compress_<mode>_*`` -- one jitted gradient
      compression event per mode (flat per-tensor E4M3 vs per-block
      MoR, with and without error feedback) on the same wide-range
      leaf, with the payload bytes/element the tag mixture implies.
    * ``kernel/optim_moments_<tier>_*`` -- encode+decode round-trip of
      an Adam moment leaf, carrying the HBM budget counter
      ``moment_bytes_per_param_milli``: physical bytes/param of the
      compacted pack in milli-bytes. Deterministic for the fixed-seed
      data (a fully-fp8 leaf prices ~1000, the NVFP4-friendly sub4
      leaf ~563), so compare.py gates it at threshold 0 -- a lane that
      silently re-inflates the moment store fails the bench diff.

    Moment leaves stay at 1024x1024 even under --smoke: the per-block
    metadata only amortizes below the budget at full leaf size, and
    the counter must not depend on the smoke flag.
    """
    from repro.core import EVENT_MOMENT_M, EVENT_MOMENT_V
    from repro.optim.compress import compress_grads, ef_init
    from repro.optim.moments import (
        decode_moment,
        encode_moment,
        physical_bytes_per_param,
    )

    iters = 3 if smoke else 10
    n = 512 if smoke else 1024
    pol = MoRPolicy(recipe="sub3", backend="xla")
    g = {"w": jnp.asarray(
        rng.standard_normal((n, n)) * np.exp2(
            rng.integers(-8, 8, (n, n))),
        jnp.float32,
    )}
    ef0 = ef_init(g)
    for mode in ("fp8", "mor", "mor_ef"):
        ef = ef0 if mode == "mor_ef" else None

        def event(gg, ee, mode=mode):
            return compress_grads(gg, mode, ee, policy=pol)

        f = jax.jit(event)
        us = _time(f, g, ef, iters=iters)
        _, _, stats = f(g, ef)
        bpe = (1.0 if stats is None
               else float(stats["w"][STAT_PAYLOAD_BPE]))
        rows.append(csv_row(
            f"kernel/grad_compress_{mode}_{n}x{n}", us,
            f"payload_bpe={bpe:.3f};"
            f"ef={int(mode.endswith('_ef'))}",
        ))

    tiers = (
        ("fp8", EVENT_MOMENT_M,
         jnp.ones((1024, 1024), jnp.float32)),
        ("sub4", EVENT_MOMENT_V,
         _nvfp4_friendly(rng, (1024, 1024)).astype(jnp.float32)),
    )
    for tier, kind, leaf in tiers:
        tpol = MoRPolicy(recipe="sub4" if tier == "sub4" else "sub3",
                         backend="xla")
        pm = encode_moment(leaf, tpol, kind=kind)
        milli = int(round(physical_bytes_per_param(pm) * 1000))

        def roundtrip(a, tpol=tpol, kind=kind):
            return decode_moment(encode_moment(a, tpol, kind=kind))

        us = _time(jax.jit(roundtrip), leaf, iters=iters)
        rows.append(csv_row(
            f"kernel/optim_moments_{tier}_1024x1024", us,
            f"moment_bytes_per_param_milli={milli};"
            f"payload_bpe={float(pm.stats[STAT_PAYLOAD_BPE]):.3f};"
            f"frac_nvfp4={float(pm.stats[STAT_FRAC_NVFP4]):.2f}",
        ))


def _sharded_rows(smoke: bool):
    """Multi-device lane (>= 4 devices): the sharded mixed GEMM and the
    allreduced-stats quantization under shard_map vs their replicated
    single-device baselines, with per-shard fused-kernel launch counts
    from the TPU cross-lowering of the shard-local computation.

    Own fixed seed so the lane's data does not depend on the lanes run
    before it."""
    from jax.sharding import PartitionSpec as P

    from repro.core.collectives import shard_map_unchecked

    rng = np.random.default_rng(7)
    rows = []
    ndev = 4
    mesh = make_mesh((ndev,), ("data",))
    M = N = K = 512
    bm = 128
    pol = MoRPolicy(recipe="sub3", partition="block", backend="xla")
    w = jnp.asarray(rng.standard_normal((N, K)), jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    mo, _ = quantize_for_gemm(w, pol)
    iters = 3 if smoke else 10

    def replicated(a):
        return mixed_gemm(
            passthrough_mixed(a, (bm, bm)), mo, backend="xla"
        )

    def row_sharded(a):
        return sharded_mixed_gemm(
            passthrough_mixed(a, (bm, bm)), mo, mesh=mesh,
            row_axis="data", backend="xla",
        )

    us_rep = _time(jax.jit(replicated), x, iters=iters)
    us_sh = _time(jax.jit(row_sharded), x, iters=iters)

    # Per-shard launch count: cross-lower the shard-local computation
    # (rows/ndev of the activation against the full weight) for TPU.
    def pallas_gemm(a):
        return mixed_gemm(
            passthrough_mixed(a, (bm, bm)), mo, backend="pallas"
        )

    per_shard = _tpu_kernel_launches(pallas_gemm, x[: M // ndev])
    rep_launches = _tpu_kernel_launches(pallas_gemm, x)
    tag = f"{M}x{N}x{K}"
    rows.append(csv_row(
        f"kernel/gemm_sharded_row_data{ndev}_{tag}", us_sh,
        f"devices={ndev};axis=data;"
        f"per_shard_tpu_kernel_launches={per_shard};"
        f"replicated_tpu_kernel_launches={rep_launches};"
        f"us_replicated={us_rep:.1f}",
    ))

    # Contraction-sharded lane: per-shard partials + one f32 psum.
    def k_sharded(a):
        return sharded_mixed_gemm(
            passthrough_mixed(a, (bm, bm)), mo, mesh=mesh,
            contract_axis="data", backend="xla",
        )

    us_k = _time(jax.jit(k_sharded), x, iters=iters)
    rows.append(csv_row(
        f"kernel/gemm_sharded_contract_data{ndev}_{tag}", us_k,
        f"devices={ndev};axis=data;reduce=psum_f32;"
        f"us_replicated={us_rep:.1f}",
    ))

    # Allreduced-stats quantization under shard_map vs single-device:
    # same decisions bit-for-bit (tests/test_sharded_mor.py), cost is
    # one extra pmax/psum handful on scalars.
    qpol = MoRPolicy(recipe="sub3", partition="block", backend="xla")
    qpol_sh = qpol.replace(mesh_axes=("data",))
    xq = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.bfloat16)
    us_q1 = _time(jax.jit(lambda a: mor_quantize(a, qpol)[0]), xq,
                  iters=iters)
    sm = jax.jit(shard_map_unchecked(
        lambda a: mor_quantize(a, qpol_sh)[0], mesh,
        P("data", None), P("data", None),
    ))
    us_q4 = _time(sm, xq, iters=iters)
    rows.append(csv_row(
        f"kernel/mor_quantize_sharded_data{ndev}_1024", us_q4,
        f"devices={ndev};axis=data;stats=allreduced;"
        f"us_single_device={us_q1:.1f};invariance=bit_identical_tags",
    ))
    return rows


def _bench_sharded(rows, smoke: bool):
    """Run the sharded lane in this process when it has >= 4 devices;
    otherwise record that it did not run."""
    n = len(jax.devices())
    if n >= 4:
        rows.extend(_sharded_rows(smoke))
        return
    rows.append(csv_row(
        "kernel/gemm_sharded_skipped", 0.0,
        f"skipped=1;reason=devices:{n}<4",
    ))


def main(smoke: bool = False, sharded: bool = True, recipe: str = "sub3"):
    rows = []
    rng = np.random.default_rng(0)

    # Mixed-representation block GEMM vs legacy dequant+matmul.
    _bench_mixed_gemm(rows, rng, smoke, recipe=recipe)

    # NVFP4 packed-payload serving lane (the v2 schema's gemm_nvfp4
    # rows ride in every artifact, whatever the main-lane recipe).
    _bench_nvfp4_gemm(rows, rng, smoke)

    # One-pass quantize-to-payload vs the retired two-pass lowering
    # (asserts the 1-launch / 0-pack-pass contract) + the GEMM
    # decode-amortization lanes.
    _bench_quantize_pack(rows, rng, smoke)
    _bench_gemm_decode_reuse(rows, rng, smoke)

    # Compressed training state: gradient-compression events and the
    # packed Adam-moment round-trip with its HBM budget counter (the
    # kernel/grad_compress_* + kernel/optim_moments_* rows the v4
    # schema contract names).
    _bench_optim_state(rows, rng, smoke)

    # Fused mor_quantize (the XLA lowering used in train steps).
    quant_sizes = ((1024, 1024),) if smoke else ((1024, 1024), (4096, 1024))
    for mkn in quant_sizes:
        x = jnp.asarray(rng.standard_normal(mkn), jnp.bfloat16)
        pol = MoRPolicy(recipe="tensor", partition="block")
        f = jax.jit(lambda a: mor_quantize(a, pol)[0])
        us = _time(f, x)
        gbps = x.size * 2 * 2 / (us * 1e-6) / 1e9
        rows.append(
            csv_row(f"kernel/mor_quantize_{mkn[0]}x{mkn[1]}", us,
                    f"GB/s={gbps:.1f}")
        )

    # Fused sub-tensor select vs the pre-refactor 3-pass lowering.
    part = PER_BLOCK_128
    for mkn in quant_sizes:
        x = jnp.asarray(rng.standard_normal(mkn), jnp.bfloat16)

        def fused_xla(a):
            return mor_select(a, part, "sub3", "gam", backend="xla").y

        def fused_pallas(a):
            return mor_select(a, part, "sub3", "gam", backend="pallas").y

        us_l = _time(jax.jit(_three_pass_sub3), x)
        us_f = _time(jax.jit(fused_xla), x)
        by_l, ps_l = _hlo_stats(_three_pass_sub3, x)
        by_f, ps_f = _hlo_stats(fused_xla, x)
        launches = _tpu_kernel_launches(fused_pallas, x)
        tag = f"{mkn[0]}x{mkn[1]}"
        rows.append(
            csv_row(f"kernel/sub3_3pass_{tag}", us_l,
                    f"hbm_bytes={by_l:.0f};operand_passes={ps_l}")
        )
        rows.append(
            csv_row(f"kernel/sub3_fused_xla_{tag}", us_f,
                    f"hbm_bytes={by_f:.0f};operand_passes={ps_f};"
                    f"speedup={us_l / us_f:.2f}x")
        )
        rows.append(
            csv_row(f"kernel/sub3_fused_pallas_{tag}", 0.0,
                    f"tpu_kernel_launches={launches};"
                    "operand_passes=2(amax reduce + fused select);"
                    f"vs_3pass_passes={ps_l}")
        )

    # mor_select pallas kernel (interpret mode on CPU).
    x = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    us = _time(
        lambda a: mor_select(a, part, "sub3", "gam", backend="interpret").y,
        x, iters=3,
    )
    rows.append(csv_row("kernel/mor_select_interp_512", us,
                        "mode=interpret"))

    # gam_quant pallas kernel (interpret mode on CPU).
    x = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    us = _time(
        lambda a: gam_quant(a, backend="interpret")[0], x, iters=3
    )
    rows.append(csv_row("kernel/gam_quant_interp_512", us, "mode=interpret"))
    us = _time(lambda a: gam_quant(a, backend="xla")[0], x)
    rows.append(csv_row("kernel/gam_quant_xla_512", us, "mode=xla-ref"))

    # flash attention reference vs model chunked attention.
    from repro.models.attention import flash_attention as xla_flash

    q = jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.bfloat16)
    f = jax.jit(
        lambda a: xla_flash(a, a, a, kind="causal", q_chunk=128,
                            k_chunk=128)
    )
    us = _time(f, q)
    flops = 4 * 2 * 512 * 512 * 4 * 64  # 2 gemms, causal not discounted
    rows.append(
        csv_row("kernel/chunked_attention_b2s512", us,
                f"GFLOP/s={flops / (us * 1e-6) / 1e9:.1f}")
    )

    # Serving lane: heavy-traffic continuous-batching trace + the
    # skinny-M decode-tile contract (benchmarks/bench_serve.py).
    from .bench_serve import bench_serve

    bench_serve(rows, smoke=smoke)

    # Structural-contract sweep (the v5 schema row): every registered
    # entry-point contract in repro.analysis.contracts, evaluated
    # here so the artifact pins how many invariants the bench vouched
    # for -- compare.py fails the gate if contracts_checked ever
    # drops, and any violation fails the bench run itself.
    _bench_analysis_contracts(rows)
    _bench_robust_guard(rows)

    # Multi-device sharded lane (in this process, >= 4 devices).
    if sharded:
        _bench_sharded(rows, smoke)
    return rows, None


def _bench_analysis_contracts(rows):
    summary = contracts.check_all()
    if not summary.ok:
        raise AssertionError(
            "structural contract violation(s):\n"
            + "\n".join(summary.violations)
        )
    rows.append(csv_row(
        "kernel/analysis_contracts", 0.0,
        f"contracts_checked={summary.contracts_checked};"
        f"contract_rules_evaluated={summary.rules_evaluated};"
        f"contract_violations={len(summary.violations)}",
    ))


def _bench_robust_guard(rows):
    """Guard-rail lane (docs/robustness.md): re-verify that the v4
    stats guard lanes cost zero extra kernel launches and zero
    operand-sized pack ops over the unguarded baseline (the
    ``robust_guard_event`` contract), and enumerate the chaos
    registry so a silently-dropped fault class or injector shrinks a
    MIN-gated counter in compare.py."""
    from repro.robust.faults import fault_specs

    report = contracts.assert_contract("robust_guard_event")
    specs = fault_specs()
    covered = len(specs)  # registry == coverage, pinned by
    # tests/test_robust_chaos.py::test_every_fault_class_has_chaos_coverage
    rows.append(csv_row(
        "kernel/robust_guard", 0.0,
        f"guard_clean_pack_ops={report.counters['tpu_pack_ops']};"
        f"guard_contract_violations={len(report.violations)};"
        f"fault_classes_registered={len(specs)};"
        f"fault_classes_covered={covered}",
    ))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced matrix for the CI bench lane")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows as a repro.bench_kernels artifact")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the multi-device sharded lane")
    ap.add_argument("--recipe", default="sub3",
                    choices=("sub2", "sub3", "sub4"),
                    help="MoR recipe for the mixed-GEMM lane "
                         "(sub4 = NVFP4 four-way)")
    args = ap.parse_args()
    out_rows = main(
        smoke=args.smoke,
        sharded=not args.no_sharded,
        recipe=args.recipe,
    )[0]
    for row in out_rows:
        print(row)
    if args.json:
        artifact = make_artifact(out_rows)
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2)
        print(
            f"wrote {len(artifact['rows'])} rows to {args.json} "
            f"({artifact['schema']})"
        )
