"""Precision-flow static analysis suite (docs/analysis.md).

Three layers, each with a positive (violation fires) and negative
(clean code passes) witness:

* AST rules MOR001..MOR005 over source fixtures, plus the inline and
  central allowlist machinery.
* The jaxpr payload-lane taint checker: sanctioned kernel consumption
  passes, a raw payload read fires, and the real
  quantize_pack -> mixed_gemm -> dequant chain verifies end to end.
* HLO/jaxpr contracts: a deliberately-broken contract reports
  violations, and the whole registered registry passes clean on the
  interpret/cross-lowering backends (the same ``check_all`` CI's lint
  job and the bench sweep run).
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    REGISTRY,
    Contract,
    ContractCase,
    ast_rules,
    check_all,
    check_contract,
    contracts,
    hlo_rules,
    lint_payload_flow,
)
from repro.core import MoRPolicy
from repro.core.mor import quantize_for_gemm
from repro.kernels import ops as kops


def _lint(src, path="src/repro/fake.py"):
    return ast_rules.lint_source(textwrap.dedent(src), path)


def _rules_hit(violations):
    return sorted({v.rule for v in violations})


# ------------------------------------------------------- AST: MOR001 --
def test_mor001_hash_fires():
    vs = _lint("seed = hash(name) % 2**31\n")
    assert _rules_hit(vs) == ["MOR001"]


def test_mor001_crc32_clean():
    vs = _lint("import zlib\nseed = zlib.crc32(name.encode())\n")
    assert vs == []


# ------------------------------------------------------- AST: MOR002 --
def test_mor002_bare_assert_fires():
    vs = _lint("def f(x):\n    assert x.ndim == 2\n    return x\n")
    assert _rules_hit(vs) == ["MOR002"]


def test_mor002_typed_exception_clean():
    vs = _lint(
        """
        def f(x):
            if x.ndim != 2:
                raise ValueError(x.shape)
            return x
        """
    )
    assert vs == []


def test_mor002_exempt_in_kernels_and_tests():
    src = "def f(x):\n    assert x == 1\n"
    assert _lint(src, "src/repro/kernels/mor_select.py") == []
    assert _lint(src, "tests/test_foo.py") == []
    assert _lint(src, "benchmarks/bench_foo.py") == []


# ------------------------------------------------------- AST: MOR003 --
def test_mor003_magic_stats_index_fires():
    for src in (
        "x = stats[11]\n",
        "y = pm.stats[8]\n",
        "s = stats.at[10].set(kind)\n",
        "z = row[5]\n",
    ):
        assert _rules_hit(_lint(src)) == ["MOR003"], src


def test_mor003_named_constant_clean():
    vs = _lint(
        "from repro.core.mor import STAT_PAYLOAD_BPE\n"
        "x = stats[STAT_PAYLOAD_BPE]\n"
    )
    assert vs == []


def test_mor003_ignores_non_stats_arrays():
    assert _lint("x = weights[3]\n") == []


# ------------------------------------------------------- AST: MOR004 --
def test_mor004_import_time_config_fires():
    vs = _lint('import jax\njax.config.update("jax_enable_x64", True)\n')
    assert _rules_hit(vs) == ["MOR004"]


def test_mor004_config_inside_function_clean():
    vs = _lint(
        """
        import jax

        def main():
            jax.config.update("jax_enable_x64", True)
        """
    )
    assert vs == []


# ------------------------------------------------------- AST: MOR005 --
def test_mor005_clock_in_jitted_fn_fires():
    vs = _lint(
        """
        import time
        import jax

        def step(x):
            t0 = time.time()
            return x + t0

        run = jax.jit(step)
        """
    )
    assert _rules_hit(vs) == ["MOR005"]


def test_mor005_host_rng_under_jit_decorator_fires():
    vs = _lint(
        """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + np.random.standard_normal()
        """
    )
    assert _rules_hit(vs) == ["MOR005"]


def test_mor005_clock_outside_jit_clean():
    vs = _lint(
        """
        import time

        def bench(f, x):
            t0 = time.time()
            f(x)
            return time.time() - t0
        """
    )
    assert vs == []


# ------------------------------------------------------- AST: MOR006 --
_KERNEL_BODY = """
    def _select_kernel(x_ref, o_ref, amax_ref):
        assert x_ref.shape[0] == 128
        o_ref[...] = x_ref[...]
"""


def test_mor006_kernel_body_assert_fires():
    vs = _lint(_KERNEL_BODY, "src/repro/kernels/mor_select.py")
    assert _rules_hit(vs) == ["MOR006"]


def test_mor006_launcher_assert_is_mor002_territory():
    # One *_ref param (or none) is a launcher/helper, not a kernel
    # body: MOR002's kernel-dir exemption applies, MOR006 stays quiet.
    src = """
        def launch(x, o_ref):
            assert x.ndim == 2
            return x
    """
    assert _lint(src, "src/repro/kernels/mor_select.py") == []


def test_mor006_scoped_to_kernels_dir():
    # Outside the kernels dir the same source is MOR002's problem
    # (plain bare-assert rule), never MOR006's.
    hits = _rules_hit(_lint(_KERNEL_BODY, "src/repro/train/train_step.py"))
    assert hits == ["MOR002"]
    assert _lint(_KERNEL_BODY, "tests/test_foo.py") == []


def test_mor006_nested_defs_not_attributed_to_kernel():
    # An assert inside a *nested* non-kernel function must not be
    # blamed on the enclosing kernel body.
    src = """
        def _kern(x_ref, o_ref):
            def helper(v):
                assert v > 0
                return v
            o_ref[...] = x_ref[...]
    """
    assert _lint(src, "src/repro/kernels/mor_select.py") == []


# ------------------------------------------------------- allowlists --
def test_inline_allow_suppresses():
    vs = _lint("seed = hash(n)  # lint: allow(MOR001) fixture\n")
    assert vs == []
    # ...but only for the named rule.
    vs = _lint("seed = hash(n)  # lint: allow(MOR002) wrong rule\n")
    assert _rules_hit(vs) == ["MOR001"]


def test_central_allowlist_is_rationaled_and_applies():
    for entry in ast_rules.ALLOWLIST:
        assert entry.rationale, entry
        assert entry.rule in ast_rules.RULES, entry
    # The PYTHONHASHSEED reassociation entry suppresses MOR001 in the
    # serve-engine test module (and nowhere else).
    src = "x = hash(n)\n"
    assert _lint(src, "tests/test_serve_engine.py") == []
    assert _rules_hit(_lint(src, "tests/test_other.py")) == ["MOR001"]


def test_repo_lints_clean():
    """Day-one guarantee: the whole repo passes its own linter."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    vs = ast_rules.lint_paths([
        os.path.join(root, d)
        for d in ("src", "tools", "benchmarks", "tests")
    ])
    assert vs == [], "\n".join(v.render() for v in vs)


# ---------------------------------------------------- jaxpr taint ----
def _mo(seed=0, shape=(256, 256)):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    mo, _ = quantize_for_gemm(
        x, MoRPolicy(recipe="sub3", backend="interpret")
    )
    return mo


def test_taint_clean_through_sanctioned_gemm():
    a, b = _mo(0), _mo(1, (128, 256))
    rep = lint_payload_flow(
        lambda x, y: kops.mixed_gemm(x, y, backend="interpret"), (a, b)
    )
    assert rep.ok, rep.render()
    assert any("payload_q" in s for s in rep.seeded)
    assert any("tags" in s for s in rep.seeded)


def test_taint_raw_payload_read_fires():
    a = _mo(2)

    def leak(m):
        return m.payload_q.astype(jnp.float32).sum() * 2.0

    rep = lint_payload_flow(leak, (a,))
    assert not rep.ok
    assert any("payload_q" in v.lane for v in rep.violations)


def test_taint_structural_ops_propagate_without_firing():
    # Slicing/transposing payload bytes moves them without reading
    # them: structural, not a violation (consuming them would be).
    a = _mo(3)
    rep = lint_payload_flow(lambda m: m.payload_q.T[:64], (a,))
    assert rep.ok, rep.render()


def test_taint_end_to_end_pack_gemm_decode_chain():
    """The acceptance chain: quantize_pack -> mixed_gemm -> dequant,
    with kernel outputs re-seeded, verifies end to end -- and a
    deliberate raw-payload leak in the same chain is caught."""
    pol = MoRPolicy(recipe="sub3", backend="interpret")
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((256, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((128, 256)), jnp.bfloat16)

    def chain(a, b):
        amo, _ = quantize_for_gemm(a, pol)
        bmo, _ = quantize_for_gemm(b, pol)
        y = kops.mixed_gemm(amo, bmo, backend="interpret")
        return amo.dequant().astype(jnp.float32).sum() + y.sum()

    rep = lint_payload_flow(chain, (x, w), seed_kernel_outputs=True)
    assert rep.ok, rep.render()
    assert rep.n_eqns > 10  # really walked the whole program

    def leaky_chain(a, b):
        amo, _ = quantize_for_gemm(a, pol)
        bmo, _ = quantize_for_gemm(b, pol)
        y = kops.mixed_gemm(amo, bmo, backend="interpret")
        return y.sum() + amo.payload_q.astype(jnp.float32).mean()

    rep = lint_payload_flow(
        leaky_chain, (x, w), seed_kernel_outputs=True
    )
    assert not rep.ok


# ------------------------------------------------------- contracts ---
def test_contract_violation_fires():
    """A contract with unsatisfiable rules reports every miss (and the
    report carries which rule missed)."""
    bad = Contract(
        name="fixture_bad",
        build=lambda: ContractCase(
            fn=lambda x: (x.astype(jnp.float64) * 2).sum(),
            args=(jnp.ones((8, 8), jnp.float32),),
        ),
        forbid_f64=True,
        taint=r"\[0\]",  # seed the whole first argument
    )
    with jax.enable_x64(True):
        report = check_contract(bad)
    assert not report.ok
    assert any("f64" in v for v in report.violations)
    # The tainted arg is consumed by `convert_element_type` in this
    # (unsanctioned) module: the taint rule fires too.
    assert any("consumed" in v for v in report.violations)
    assert report.rules_evaluated == 2


def test_contract_custom_call_range_fires():
    low = Contract(
        name="fixture_launches",
        build=lambda: ContractCase(
            fn=lambda x: x + 1.0,  # zero custom calls
            args=(jnp.ones((8, 8), jnp.float32),),
        ),
        custom_calls=(1, 1),
        forbid_f64=False,
    )
    report = check_contract(low)
    assert not report.ok
    assert "custom calls" in report.violations[0]


def test_registry_names_and_constants():
    expected = {
        "quantize_pack_sub3", "quantize_pack_sub4",
        "mor_quantize_sub4", "mixed_gemm", "qdot_sub3", "qdot_sub4",
        "mor_dot_fused_fwd", "mor_dot_fused_grads", "flash_attention",
        "compress_grads_mor", "adamw_packed_moments",
        "engine_decode_step", "engine_prefill",
    }
    assert expected <= set(REGISTRY)
    assert contracts.SINGLE_LAUNCH == (1, 1)
    assert contracts.MAX_PACK_OPS_OVER_SELECT == 0
    # The decode-tile pin matches the kernel layer's own resolution.
    assert contracts.DECODE_ROW_BLOCK == kops.decode_row_block(4)


@pytest.mark.slow
def test_check_all_registry_clean():
    """Every registered entry-point contract passes on this host (the
    blocking CI lint job runs exactly this sweep)."""
    summary = check_all()
    assert summary.contracts_checked == len(REGISTRY)
    assert summary.rules_evaluated >= summary.contracts_checked
    assert summary.ok, "\n".join(summary.violations)


def test_kernel_contracts_clean_fast():
    """Tier-1 subset of the sweep: the kernel-level contracts (no
    engine build) pass clean."""
    summary = check_all([
        "quantize_pack_sub3", "mixed_gemm", "qdot_sub3",
        "flash_attention",
    ])
    assert summary.ok, "\n".join(summary.violations)


# ------------------------------------------------------- hlo_rules ---
def test_operand_sized_ops_counts_and_families():
    txt = "\n".join([
        "func something",
        '%0 = stablehlo.convert %arg0 : tensor<256x256xbf16>',
        '%1 = stablehlo.add %0, %0 : tensor<256x256xf32>',
        '%2 = stablehlo.pad %1 : tensor<16xf32>',  # small: not counted
        "return %1",
    ])
    assert hlo_rules.operand_sized_ops(txt, (256, 256)) == 2
    fams = hlo_rules.operand_sized_packing_ops(txt, (256, 256))
    assert len(fams) == 1 and "convert" in fams[0]


def test_f64_and_host_transfer_detection():
    assert hlo_rules.f64_lines(
        "%0 = stablehlo.add %a : tensor<4x4xf64>"
    )
    assert not hlo_rules.f64_lines(
        "%0 = stablehlo.add %a : tensor<4x4xf32>"
    )
    assert hlo_rules.host_transfer_lines(
        '%1 = "stablehlo.send"(%a) : tensor<4xf32>'
    )


def test_donated_arg_count_sees_donation():
    def f(pool, x):
        return {"kv": pool["kv"] + x}, x.sum()

    args = ({"kv": jnp.ones((8, 8))}, jnp.ones((8, 8)))
    txt = hlo_rules.lowering_text(f, *args, donate_argnums=(0,))
    assert hlo_rules.donated_arg_count(txt) >= 1
    txt0 = hlo_rules.lowering_text(f, *args)
    assert hlo_rules.donated_arg_count(txt0) == 0
