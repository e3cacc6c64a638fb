"""Validate the trip-count-aware HLO walker against analytic FLOP counts."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze_hlo, parse_hlo


def test_scan_matmul_flops_trip_multiplied():
    """A scanned matmul must count flops ~= trips * 2*M*N*K."""
    M = N = K = 128
    trips = 7

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(body, x, None, length=trips)
        return y

    x = jnp.zeros((M, K), jnp.float32)
    w = jnp.zeros((K, N), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    cost = analyze_hlo(compiled.as_text())
    expect = trips * 2 * M * N * K
    assert 0.9 * expect < cost.flops < 1.6 * expect, (
        f"walked={cost.flops:.3e} expected~{expect:.3e}"
    )
    # XLA's own analysis (trip-count-blind) must be well below ours.
    xla = float(compiled.cost_analysis().get("flops", 0.0))
    assert xla < 0.5 * cost.flops


def test_plain_matmul_flops():
    M, N, K = 64, 96, 256

    def f(x, w):
        return x @ w

    compiled = (
        jax.jit(f)
        .lower(
            jnp.zeros((M, K), jnp.float32), jnp.zeros((K, N), jnp.float32)
        )
        .compile()
    )
    cost = analyze_hlo(compiled.as_text())
    expect = 2 * M * N * K
    assert 0.9 * expect <= cost.flops < 1.3 * expect


def test_parse_finds_computations():
    hlo = """\
HloModule test

%helper (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %t = f32[4]{0} tanh(%a)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %c = s32[] constant(5)
  ROOT %call.1 = f32[4]{0} call(%x), to_apply=%helper
}
"""
    comps = parse_hlo(hlo)
    assert "helper" in comps and "main" in comps
    cost = analyze_hlo(hlo)
    assert cost.flops == 4.0  # tanh over 4 elements, via the call


def test_collective_accounting():
    hlo = """\
HloModule test

ENTRY %main (x: f32[16,1024]) -> f32[16,1024] {
  %x = f32[16,1024]{1,0} parameter(0)
  ROOT %ar = f32[16,1024]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add
}
"""
    cost = analyze_hlo(hlo, n_partitions=256)
    sz = 16 * 1024 * 4
    assert cost.coll_operand_bytes["all-reduce"] == sz
    np.testing.assert_allclose(
        cost.coll_traffic_bytes["all-reduce"], 2 * sz * 15 / 16
    )
