"""The trace reduction on a small trace recorded on a TPU v5e: three
calls of a jitted step (a matmul and the Pallas ``gam_quant`` kernel),
each under a ``bench.fixture_step`` span, with a 2 ms host sleep under
``bench.host_wait`` between them."""
import pathlib

import pytest


FIXTURE = pathlib.Path(__file__).with_name("data") / "v5e_fixture.xplane.pb"


@pytest.fixture(scope="module")
def red():
    from chipbench import trace

    return trace.reduce_file(str(FIXTURE), 1)


def test_spans_window_and_programs(red):
    names = [s.name for s in red.spans]
    assert names == ["bench.fixture_step", "bench.host_wait"] * 3
    assert red.t0 == red.spans[0].start
    assert red.t1 >= red.spans[-1].end
    runs = [m for m in red.modules[0] if "jit_step" in m.name]
    assert len(runs) == 3


def test_busy_is_the_union_of_device_ops(red):
    from chipbench.trace import union_ns

    assert red.busy_ns(0) == union_ns(red.clipped(red.ops[0]))
    assert 0 < red.busy_s < red.window_s
    # Three 2 ms sleeps in a ~10 ms window: mostly idle.
    assert 90.0 < red.idle_percent() < 100.0
    assert union_ns([(0, 10), (5, 15), (20, 30)]) == 25


def test_kernel_events_are_named_and_shaped(red):
    quant = [o for o in red.ops[0] if o.short == "gam_quant_blocks"]
    assert len(quant) == 3
    assert all(o.kind == "gam_quant_blocks bf16[512,512]" for o in quant)


def test_breakdown(red):
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "gam_quant_blocks bf16[512,512]"
    assert bd["idle_gaps"][0][0].startswith("bench.host_wait")
    idle = sum(v for _, v in bd["idle_gaps"])
    assert idle <= red.window_s - red.busy_s + 1e-9


def test_quant_roofline_reader_on_the_fixture(red):
    from chipbench import counts, metrics

    ctx = metrics.Ctx(cell=None, cfg=None, trace=red, counters={},
                      peaks=counts.peaks("TPU v5 lite"), chips=1)
    share = metrics.reader("quant_kernel_roofline")(ctx)
    spent = sum(o.dur for o in red.ops[0]
                if o.short == "gam_quant_blocks") / 1e9
    least = 3 * counts.fake_quant_bytes((512, 512)) / 819e9
    assert share == pytest.approx(100 * least / spent)
    assert 0 < share <= 100


def _decode_trace():
    from chipbench.trace import Op, Reduced, Span

    gemm = ("%mixed_gemm_blocks.7 = bf16[{m},3072]{{1,0}} custom-call("
            "u8[{m},128]{{1,0}} %a, bf16[{m},9216]{{1,0}} %b, "
            "u8[3072,9216]{{1,0}} %w, u8[24,72]{{1,0}} %t, "
            "f32[24,72]{{1,0}} %s), custom_call_target=\"tpu_custom_call\"")
    mods = [Op("jit_step_fn(11)", 0, 10_000_000),
            Op("jit_step_fn(22)", 20_000_000, 40_000_000),
            Op("jit_step_fn(11)", 70_000_000, 12_000_000)]
    ops = [Op(gemm.format(m=16), 1_000_000, 4_000_000),
           Op(gemm.format(m=256), 21_000_000, 30_000_000),
           Op(gemm.format(m=16), 71_000_000, 4_000_000)]
    return Reduced({0: ops}, {0: mods}, [Span("bench.engine_step", 0, 82)],
                   [], 0, 82_000_000)


def test_decode_readers_pick_the_decode_program():
    from chipbench import counts, metrics
    from chipbench_tiny import file_cell

    cell = file_cell("minitron-4b", "chat_overload")
    lens = [[100] * 10, [300] * 12]
    ctx = metrics.Ctx(cell=cell, cfg=cell.family.arch_config(cell.config),
                      trace=_decode_trace(),
                      counters={"traced_calls": [("prefill", None)] +
                                [("decode", l) for l in lens]},
                      peaks=counts.peaks("TPU v5 lite"), chips=1)
    assert metrics.reader("decode_step_ms")(ctx) == pytest.approx(11.0)
    count = cell.family.decode_step_flops
    flops = (count(ctx.cfg, lens[0]) + count(ctx.cfg, lens[1])) / 2
    mfu = metrics.reader("decode_step_mfu")(ctx)
    assert mfu == pytest.approx(100 * flops / 0.011 / 197e12)
    share = metrics.reader("mixed_gemm_roofline")(ctx)
    least = 2 * counts.roofline_s(2.0 * 16 * 9216 * 3072,
                                  counts.mixed_gemm_bytes(16, 9216, 3072),
                                  ctx.peaks)
    assert share == pytest.approx(100 * least / 0.008)
    assert 0 < share <= 100
