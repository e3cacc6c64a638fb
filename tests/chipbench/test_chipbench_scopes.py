"""Per-layer device time from the program's named scopes.

The scoped fixture was recorded on a TPU v5e by
``record_scopes_fixture.py``: three calls of a jitted step under
``bench.train_step`` spans whose parts run under ``mlp/fc1/mor_quant``
(the ``gam_quant`` kernel), ``mlp/fc1/gemm`` (a matmul), ``attn/core``
(a softmax), ``optim`` (an update) and no scope (a sum). The older
fixture has no scopes at all.
"""
import pathlib
import shutil
import types

import pytest

DATA = pathlib.Path(__file__).with_name("data")
SCOPED = DATA / "v5e_scopes.xplane.pb"
PLAIN = DATA / "v5e_fixture.xplane.pb"
READERS = {"quant_ms.train": "mor_quant", "attention_ms.train": "attn/core",
           "gemm_ms.train": "gemm", "optimizer_ms.train": "optim"}


@pytest.mark.parametrize("path", [SCOPED, PLAIN], ids=["scoped", "plain"])
def test_wire_reader_matches_xplane_pb2(path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from chipbench.metrics import _scopes

    space = xplane_pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = want.setdefault(plane.name, {})
        for md in plane.event_metadata.values():
            for st in md.stats:
                if names.get(st.metadata_id) == "tf_op":
                    op = st.str_value or names.get(st.ref_value, "")
                    if op:
                        ops.setdefault(md.name, op)
    got = _scopes.read_tf_ops(str(path))
    assert got == want and any(got.values())


@pytest.mark.parametrize("tf_op, layer", [
    ("jit(train_step)/transpose(jvp(stack))/while/body/closed_call/"
     "checkpoint/attn/qkv/mor_quant/dgrad_dy/jit(frexp)/and", "mor_quant"),
    ("jit(step)/transpose(jvp(attn/core))/while/body/exp:", "attn/core"),
    ("transpose(jvp(attn))/core/bqhgd,bkhd->bhgqk/dot_general", "attn/core"),
    ("checkpoint/rematted_computation/mlp/fc2/gemm/fwd/dot_general:",
     "gemm"),
    ("while/body/closed_call/mlp/act/jit(relu)/max", "mlp/act"),
    ("jit(train_step)/jvp(head)/gemm/bsd,dv->bsv/dot_general", "gemm"),
    ("jit(train_step)/jvp(head)/lt", "head"),
    ("jit(train_step)/optim/sqrt:", "optim"),
    ("jit(train_step)/jvp(stack)/while/body/dynamic_slice", "stack"),
    ("jit(f)/attn/qkv/reshape;checkpoint/attn/rope/mul", "attn/qkv"),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", None),
    ("jit(step)/jit(gam_quant_blocks)/pallas_call:", None),
    ("jit(norm)/add", None),
    ("jit(step)/core/exp", None),
    ("", None),
])
def test_path_rule(tf_op, layer):
    from chipbench.metrics._scopes import layer_of

    assert layer_of(tf_op) == layer


def _ctx(monkeypatch, tmp_path, fixture, red=None):
    """A reader's context whose cell's trace directory holds ``fixture``."""
    from chipbench import counts, metrics, trace

    monkeypatch.setattr(trace, "OUT", tmp_path)
    cell = types.SimpleNamespace(name="fixture.cell")
    dst = tmp_path / "trace" / cell.name / "plugins" / "profile" / "1"
    dst.mkdir(parents=True)
    shutil.copy(fixture, dst / "host.xplane.pb")
    red = red or trace.reduce_file(str(fixture), 1)
    return metrics.Ctx(cell=cell, cfg=None, trace=red, counters={},
                       peaks=counts.peaks("TPU v5 lite"), chips=1)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_on_the_scoped_fixture(monkeypatch, tmp_path, name):
    from chipbench import metrics

    ctx = _ctx(monkeypatch, tmp_path, SCOPED)
    value = metrics.reader(name)(ctx)
    assert value is not None and value > 0
    assert value < ctx.trace.busy_s * 1e3 / 3


def test_layer_times_on_the_scoped_fixture(monkeypatch, tmp_path):
    from chipbench import metrics
    from chipbench.metrics import _scopes
    from chipbench.trace import CONTAINERS

    ctx = _ctx(monkeypatch, tmp_path, SCOPED)
    per = _scopes.per_step_ms(ctx)
    assert set(READERS.values()) < set(per) and None in per

    def ms_per_step(ops):
        return sum(e - s for s, e in ctx.trace.clipped(ops)) / 1e6 / 3

    # The kernel is a custom call: nothing fuses into it. Time counts
    # inside the window only, as in the breakdown.
    quant = [o for o in ctx.trace.ops[0] if o.short == "gam_quant_blocks"]
    assert len(quant) == 3
    assert per["mor_quant"] == pytest.approx(ms_per_step(quant))
    ops = [o for o in ctx.trace.ops[0] if o.short not in CONTAINERS]
    assert sum(per.values()) == pytest.approx(ms_per_step(ops))
    readers = sum(metrics.reader(n)(ctx) for n in READERS)
    assert readers <= ctx.trace.busy_ns(0) / 1e6 / 3


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_find_nothing_without_scopes(monkeypatch, tmp_path, name):
    """The older fixture, its spans renamed to train steps: its ops carry
    no scope, so each reader finds nothing (not 0)."""
    from chipbench import metrics, trace

    red = trace.reduce_file(str(PLAIN), 1)
    red.spans = [trace.Span("bench.train_step", s.start, s.dur)
                 if s.name == "bench.fixture_step" else s for s in red.spans]
    ctx = _ctx(monkeypatch, tmp_path, PLAIN, red)
    assert metrics.reader(name)(ctx) is None


def test_readers_find_nothing_without_a_trace_file(monkeypatch, tmp_path):
    from chipbench import metrics, trace

    monkeypatch.setattr(trace, "OUT", tmp_path)
    ctx = metrics.Ctx(cell=types.SimpleNamespace(name="none"), cfg=None,
                      trace=trace.reduce_file(str(SCOPED), 1), counters={},
                      peaks={}, chips=1)
    assert all(metrics.reader(n)(ctx) is None for n in READERS)


def test_train_step_ops_each_carry_one_layer():
    """The tiny train cell's step compiled for the CPU."""
    from chipbench_tiny import tiny_cell
    from scoped_hlo import check_step_scopes, train_step_hlo

    check_step_scopes(train_step_hlo(tiny_cell("train")))
