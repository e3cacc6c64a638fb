"""The moonlight family (``chipbench/families/moonlight.py``: Moonlight-
16B-A3B, DeepSeek-V3's layout) against the program at a tiny size on the
CPU: the latent attention, the MoE layer with a selection bias, a
router skewed onto the held experts, the whole model's loss and
gradients, and the eight shares of the experts adding up to the uncut
layer; a cell of the family run through ``chipbench.run`` from a copy of
the benchmark; the scopes of its train step; the readers of the MoE
layer's metrics; and its counts."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import ROOT

CONFIG = "moonlight-16b-a3b-l6e8"
CELL = "train.moonlight-16b-a3b-l6e8.s2048b8"
# Widths cut to a test's size; the structure (a leading dense layer, MoE
# layers with shared experts, latent attention, a router wider than the
# held experts) stays.
TINY = {"num_hidden_layers": 3, "hidden_size": 128,
        "num_attention_heads": 2, "intermediate_size": 256,
        "moe_intermediate_size": 64, "kv_lora_rank": 64,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "vocab_size": 512, "num_experts_per_tok": 4}
E = 16  # the router's width at the tiny size
SEED = 2**31 + 5


def _json(rel):
    return json.loads((ROOT / "chipbench" / rel).read_text())


def tiny_conf(held=E, first=0, **kw):
    conf = _json(f"configs/{CONFIG}.json")
    conf.update(TINY, n_routed_experts=held, first_held_expert=first, **kw)
    conf["published"] = dict(conf["published"], n_routed_experts=E)
    return conf


def _family():
    from chipbench import spec

    return spec.family_of({"family": "moonlight"})


def _setup(conf, seed=SEED):
    """(family, cfg, seeded params in the program's tree, the reference's
    float32 weights by group)."""
    from chipbench import weights as W

    fam = _family()
    cfg = fam.arch_config(conf)
    params = W.make_params(fam, cfg, seed)
    ref = jax.tree.map(lambda a: a.astype(jnp.float32), fam.stack(params))
    return fam, cfg, params, ref


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _tokens(cfg, group):
    from repro.models import make_tokens

    return _layer(make_tokens(cfg)["blocks"][group], 0)


def _input(cfg, B=2, S=32, seed=0):
    """A bf16 input and its float32 copy (the same values), so that the
    program and the reference route the same scores."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model))
    x = x.astype(jnp.bfloat16)
    return x, x.astype(jnp.float32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ---------------------------------------------------- against the reference --
def test_latent_attention_matches_the_reference(cpu_devices):
    from repro.core import BF16_BASELINE
    from repro.models import blocks as B

    fam, cfg, params, ref = _setup(tiny_conf())
    assert cfg.mla and cfg.qk_head_dim == 48 and cfg.head_dim == 32
    x, xf = _input(cfg)
    p = _layer(params["blocks"]["lead"], 0)
    y, _, _ = B.attn_sublayer(p, x, _tokens(cfg, "lead"), BF16_BASELINE,
                              cfg, "train", None, None)
    want = jax.vmap(lambda s: fam._attention(
        cfg, _layer(ref["lead"], 0), s, 0, True))(xf)
    assert _rel(y, want) < 2e-2


def _program_moe(cfg, params, x, layer=0):
    from repro.core import BF16_BASELINE
    from repro.models import blocks as B

    p = _layer(params["blocks"]["moe"], layer)["moe"]
    return B.moe_sublayer(p, x, _tokens(cfg, "moe"), BF16_BASELINE, cfg)


def _reference_moe(fam, cfg, ref, xf, layer=0):
    return jax.vmap(lambda s: fam.moe(cfg, _layer(ref["moe"], layer), s))(xf)


def _with_bias(params, ref, bias):
    params = jax.tree.map(lambda a: a, params)
    params["blocks"]["moe"]["moe"]["bias"] = bias
    ref = dict(ref, moe=dict(ref["moe"], bias=bias))
    return params, ref


def test_moe_layer_with_a_selection_bias_matches_the_reference(cpu_devices):
    fam, cfg, params, ref = _setup(tiny_conf())
    n_moe = cfg.n_layers - cfg.n_lead
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (n_moe, E))
    params, ref = _with_bias(params, ref, bias)
    x, xf = _input(cfg)
    (y, st), (want, aux) = (_program_moe(cfg, params, x),
                            _reference_moe(fam, cfg, ref, xf))
    assert _rel(y, want) < 2e-2
    np.testing.assert_allclose(float(st["aux_loss"]), float(np.mean(aux)),
                               rtol=1e-5)
    # The bias moves the choice of experts, and the gates stay the scores.
    p = _layer(ref["moe"], 0)
    idx, gate, _ = fam.route(cfg, p, xf[0])
    idx0, _, _ = fam.route(cfg, dict(p, bias=jnp.zeros_like(p["bias"])),
                           xf[0])
    assert not np.array_equal(np.sort(idx, -1), np.sort(idx0, -1))
    np.testing.assert_allclose(np.sum(gate, -1), cfg.routed_scale,
                               rtol=1e-5)


def test_skewed_router_drops_no_copy(cpu_devices):
    """A bias that sends every token's copies to the held experts: the
    buffer's worst case, T * min(K, held) rows. Every copy is computed
    (none dropped) and the layer is the reference's."""
    held = 4
    conf = tiny_conf(held=held, first=8)
    fam, cfg, params, ref = _setup(conf)
    n_moe = cfg.n_layers - cfg.n_lead
    bias = jnp.zeros((n_moe, E)).at[:, 8:8 + held].set(10.0)
    params, ref = _with_bias(params, ref, bias)
    x, xf = _input(cfg)
    y, st = _program_moe(cfg, params, x)
    want, _ = _reference_moe(fam, cfg, ref, xf)
    T, K = x.shape[0] * x.shape[1], cfg.top_k
    assert float(st["routed_rows"]) == T * min(K, held)
    assert float(st["padded_rows"]) == (-(T * K // held) % 128) * held
    assert float(st["load_max_over_mean"]) == 1.0
    assert _rel(y, want) < 2e-2


def test_shares_of_the_experts_add_up_to_the_uncut_layer(cpu_devices):
    """Eight shares of E/8 experts each, as eight chips of the expert-
    parallel group hold them: their routed parts, with the shared
    experts counted once, add up to the uncut reference layer."""
    fam, cfg, params, ref = _setup(tiny_conf())
    x, xf = _input(cfg)
    want, _ = _reference_moe(fam, cfg, ref, xf)
    per = E // 8
    total = 0.0
    for s in range(8):
        conf = tiny_conf(held=per, first=s * per,
                         n_shared_experts=2 if s == 0 else 0)
        cfg_s = _family().arch_config(conf)
        p = jax.tree.map(lambda a: a, params)
        m = p["blocks"]["moe"]["moe"]
        m["w1"] = m["w1"][:, s * per:(s + 1) * per]
        m["w2"] = m["w2"][:, s * per:(s + 1) * per]
        if s:
            del m["shared"]
        y, _ = _program_moe(cfg_s, p, x)
        total = total + np.asarray(y, np.float32)
    assert _rel(total, want) < 2e-2


def test_model_loss_and_gradients_match_the_reference(cpu_devices):
    from chipbench import check
    from chipbench import reference as R
    from repro.core import BF16_BASELINE
    from repro.models import make_loss_fn, make_tokens

    fam, cfg, params, _ = _setup(tiny_conf())
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss_fn = make_loss_fn(cfg, BF16_BASELINE, remat=False)
    (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, make_tokens(cfg), batch)
    master = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        loss, want = fam.batch_grads(cfg, master, batch["tokens"],
                                     batch["labels"])
    assert abs(float(aux["loss"]) - float(loss)) < 2e-3
    g = {p: v for p, v in R.leaf_norms(want).items()}
    med = float(np.median(np.concatenate(list(g.values()))))
    keep = {p: v >= check.NEGLIGIBLE_GRAD * med for p, v in g.items()}
    gap, where = check._norm_gap(R.leaf_norms(grads), g, keep)
    assert gap < 3e-2, where
    # The selection bias gets no gradient.
    assert not np.asarray(grads["blocks"]["moe"]["moe"]["bias"]).any()


# -------------------------------------------- a cell run from new files --
TRAIN_LIMITS = {"limits": {"loss_gap": 0.02, "grad_norm_gap": 0.02,
                           "change_norm_gap": 0.05}}

RUN_TINY = """
import json, types
import jax
from chipbench import run, spec
cell = spec.load_cell("tiny.moonlight")
res, table = run.run_cell(cell, types.SimpleNamespace(
    seed=2**31 + 17, seconds=1.0, trace=0), jax.devices("cpu")[:1])
print(json.dumps({"correct": res["correct"], "table": table,
                  "family": cell.family.__file__}))
"""


def test_a_tiny_moonlight_cell_runs_through_chipbench_run(tmp_path):
    """The benchmark copied as it stands, with a tiny configuration of
    the family and a tiny job as data only: the cell runs the program's
    train step and comes out correct against the family's reference."""
    root = tmp_path
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", root / "src")
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_conf(held=4, first=4)))
    job = _json("traffic/s2048b8.json")
    job.update(seq_len=64, global_batch=2, trace_steps=2)
    (root / "chipbench" / "traffic" / "tiny.json").write_text(
        json.dumps(job))
    (root / "chipbench" / "limits" / "tiny.moonlight.json").write_text(
        json.dumps(TRAIN_LIMITS))
    bench = {
        "configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.moonlight", "config": "tiny",
                       "traffic": "tiny", "chips": 1}],
        "end_to_end": [{"name": n, "unit": "x"}
                       for n in ("train_tokens_per_s", "setup_s")],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=f"{root}:{root / 'src'}")
    out = subprocess.run([sys.executable, "-c", RUN_TINY], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["family"].endswith("chipbench/families/moonlight.py")
    assert res["correct"], res["table"]


# ------------------------------------------------------------ scopes --
def test_moe_train_step_ops_each_carry_one_layer():
    """The tiny MoE train step compiled for the CPU: every dot, custom
    call, reduce and fusion with an op_name names one layer, and the MoE
    layer's parts and the latent attention's scope are there."""
    from chipbench import spec
    from chipbench.metrics._moe import PARTS, part_of
    from scoped_hlo import check_step_scopes, op_names, train_step_hlo

    job = _json("traffic/s2048b8.json")
    job.update(seq_len=64, global_batch=2)
    cell = spec.Cell(name="tiny.moe", chips=1, config=tiny_conf(held=4),
                     traffic=job, limits=None, end_to_end=[], per_layer=[])
    text = train_step_hlo(cell)
    check_step_scopes(text)
    names = set(op_names(text))
    parts = {part_of(op) for op in names} - {None}
    assert {p for p, _ in parts} == set(PARTS)
    assert ("experts", True) in parts and ("route", False) in parts
    assert any("/attn/latent/" in op for op in names)


@pytest.mark.parametrize("tf_op, part", [
    ("jit(train_step)/jvp(stack)/while/body/closed_call/moe/route/logistic",
     ("route", False)),
    ("jit(train_step)/transpose(jvp(stack))/while/body/closed_call/"
     "checkpoint/moe/experts/mor_quant/fwd_x/jit(gam_quant_groups)/"
     "gam_group_quant/pallas_call", ("experts", True)),
    ("transpose(jvp(moe))/combine/while/body/gather:", ("combine", False)),
    ("jit(step)/moe/shared/mlp/fc1/gemm/fwd/dot_general", ("shared", True)),
    ("jit(step)/moe/dispatch/argsort;other", ("dispatch", False)),
    ("jit(step)/attn/core/exp", None),
    ("jit(step)/moe/add", None),
    ("", None),
])
def test_moe_part_path_rule(tf_op, part):
    from chipbench.metrics._moe import part_of

    assert part_of(tf_op) == part


@pytest.mark.parametrize("name", ["moe_route_ms.train",
                                  "moe_experts_ms.train",
                                  "gam_group_quant_roofline"])
def test_moe_readers_find_nothing_in_a_dense_trace(monkeypatch, tmp_path,
                                                   name):
    """The recorded dense step has no MoE scope and no grouped kernel,
    and its family no experts: each reader returns None, never 0."""
    import types

    from chipbench import counts, metrics, spec, trace

    fixture = ROOT / "tests" / "chipbench" / "data" / "v5e_scopes.xplane.pb"
    monkeypatch.setattr(trace, "OUT", tmp_path)
    cell = types.SimpleNamespace(
        name="fixture.cell", traffic={"global_batch": 2, "seq_len": 8},
        family=spec.family_of({"family": "dense"}))
    dst = tmp_path / "trace" / cell.name / "plugins" / "profile" / "1"
    dst.mkdir(parents=True)
    shutil.copy(fixture, dst / "host.xplane.pb")
    ctx = metrics.Ctx(cell=cell, cfg=None,
                      trace=trace.reduce_file(str(fixture), 1), counters={},
                      peaks=counts.peaks("TPU v5 lite"), chips=1)
    assert metrics.reader(name)(ctx) is None


def test_group_quant_roofline_counts_the_live_rows():
    """Least bytes of the grouped kernels: the expected live rows of one
    layer's buffer read (error pass) and read and written (apply pass),
    never the buffer's static bound."""
    import types

    from chipbench import counts, metrics

    fam = _family()
    cfg = fam.arch_config(_json(f"configs/{CONFIG}.json"))
    T = 7 * 2048
    rows = fam.expected_group_rows(cfg, T)
    assert rows == T * 6 * 8 / 64 + 8 * 127 / 2

    def op(name, shape, dur):
        return types.SimpleNamespace(
            short=name, dur=dur,
            text=f"%{name}.1 = bf16[{shape[0]},{shape[1]}]{{1,0}} "
                 f"custom-call(bf16[{shape[0]},{shape[1]}]{{1,0}} %x)")

    pk = counts.peaks("TPU v5 lite")
    bound = (T * 6 + 8 * 127 + 127) // 128 * 128
    ops = [op("gam_group_quant", (bound, 2048), 1_000_000),
           op("gam_group_err", (bound, 2048), 1_000_000),
           op("gam_quant_blocks", (bound, 2048), 5)]
    ctx = metrics.Ctx(
        cell=types.SimpleNamespace(family=fam,
                                   traffic={"global_batch": 7,
                                            "seq_len": 2048}),
        cfg=cfg, trace=types.SimpleNamespace(ops={0: ops}), counters={},
        peaks=pk, chips=1)
    least = (2 * rows * 2048 * 2 + rows * 2048 * 2) / pk["hbm_bytes_per_s"]
    got = metrics.reader("gam_group_quant_roofline")(ctx)
    assert got == pytest.approx(100 * least / 2e-3)


# ------------------------------------------------------------ counts --
def test_parameters_of_the_cell_are_the_configurations():
    from chipbench import spec

    cell = spec.load_cell(CELL)
    cfg = cell.family.arch_config(cell.config)
    table = cell.family.leaf_table(cfg)
    n = sum(int(np.prod(shape)) for shape, _, _ in table.values())
    assert n == cell.config["parameters"] == 668_890_432
    # The program's count leaves out the block norms' scales and the
    # selection bias.
    small = sum(int(np.prod(shape)) for path, (shape, _, _) in table.items()
                if path.endswith(("ln1/scale", "ln2/scale",
                                  "final_norm/scale", "moe/bias")))
    assert cfg.param_count() == n - small
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert) == (64, 8, 0)
    assert table["blocks/moe/moe/w1"][0] == (5, 8, 2048, 2816)
    assert table["blocks/lead/mlp/wi"][0] == (1, 2048, 22528)


def test_step_flops_by_hand():
    from chipbench import spec, train

    cell = spec.load_cell(CELL)
    d, H, S, B = 2048, 16, 2048, 8
    attn = d * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    expert = 3 * d * 1408
    per_token = (6 * attn + 3 * d * 11264
                 + 5 * (d * 64 + 2 * expert + 6 * 8 / 64 * expert)
                 + d * 20480)
    fwd = 2 * per_token * B * S + B * 6 * S * S * H * (192 + 128)
    assert train.step_flops(cell) == pytest.approx(3 * fwd, rel=1e-12)


def test_moe_readers_sum_their_parts(monkeypatch):
    """A synthetic trace of two steps: each op named by its ``tf_op``.
    ``moe_route_ms.train`` sums route, dispatch and combine but for the
    products and quantization under them; ``moe_experts_ms.train`` sums
    everything under experts; ops elsewhere count in neither."""
    import types

    from chipbench import metrics, trace
    from chipbench.metrics import _moe

    step = "jit(train_step)/jvp(stack)/while/body/closed_call/mlp/moe"
    ops = {  # instruction -> (tf_op, ns)
        "%a": (f"{step}/route/logistic", 1_000_000),
        "%b": (f"{step}/dispatch/gather", 2_000_000),
        "%c": (f"transpose(jvp({step}))/combine/while/body/gather", 3_000_000),
        "%d": (f"{step}/combine/gemm/fwd/dot_general", 50_000_000),
        "%e": (f"{step}/experts/mor_quant/fwd_x/gam_group_quant", 4_000_000),
        "%f": (f"{step}/experts/gemm/fwd/ragged_dot", 6_000_000),
        "%g": (f"{step}/shared/mlp/fc1/gemm/fwd/dot_general", 70_000_000),
        "%h": ("jit(train_step)/attn/core/exp", 80_000_000),
    }
    evs, t = [], 0
    for name, (_, ns) in ops.items():
        evs.append(trace.Op(f"{name} = f32[] fusion()", t, ns))
        t += ns
    tf = {f"{name} = f32[] fusion()": op for name, (op, _) in ops.items()}
    red = types.SimpleNamespace(
        ops={0: evs}, t0=0, t1=t,
        spans=[trace.Span(_moe.STEP_SPAN, 0, t)] * 2)
    monkeypatch.setattr(_moe, "trace_file", lambda name: "x.xplane.pb")
    monkeypatch.setattr(_moe.os.path, "getmtime", lambda path: 0.0)
    monkeypatch.setattr(_moe, "_tf_ops_by_device", lambda p, m: {0: tf})
    ctx = metrics.Ctx(cell=types.SimpleNamespace(name="synthetic"),
                      cfg=None, trace=red, counters={}, peaks={}, chips=1)
    assert metrics.reader("moe_route_ms.train")(ctx) == pytest.approx(3.0)
    assert metrics.reader("moe_experts_ms.train")(ctx) == pytest.approx(5.0)
