"""A model family is one file, ``chipbench/families/<family>.py``, found
by the configuration's ``family`` key: its interface, its refusal when
missing, the guard that keeps family code out of the rest of the
harness, a new family run from new files only, and the dense family's
readings pinned at the tiny size."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench_tiny import ROOT, TINY, tiny_cell

FAMILIES = sorted(p.stem for p in (ROOT / "chipbench" / "families")
                  .glob("*.py"))
INTERFACE = ("arch_config", "leaf_table", "stack", "layer", "batch_grads",
             "train_step_flops", "decode_step_flops")


@pytest.mark.parametrize("name", FAMILIES)
def test_family_file_exposes_the_interface(name):
    from chipbench import spec

    fam = spec.family_of({"family": name})
    for attr in INTERFACE:
        assert callable(getattr(fam, attr)), attr


@pytest.mark.parametrize("family", ["nope", None, "../spec"])
def test_unknown_family_is_a_spec_error(family):
    from chipbench import spec

    with pytest.raises(spec.SpecError, match="chipbench/families/"):
        spec.family_of({"family": family})


def test_load_cell_resolves_the_family(tmp_path):
    from chipbench import spec

    conf = json.loads(
        (ROOT / "chipbench" / "configs" / "nemotron3-8b-l2.json").read_text())
    conf["family"] = "nope"
    (tmp_path / "nope.json").write_text(json.dumps(conf))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"][0]["file"] = "nope.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = bench["workloads"][0]["name"]
    with pytest.raises(spec.SpecError, match="chipbench/families/nope.py"):
        spec.load_cell(cell, root=tmp_path)
    fam = spec.load_cell(cell).family
    assert fam.__file__ == str(ROOT / "chipbench" / "families" / "dense.py")


# Names of the dense decoder's leaves, source keys and mapping: only its
# family file may state them.
DENSE_ONLY = re.compile(
    r"blocks/dense|wqkv|\bln[12]\b|mlp/w[io]|\bwo2\b|_DENSE_KEYS|"
    r"intermediate_size|num_attention_heads|[\"']dense[\"']")


def test_no_dense_code_outside_its_family_file():
    bench = ROOT / "chipbench"
    hits = []
    for path in sorted(bench.rglob("*.py")):
        if path.parent.name == "families":
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if DENSE_ONLY.search(line):
                hits.append(f"{path.relative_to(ROOT)}:{i}: {line.strip()}")
    assert not hits, "\n".join(hits)


# ---------------------------------------------- a family from new files --
TRAIN_LIMITS = {"limits": {"loss_gap": 0.02, "grad_norm_gap": 0.02,
                           "change_norm_gap": 0.05}}

RUN_ALIAS = """
import json, types
import jax
from chipbench import run, spec
cell = spec.load_cell("tiny.alias")
res, table = run.run_cell(cell, types.SimpleNamespace(
    seed=2**31 + 17, seconds=1.0, trace=0), jax.devices("cpu")[:1])
print(json.dumps({"correct": res["correct"], "table": table,
                  "family": cell.family.__file__}))
"""


@pytest.fixture(scope="module")
def copied_bench(tmp_path_factory):
    """The benchmark copied as it stands, with a new family file (a copy
    of ``dense.py``) and, as data only, a tiny configuration naming it, a
    tiny job, the cell's limits and a BENCHMARK.json of two cells: that
    one, and one whose family has no file."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", root / "src")
    fams = root / "chipbench" / "families"
    shutil.copy(fams / "dense.py", fams / "dense_alias.py")
    conf = json.loads((ROOT / "chipbench" / "configs" /
                       "nemotron3-8b-l2.json").read_text())
    conf.update(TINY, family="dense_alias")
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(conf))
    conf.update(family="nope")
    (root / "chipbench" / "configs" / "nope.json").write_text(
        json.dumps(conf))
    (root / "chipbench" / "traffic" / "tiny.json").write_text(
        json.dumps(tiny_cell("train").traffic))
    (root / "chipbench" / "limits" / "tiny.alias.json").write_text(
        json.dumps(TRAIN_LIMITS))
    bench = {
        "configs": [{"name": n, "file": f"chipbench/configs/{n}.json"}
                    for n in ("tiny", "nope")],
        "workloads": [{"name": f"tiny.{c}", "config": n, "traffic": "tiny",
                       "chips": 1} for c, n in (("alias", "tiny"),
                                                ("nope", "nope"))],
        "end_to_end": [{"name": n, "unit": "x"}
                       for n in ("train_tokens_per_s", "setup_s")],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _python(root, *args, timeout=600):
    env = dict(os.environ, PYTHONPATH=f"{root}:{root / 'src'}")
    return subprocess.run([sys.executable, *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_new_family_file_runs_a_cell_from_new_files_only(copied_bench):
    out = _python(copied_bench, "-c", RUN_ALIAS)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["family"].endswith("chipbench/families/dense_alias.py")
    assert res["correct"], res["table"]


def test_run_refuses_a_family_without_a_file(copied_bench):
    out = _python(copied_bench, "-m", "chipbench.run", "--workload",
                  "tiny.nope", "--seed", "1", "--seconds", "1", timeout=120)
    assert out.returncode == 2, out.stderr[-4000:]
    assert "chipbench/families/nope.py" in out.stderr
    assert "no TPU" not in out.stderr, "refused before the device gate"
    assert not out.stdout.strip()


# ------------------------------- what the shared parts leave to a family --
def _dense_but(**attrs):
    """The dense family with some of its functions replaced."""
    from chipbench import spec

    return types.SimpleNamespace(
        **dict(vars(spec.family_of({"family": "dense"})), **attrs))


def test_batch_grads_is_the_familys():
    from chipbench import reference

    fam = _dense_but(batch_grads=lambda cfg, p, t, l, bits: (t, l, bits))
    batch = {"tokens": np.arange(8).reshape(4, 2),
             "labels": -np.arange(8).reshape(4, 2)}
    toks, labs, bits = reference.batch_grads(fam, None, {}, batch, bits=4,
                                             rows=3)
    assert toks.tolist() == [[0, 1], [2, 3], [4, 5]] and bits == 4
    assert labs.tolist() == [[0, -1], [-2, -3], [-4, -5]]


def test_stack_depth_is_the_leaf_tables():
    from chipbench import weights as W

    cell = tiny_cell("train")
    cfg = cell.family.arch_config(cell.config)
    assert W.stack_depth(cell.family, cfg) == cfg.n_layers == 2
    table = dict(cell.family.leaf_table(cfg))
    table["blocks/other/w"] = ((3, 4), table["embed"][1], W.STD)
    with pytest.raises(ValueError, match=r"\[2, 3\]"):
        W.stack_depth(_dense_but(leaf_table=lambda cfg: table), cfg)


def test_serving_runs_each_unit_the_leaf_table_stacks(cpu_devices):
    """Serving draws and runs as many units as the ``blocks/`` leaves
    stack, not ``cfg.n_layers``: a family that stacks one unit under a
    configuration of two layers serves as a one-layer model."""
    import dataclasses

    from chipbench import reference

    cell = tiny_cell("serve")
    fam = cell.family
    cfg = fam.arch_config(cell.config)
    one = dataclasses.replace(cfg, n_layers=1)
    seqs = [np.arange(1, 41, dtype=np.int32)]
    picks = [np.arange(30, 40)]
    got = reference.serve_logits(
        _dense_but(leaf_table=lambda _: fam.leaf_table(one)), cfg, 5, seqs,
        picks, pad_to=64)
    want = reference.serve_logits(fam, one, 5, seqs, picks, pad_to=64)
    np.testing.assert_array_equal(got[0], want[0])
    two = reference.serve_logits(fam, cfg, 5, seqs, picks, pad_to=64)
    assert not np.array_equal(two[0], want[0])


# ------------------------------------------------ dense family parity --
# Readings of the tiny dense cell at the parent commit 0fc128f, where the
# dense decoder still lived in spec.py, weights.py, reference.py and
# counts.py: the seeded params' digest, the reference's three losses and
# the norms of its first gradient and of its change after three steps
# (CPU, equal bit for bit on 1 and 8 cores), and the operation count of
# the nemotron3-8b-l2 cell's train step.
PARENT = {
    "digest": ("def9598afe74ef9f05d4daa1baf113ed"
               "66ff490db265d276fa29331077f2c65c"),
    "losses": [6.23961067199707, 6.284261703491211, 6.278624534606934],
    "grad_norms": {
        "blocks/dense/ln1/scale": [0.0030464378651231527,
                                   0.0032889372669160366],
        "blocks/dense/ln2/scale": [0.004264140501618385,
                                   0.004222055897116661],
        "blocks/dense/mlp/wi": [0.21673902869224548, 0.1903553158044815],
        "blocks/dense/mlp/wo": [0.39062362909317017, 0.32392820715904236],
        "blocks/dense/wo": [0.3249847888946533, 0.30195626616477966],
        "blocks/dense/wqkv": [0.17002631723880768, 0.17354458570480347],
        "embed": [0.4601386487483978],
        "final_norm/scale": [0.008746037259697914],
        "lm_head": [0.43771347403526306]},
    "change_norms": {
        "blocks/dense/ln1/scale": [0.00012190245615784079,
                                   0.00012430798960849643],
        "blocks/dense/ln2/scale": [0.00012681940279435366,
                                   0.00012044011236866936],
        "blocks/dense/mlp/wi": [0.00195865985006094, 0.0019721705466508865],
        "blocks/dense/mlp/wo": [0.0019575399346649647,
                                0.0019498987821862102],
        "blocks/dense/wo": [0.0013775439001619816, 0.0013770341174677014],
        "blocks/dense/wqkv": [0.0019517296459525824, 0.0019559236243367195],
        "embed": [0.0018202849896624684],
        "final_norm/scale": [0.0001246823085239157],
        "lm_head": [0.0031748770270496607]},
    "control_losses": [6.240791320800781, 6.287472724914551,
                       6.288588523864746],
    "step_flops": 27058293964800.0,
    # Serving reference on the tiny minitron-4b cell, seed 3, two
    # 40-token prompts, positions 30-39: each position's best token, and
    # each prompt's sum of logits (the sums move by 3e-7 with the number
    # of cores).
    "serve_argmax": [[85, 96, 399, 100, 364, 323, 461, 436, 134, 118],
                     [463, 508, 118, 173, 5, 90, 383, 16, 153, 295]],
    "serve_sums": [-27.881421292200685, -16.921303837094456],
    "control_sums": [-26.631100680300733, -10.535520302834811],
}
SEED = 2**31 + 9


def test_dense_seeded_params_equal_the_parents(cpu_devices):
    from chipbench import weights as W

    cell = tiny_cell("train")
    params = W.make_params(cell.family, cell.family.arch_config(cell.config),
                           SEED)
    h = hashlib.sha256()
    for path, x in sorted(W.flatten(params).items()):
        h.update(path.encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == PARENT["digest"]


def test_dense_reference_readings_equal_the_parents(cpu_devices):
    from chipbench import train

    cell = tiny_cell("train")
    ref = train.reference_readings(cell, SEED)
    assert ref["losses"] == PARENT["losses"]
    for key in ("grad_norms", "change_norms"):
        got = {p: v.tolist() for p, v in ref[key].items()}
        assert got == PARENT[key], key
    ctl = train.reference_readings(cell, SEED, bits=4)
    assert ctl["losses"] == PARENT["control_losses"]


def test_dense_step_flops_equal_the_parents():
    from chipbench import spec, train

    cell = spec.load_cell("train.nemotron3-8b-l2.s2048b4")
    assert train.step_flops(cell) == PARENT["step_flops"]


def test_dense_serving_reference_equals_the_parents(cpu_devices):
    from chipbench import reference

    cell = tiny_cell("serve")
    cfg = cell.family.arch_config(cell.config)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg.vocab, 40).astype(np.int32)
            for _ in range(2)]
    picks = [np.arange(30, 40) for _ in seqs]
    lg = reference.serve_logits(cell.family, cfg, 3, seqs, picks,
                                pad_to=256)
    lg4 = reference.serve_logits(cell.family, cfg, 3, seqs, picks, bits=4,
                                 pad_to=256)
    assert [x.argmax(-1).tolist() for x in lg] == PARENT["serve_argmax"]
    sums = [float(np.sum(x.astype(np.float64))) for x in lg]
    assert sums == pytest.approx(PARENT["serve_sums"], rel=1e-5)
    sums4 = [float(np.sum(x.astype(np.float64))) for x in lg4]
    assert sums4 == pytest.approx(PARENT["control_sums"], rel=1e-5)
