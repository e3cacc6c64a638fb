"""The comparison that decides ``correct``, at a size a test can hold.

A sound run of the program passes; the control (the reference at int4
in the program's place) and each fault a cell can have, planted under a
run that skips the look for a chip, fail. The limits here are the tiny
size's own, set the same way as a cell's (program readings on CPU seeds
1-5 at most 0.012 on ``grad_norm_gap``, 0.0046 on ``loss_gap``, 0.0035
on ``change_norm_gap`` and 0.0063 on ``logit_gap``; the control at least
0.027, 0.034 on ``logit_gap``; half a batch at least 0.054 and 0.22).
"""
import types

import numpy as np
import pytest

from chipbench_tiny import tiny_cell

TRAIN_LIMITS = {"limits": {"loss_gap": 0.02, "grad_norm_gap": 0.02,
                           "change_norm_gap": 0.05}}
SERVE_LIMITS = {"limits": {"logit_gap": 0.02}}


def _run(cell, devs, seconds=1.0, trace=0):
    from chipbench import run

    args = types.SimpleNamespace(seed=2**31 + 9, seconds=seconds,
                                 trace=trace)
    return run.run_cell(cell, args, devs)


def test_train_sound_run_is_correct(cpu_devices):
    res, table = _run(tiny_cell("train", TRAIN_LIMITS), cpu_devices)
    assert res["correct"], table
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(table) == {"loss_gap", "grad_norm_gap", "change_norm_gap"}


def test_train_control_fails(cpu_devices):
    from chipbench import check, train

    cell = tiny_cell("train", TRAIN_LIMITS)
    ref = train.reference_readings(cell, 4)
    ctl = train.reference_readings(cell, 4, bits=4)
    ok, table = check.judge(check.train_numbers(ctl, ref), cell.limits)
    assert not ok, table


def _patch_step(monkeypatch, wrap):
    from chipbench import train

    orig = train.TrainSession.__init__

    def init(self, cell, seed):
        orig(self, cell, seed)
        self.trainer.step_fn = wrap(self.trainer.step_fn)

    monkeypatch.setattr(train.TrainSession, "__init__", init)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_train_fault_fails(cpu_devices, monkeypatch, fault):
    def wrap(step):
        def broken(params, opt, batch):
            if fault == "unchanged_state":
                _, _, m = step(jax_copy(params), jax_copy(opt), batch)
                return params, opt, m
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt, half)
        return broken

    _patch_step(monkeypatch, wrap)
    res, table = _run(tiny_cell("train", TRAIN_LIMITS), cpu_devices)
    assert not res["correct"], table


def jax_copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, tree)


def test_serve_sound_run_is_correct(cpu_devices):
    res, table = _run(tiny_cell("serve", SERVE_LIMITS), cpu_devices,
                      seconds=2.0)
    assert res["correct"], table
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_serve_control_fails(cpu_devices):
    from chipbench import check, reference

    cell = tiny_cell("serve", SERVE_LIMITS)
    cfg = cell.family.arch_config(cell.config)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg.vocab, 40).astype(np.int32)
            for _ in range(4)]
    picks = [np.arange(10, 40) for _ in seqs]
    fam = cell.family
    ref = reference.serve_logits(fam, cfg, 3, seqs, picks, pad_to=256)
    ctl = reference.serve_logits(fam, cfg, 3, seqs, picks, bits=4,
                                 pad_to=256)
    nums = check.serve_numbers([c.argmax(-1) for c in ctl], ref)
    ok, table = check.judge(nums, cell.limits)
    assert not ok, table


def test_serve_altered_token_fails(cpu_devices, monkeypatch):
    from repro.serve import Engine

    orig = Engine._sample

    def altered(self, req, row):
        tok = orig(self, req, row)
        return (tok + 1) % self.cfg.vocab if req.temperature <= 0 else tok

    monkeypatch.setattr(Engine, "_sample", altered)
    res, table = _run(tiny_cell("serve", SERVE_LIMITS), cpu_devices,
                      seconds=2.0)
    assert not res["correct"], table


def test_traced_run_reads_host_metrics(cpu_devices, monkeypatch):
    from chipbench import counts

    monkeypatch.setattr(counts, "peaks", lambda kind: {
        "peak_flops_bf16": 1e12, "hbm_bytes_per_s": 1e11})
    cell = tiny_cell("serve", SERVE_LIMITS)
    cell.per_layer.extend({"name": n, "unit": "x"} for n in (
        "admit_wait_p90_ms", "decode_occupancy", "ttft_p90_ms", "itl_p95_ms",
        "device_idle.serve", "decode_step_ms", "mixed_gemm_roofline"))
    res, _ = _run(cell, cpu_devices, seconds=2.0, trace=1)
    # No device ops on the CPU: the device readers find nothing and the
    # line leaves them out; the host readers read.
    assert set(res["metrics"]) == {"admit_wait_p90_ms", "decode_occupancy",
                                   "ttft_p90_ms", "itl_p95_ms"}
    assert res["device"]["window_s"] > 0
    assert res["breakdown"]["idle_gaps"]


def test_judge_needs_a_limit_for_every_number():
    from chipbench import check

    limits = {"limits": {"loss_gap": 0.01}}
    assert check.judge({"loss_gap": 0.005, "_at": "x"}, limits)[0]
    assert not check.judge({"loss_gap": float("nan")}, limits)[0]
    with pytest.raises(KeyError):
        check.judge({"loss_gap": 0.005, "grad_norm_gap": 0.0}, limits)
