"""Substrate tests: data determinism, checkpoint round-trip + fault
tolerance, trainer resume, optimizer math, serving engine, QTensor path."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, latest_step
from repro.configs import get_config, reduced
from repro.core import MoRPolicy, TENSOR_MOR
from repro.data import DataConfig, SyntheticLM, prefetch
from repro.models import init_params
from repro.optim import AdamWConfig, adamw_update, cosine_lr, init_opt_state
from repro.serve import Engine, Request, ServeConfig, quantize_params
from repro.serve.quantized import quantize_weight
from repro.train import Trainer, TrainerConfig, TrainConfig


# ------------------------------------------------------------------ data --
def test_data_deterministic_and_sharded():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=8, num_shards=2,
                     shard_id=0)
    a = SyntheticLM(cfg).batch_at(7)
    b = SyntheticLM(cfg).batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    other = SyntheticLM(dataclasses.replace(cfg, shard_id=1)).batch_at(7)
    assert not np.array_equal(a["tokens"], other["tokens"])
    # Labels are next-token shifted.
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_data_is_learnable_structure():
    cfg = DataConfig(vocab=64, seq_len=64, global_batch=4, order=1.0)
    b = SyntheticLM(cfg).batch_at(0)
    perm = SyntheticLM(cfg).perm
    np.testing.assert_array_equal(perm[b["tokens"]], b["labels"])


def test_prefetch_preserves_order():
    it = prefetch(iter(range(10)), depth=3)
    assert list(it) == list(range(10))


# ------------------------------------------------------------ checkpoint --
def test_checkpoint_roundtrip_and_keep(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    for s in (10, 20, 30):
        ck.save(s, jax.tree.map(lambda x: x * s, tree))
    assert latest_step(str(tmp_path)) == 30
    assert not os.path.exists(tmp_path / "step_10")  # gc'd
    got = ck.restore(30, tree)
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.arange(6).reshape(2, 3) * 30)


def test_checkpoint_atomicity(tmp_path):
    """A leftover .tmp dir must never be visible as a checkpoint."""
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(5, {"x": jnp.ones(3)})
    os.makedirs(tmp_path / "step_99.tmp")
    assert latest_step(str(tmp_path)) == 5


# -------------------------------------------------------------- optimizer --
def test_adamw_decreases_quadratic_loss():
    cfg = AdamWConfig(peak_lr=0.1, final_lr=0.1, warmup_steps=0,
                      total_steps=100, weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0], jnp.bfloat16)}
    opt = init_opt_state(params)

    def loss(p):
        return jnp.sum(p["w"].astype(jnp.float32) ** 2)

    val = None
    for _ in range(50):
        g = jax.grad(loss)(jax.tree.map(lambda m: m.astype(jnp.bfloat16),
                                        opt.master))
        params, opt, _ = adamw_update(cfg, g, opt)
        val = loss(params)
    assert float(val) < 0.5


def test_cosine_lr_schedule():
    cfg = AdamWConfig(peak_lr=1.0, final_lr=0.1, warmup_steps=10,
                      total_steps=110)
    assert float(cosine_lr(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(cosine_lr(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(cosine_lr(cfg, jnp.asarray(110))) == pytest.approx(
        0.1, abs=1e-6
    )


# --------------------------------------------------------------- trainer --
def _tiny_trainer(tmp_path, total_steps, ckpt_every=5):
    cfg = dataclasses.replace(
        reduced(get_config("llama3-8b")), vocab=128
    )
    return Trainer(
        cfg,
        TENSOR_MOR,
        TrainConfig(optimizer=AdamWConfig(
            peak_lr=1e-3, final_lr=1e-4, warmup_steps=5, total_steps=200
        )),
        TrainerConfig(
            total_steps=total_steps, ckpt_dir=str(tmp_path),
            ckpt_every=ckpt_every, log_every=100,
        ),
        DataConfig(vocab=128, seq_len=32, global_batch=4),
    )


def test_trainer_runs_and_loss_drops(tmp_path):
    out = _tiny_trainer(tmp_path / "a", total_steps=30).run()
    losses = [h["loss"] for h in out["history"]]
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_trainer_restart_resumes_bitexact(tmp_path):
    d = tmp_path / "b"
    # Run 1: 10 steps (checkpoint at 5 and 10).
    r1 = _tiny_trainer(d, total_steps=10, ckpt_every=5).run()
    # Simulated failure: new trainer, same dir -> resumes from step 10.
    t2 = _tiny_trainer(d, total_steps=14, ckpt_every=5)
    r2 = t2.run()
    assert r2["history"][0]["step"] == 10
    # Reference: uninterrupted 14-step run.
    r3 = _tiny_trainer(tmp_path / "c", total_steps=14).run()
    l_resumed = [h["loss"] for h in r2["history"]]
    l_straight = [h["loss"] for h in r3["history"][10:]]
    # Checkpoint state round-trips bit-exactly; the residual tolerance is
    # XLA-CPU thread-pool reduction-order nondeterminism (order changes
    # under load), not resume error -- first resumed steps match exactly.
    np.testing.assert_allclose(l_resumed, l_straight, rtol=5e-4)


def test_trainer_donated_state_resumes_bitexact(tmp_path):
    """The step donates params and opt state (updated in place), keeps
    every param dtype, and a restart restores the state a donating run
    checkpointed bit for bit."""
    tr = _tiny_trainer(tmp_path / "e", total_steps=6, ckpt_every=3)
    params = init_params(tr.cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    batch = jax.tree.map(
        jnp.asarray, SyntheticLM(tr.data_cfg).batch_at(0)
    )
    new_params, new_opt, _ = tr.step_fn(params, opt, batch)
    old = jax.tree.leaves((params, opt))
    assert all(leaf.is_deleted() for leaf in old)
    assert jax.tree.map(lambda a: a.dtype, new_params) == jax.tree.map(
        lambda a: a.dtype, params
    )

    r1 = tr.run()
    r2 = _tiny_trainer(tmp_path / "e", total_steps=6, ckpt_every=3).run()
    assert r2["history"] == []  # resumed at the last step: nothing to run
    for a, b in zip(
        jax.tree.leaves((r1["params"], r1["opt_state"])),
        jax.tree.leaves((r2["params"], r2["opt_state"])),
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_straggler_watchdog(tmp_path):
    hits = []
    tr = _tiny_trainer(tmp_path / "d", total_steps=12)
    tr.straggler_cb = lambda step, ratio: hits.append((step, ratio))
    tr.run_cfg = dataclasses.replace(
        tr.run_cfg, straggler_factor=0.0  # every step is a "straggler"
    )
    tr.run()
    assert len(hits) > 0


# --------------------------------------------------------------- serving --
def test_engine_batched_decode():
    cfg = dataclasses.replace(reduced(get_config("gemma-2b")), vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, TENSOR_MOR, params, ServeConfig(slots=3, max_seq=64))
    rng = np.random.default_rng(0)
    reqs = [
        Request(i, rng.integers(0, 128, 8).astype(np.int32), max_tokens=4)
        for i in range(5)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r in reqs:
        assert r.done and len(r.out) >= 4
        assert all(0 <= t < 128 for t in r.out)


def test_qtensor_weight_quantization():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    qt, st = quantize_weight(w, MoRPolicy(recipe="tensor"))
    assert qt.is_quantized and st["quantized"] == 1.0
    deq = np.asarray(qt.dequant(), np.float32)
    rel = np.abs(deq - np.asarray(w)) / (np.abs(np.asarray(w)) + 1e-6)
    assert np.median(rel) < 0.05
    # Wide-dynamic-range tensor falls back to BF16 storage.
    bad = jnp.asarray(
        np.exp2(rng.uniform(-30, 30, (256, 128))).astype(np.float32)
    )
    qt2, st2 = quantize_weight(bad, MoRPolicy(recipe="tensor"))
    assert not qt2.is_quantized and st2["quantized"] == 0.0


def test_quantize_params_tree():
    cfg = dataclasses.replace(reduced(get_config("llama3-8b")), vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(2))
    qparams, stats = quantize_params(
        params, MoRPolicy(recipe="tensor"), min_size=1024
    )
    assert len(stats) > 0
    frac_q = np.mean([s["quantized"] for s in stats.values()])
    assert frac_q > 0.9  # gaussian init weights all quantize


def test_engine_decode_with_quantized_weights():
    """The serving engine over sub-tensor QTensor weights: every matmul
    against a quantized leaf runs through the mixed-representation block
    GEMM, and greedy decode still completes."""
    cfg = dataclasses.replace(reduced(get_config("llama3-8b")), vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        cfg, TENSOR_MOR, params, ServeConfig(slots=2, max_seq=64),
        quantize=MoRPolicy(recipe="sub3"), quantize_min_size=1024,
    )
    assert eng.qstats and any(
        s["quantized"] for s in eng.qstats.values()
    ), eng.qstats
    # The layer-stacked block weights must be covered, not just lm_head.
    assert any("blocks/" in name for name in eng.qstats), eng.qstats
    rng = np.random.default_rng(0)
    reqs = [
        Request(i, rng.integers(0, 128, 8).astype(np.int32), max_tokens=4)
        for i in range(3)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r in reqs:
        assert r.done and len(r.out) >= 4
        assert all(0 <= t < 128 for t in r.out)


def test_train_step_with_fused_mixed_gemm():
    """A full jitted train step (scan over layers, remat, custom_vjp,
    ZeRO-2 constraints) with every GEMM routed through the mixed-
    representation kernel: finite loss, stats populated."""
    from repro.core import paper_default
    from repro.data import SyntheticLM
    from repro.optim import init_opt_state
    from repro.train import make_train_step

    cfg = dataclasses.replace(
        reduced(get_config("llama3-8b")), vocab=128
    )
    pol = paper_default("sub3")
    pol = pol.replace(
        act=pol.act.replace(backend="xla"),
        weight=pol.weight.replace(backend="xla"),
        grad=pol.grad.replace(backend="xla"),
        fuse_gemm=True,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(
        cfg, pol,
        TrainConfig(optimizer=AdamWConfig(
            peak_lr=1e-3, final_lr=1e-4, warmup_steps=2, total_steps=10
        )),
    ))
    data = SyntheticLM(DataConfig(vocab=128, seq_len=32, global_batch=4))
    batch = jax.tree.map(jnp.asarray, data.batch_at(0))
    params, opt, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    # The mixed path must still report MoR decisions.
    assert float(m["fwd_rel_err"]) > 0.0


# ------------------------------------------------------------ mor stats --
def test_summarize_mor_stats_uses_stats_width():
    """Regression: train_step's stats-leaf filter must track STATS_WIDTH
    (it used to hard-code 8 and would silently drop every stats row if
    the layout grew)."""
    from repro.core import STATS_WIDTH
    from repro.train.train_step import summarize_mor_stats

    row = np.zeros((3, STATS_WIDTH), np.float32)
    row[:, 5] = 0.5  # frac_bf16
    row[:, 1] = 0.25  # rel_err
    fwd = {"layer": jnp.asarray(row)}
    # Decoys with a non-STATS_WIDTH trailing dim must be ignored.
    bwd = {
        "stats": jnp.asarray(row),
        "decoy": jnp.ones((4, STATS_WIDTH + 1), jnp.float32),
    }
    out = summarize_mor_stats(fwd, bwd)
    assert float(out["fwd_frac_bf16"]) == pytest.approx(0.5)
    assert float(out["fwd_rel_err"]) == pytest.approx(0.25)
    assert float(out["bwd_frac_bf16"]) == pytest.approx(0.5)
