"""Training cells: ``Trainer.step_fn`` driven from the seed.

Set-up builds one trainer, its seeded params and optimizer state, and
drives the compiled step through the checked steps on fresh batches; the
window then takes that same state on. Batches are drawn on the device
from the seed and the step index, so every row differs and every seed
does the same work.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import reference, weights as W

CHECKED_STEPS = 3


def data_key(seed: int):
    return jax.random.fold_in(W.base_key(seed), 0x0DA7A)


def make_batch_fn(cfg, job):
    B, S = job["global_batch"], job["seq_len"]

    @jax.jit
    def batch_at(key, i):
        ids = jax.random.randint(jax.random.fold_in(key, i), (B, S + 1), 0,
                                 cfg.vocab, dtype=jnp.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

    return batch_at


def policy_of(job):
    from repro.core import paper_default

    p = job["policy"]
    return paper_default(recipe=p["recipe"], partition=p["partition"])


class TrainSession:
    """One trainer with its seeded state on one chip."""

    def __init__(self, cell, seed: int):
        from repro.data import DataConfig
        from repro.optim import AdamWConfig, init_opt_state
        from repro.train import TrainConfig, Trainer, TrainerConfig

        self.cell, self.seed = cell, seed
        self.job = job = cell.traffic
        self.cfg = cfg = cell.family.arch_config(cell.config)
        self.trainer = Trainer(
            cfg, policy_of(job),
            TrainConfig(optimizer=AdamWConfig(**job["optimizer"])),
            TrainerConfig(total_steps=job["optimizer"]["total_steps"]),
            DataConfig(vocab=cfg.vocab, seq_len=job["seq_len"],
                       global_batch=job["global_batch"]),
        )
        self.params = W.make_params(cell.family, cfg, seed)
        W.check_tree(cfg, self.params)
        self.opt = jax.jit(init_opt_state)(self.params)
        self.batch_at = make_batch_fn(cfg, job)
        self.key = data_key(seed)
        self.step_index = 0
        self.losses = []

    # ---------------------------------------------------------- steps --
    def step(self):
        """One call of the compiled step on the next fresh batch."""
        batch = self.batch_at(self.key, self.step_index)
        self.params, self.opt, m = self.trainer.step_fn(
            self.params, self.opt, batch)
        self.step_index += 1
        self.losses.append(m["loss"])
        return m

    def checked_steps(self) -> Dict[str, Any]:
        """The first steps, with the program's side of the check read
        from its own state: the first gradient from Adam's first moment
        after one step, and the change from the f32 master weights
        after the last checked step against the seeded initial weights."""
        b1 = self.job["optimizer"]["b1"]
        self.step()
        grad_norms = {p: n / (1.0 - b1) for p, n in
                      reference.leaf_norms(self.opt.m).items()}
        for _ in range(CHECKED_STEPS - 1):
            self.step()
        change = reference.change_norms(self.cell.family, self.cfg,
                                        self.seed, self.opt.master)
        losses = [float(l) for l in self.losses[:CHECKED_STEPS]]
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}

    def free(self):
        self.params = self.opt = None
        self.losses = []
        self.trainer = None
        gc.collect()


def reference_readings(cell, seed: int, bits: int = 0,
                       rows: Optional[int] = None):
    """The reference's side, on the same batches as the checked steps."""
    cfg, job = cell.family.arch_config(cell.config), cell.traffic
    batch_at = make_batch_fn(cfg, job)
    key = data_key(seed)
    batches = [jax.device_get(batch_at(key, i)) for i in range(CHECKED_STEPS)]
    return reference.train_readings(cell.family, cfg, job, seed, batches,
                                    bits=bits, rows=rows,
                                    steps=CHECKED_STEPS)


def window(sess: TrainSession, seconds: float, tracer=None,
           in_flight: int = 2):
    """Steps until ``seconds`` have passed, with ``in_flight`` steps
    queued on the device so that a pause of the host does not starve it;
    the window ends when the last step's results are ready. A tracer
    profiles the first steps. Returns (steps, wall seconds, losses of
    the window)."""
    start = sess.step_index
    t0 = time.perf_counter()
    pending = []
    while True:
        i = sess.step_index - start
        if tracer is not None and i == 0:
            tracer.start()
        if tracer is not None and tracer.on:
            with tracer.span("train_step"):
                m = sess.step()
            if i == tracer.steps - 1:
                tracer.stop((sess.params, sess.opt, m))
        else:
            m = sess.step()
        pending.append(m)
        if len(pending) > in_flight:
            pending.pop(0)["loss"].block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready((sess.params, sess.opt, m))
    wall = time.perf_counter() - t0
    if tracer is not None and tracer.on:
        tracer.stop()
    losses = np.asarray([float(l) for l in sess.losses[start:]])
    return sess.step_index - start, wall, losses


def step_flops(cell) -> float:
    cfg, job = cell.family.arch_config(cell.config), cell.traffic
    return cell.family.train_step_flops(cfg, job["global_batch"],
                                        job["seq_len"])
