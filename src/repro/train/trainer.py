"""Fault-tolerant training loop.

Responsibilities beyond make_train_step:
  * checkpoint/restart: resumes bit-identically (data pipeline is a pure
    function of the step counter; RNG-free steps),
  * preemption handling: SIGTERM -> synchronous final checkpoint,
  * straggler mitigation: per-step deadline watchdog; steps that exceed
    ``straggler_factor`` x the trailing-median step time are logged with
    the host set, and repeated offenders trigger a (pluggable) callback --
    on a real cluster this is where you'd eject/replace the slow host and
    trigger the elastic re-mesh path (repro.checkpoint restores onto the
    surviving mesh),
  * MoR statistics streaming into MoRStatsTracker (Fig. 10/11 machinery).
"""
from __future__ import annotations

import dataclasses
import signal
import time
from collections import deque
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.ckpt import Checkpointer, latest_step
from repro.configs.base import ArchConfig
from repro.core import (
    STAT_FRAC_BF16,
    STAT_GROUP_MANTISSA,
    STAT_REL_ERR,
    STATS_WIDTH,
    MoRDotPolicy,
    MoRStatsTracker,
)
from repro.data.pipeline import DataConfig, SyntheticLM, prefetch
from repro.models import init_params
from repro.optim.adamw import init_opt_state
from repro.train.train_step import TrainConfig, make_train_step

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    seed: int = 0


class Trainer:
    def __init__(
        self,
        cfg: ArchConfig,
        policy: MoRDotPolicy,
        tcfg: TrainConfig,
        run_cfg: TrainerConfig,
        data_cfg: Optional[DataConfig] = None,
        straggler_cb: Optional[Callable[[int, float], None]] = None,
    ):
        self.cfg = cfg
        self.policy = policy
        self.run_cfg = run_cfg
        self.data_cfg = data_cfg or DataConfig(
            vocab=cfg.vocab, seq_len=256, global_batch=8,
            seed=run_cfg.seed,
        )
        # Donated params and opt state update in place: at full width a
        # second copy of the training state does not fit the chip.
        self.step_fn = jax.jit(
            make_train_step(cfg, policy, tcfg), donate_argnums=(0, 1)
        )
        self.tracker = MoRStatsTracker()
        self.ckpt = (
            Checkpointer(run_cfg.ckpt_dir, keep=run_cfg.keep)
            if run_cfg.ckpt_dir
            else None
        )
        self.straggler_cb = straggler_cb or (lambda step, t: None)
        self._preempted = False
        self.history: list = []

    def _install_sigterm(self):
        def handler(signum, frame):
            self._preempted = True

        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not main thread (tests)

    def run(self) -> Dict[str, Any]:
        self._install_sigterm()
        params = init_params(self.cfg, jax.random.PRNGKey(self.run_cfg.seed))
        opt_state = init_opt_state(params)
        start = 0

        if self.ckpt is not None:
            last = latest_step(self.run_cfg.ckpt_dir)
            if last is not None:
                state = self.ckpt.restore(last, (params, opt_state))
                params, opt_state = state
                start = last
        data = SyntheticLM(
            dataclasses.replace(self.data_cfg, seed=self.run_cfg.seed)
        )

        times: deque = deque(maxlen=32)
        step = start
        for step in range(start, self.run_cfg.total_steps):
            batch = jax.tree.map(
                jax.numpy.asarray, data.batch_at(step)
            )
            t0 = time.time()
            params, opt_state, metrics = self.step_fn(
                params, opt_state, batch
            )
            loss = float(metrics["loss"])  # blocks; acts as step barrier
            dt = time.time() - t0
            # Straggler watchdog.
            if len(times) >= 8:
                med = float(np.median(times))
                if dt > self.run_cfg.straggler_factor * med:
                    self.straggler_cb(step, dt / med)
            times.append(dt)

            self.history.append(
                {"step": step, "loss": loss, "dt": dt,
                 "fwd_bf16": float(metrics.get("fwd_frac_bf16", 0.0)),
                 "bwd_bf16": float(metrics.get("bwd_frac_bf16", 0.0))}
            )
            row = np.zeros(STATS_WIDTH, np.float64)
            row[STAT_REL_ERR] = float(metrics.get("fwd_rel_err", 0.0))
            row[STAT_FRAC_BF16] = float(
                metrics.get("fwd_frac_bf16", 0.0)
            )
            row[STAT_GROUP_MANTISSA] = 1.0
            self.tracker.update({"global": row}, step)
            if self.ckpt and (
                (step + 1) % self.run_cfg.ckpt_every == 0 or self._preempted
            ):
                self.ckpt.save(step + 1, (params, opt_state))
                if self._preempted:
                    self.ckpt.wait()
                    break

        if self.ckpt:
            self.ckpt.save(self.run_cfg.total_steps, (params, opt_state))
            self.ckpt.wait()
        return {
            "params": params,
            "opt_state": opt_state,
            "history": self.history,
            "final_step": step + 1,
        }
