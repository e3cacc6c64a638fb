"""End-to-end training driver: train an LM with MoR mixed-precision,
checkpointing, restart tolerance, and MoR statistics.

Presets:
  tiny  (~2M params, 50 steps)   -- seconds; CI smoke.
  small (~25M params, 200 steps) -- minutes on CPU.
  100m  (~100M params, 300 steps)-- the deliverable-scale run (hours on
                                     CPU; minutes on one accelerator).

    PYTHONPATH=src python examples/train_lm.py --preset tiny \
        --arch llama3-8b --policy mor_block --ckpt /tmp/mor_ckpt
"""
import argparse
import dataclasses
import json

import numpy as np

from repro.configs import get_config, reduced
from repro.core import BF16_BASELINE, paper_default
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamWConfig
from repro.train import Trainer, TrainerConfig, TrainConfig

PRESETS = {
    # name: (d_model, n_layers, n_heads, d_ff, vocab, seq, batch, steps)
    "tiny": (128, 2, 4, 384, 512, 128, 8, 50),
    "small": (320, 6, 8, 1024, 2048, 256, 8, 200),
    "100m": (640, 12, 10, 2048, 8192, 512, 8, 300),
}


def build_cfg(arch: str, preset: str):
    d, L, H, f, v, seq, batch, steps = PRESETS[preset]
    base = reduced(get_config(arch))
    kv = 1 if base.n_kv == 1 else max(2, H // 4)
    cfg = dataclasses.replace(
        base,
        name=f"{arch}-{preset}",
        d_model=d,
        n_layers=L * len(base.unit),
        n_heads=H,
        n_kv=kv,
        head_dim=d // H,
        d_ff=0 if base.d_ff == 0 else f,
        vocab=v,
        n_experts=min(base.n_experts, 8),
        top_k=min(base.top_k, 2),
    )
    return cfg, seq, batch, steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--preset", default="tiny", choices=PRESETS)
    ap.add_argument("--policy", default="mor_block",
                    choices=["bf16", "mor_block", "mor_tensor",
                             "mor_channel", "sub2", "sub3", "sub4"])
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg, seq, batch, steps = build_cfg(args.arch, args.preset)
    steps = args.steps or steps
    if args.policy == "bf16":
        policy = BF16_BASELINE
    elif args.policy.startswith("mor_"):
        policy = paper_default(partition=args.policy.split("_")[1])
    else:
        policy = paper_default(args.policy)

    n_params = cfg.param_count()
    print(f"arch={cfg.name} params~{n_params/1e6:.1f}M policy={args.policy} "
          f"steps={steps} seq={seq} batch={batch}")

    trainer = Trainer(
        cfg,
        policy,
        TrainConfig(
            optimizer=AdamWConfig(
                peak_lr=args.lr, final_lr=args.lr / 10,
                warmup_steps=max(steps // 20, 5), total_steps=steps,
            )
        ),
        TrainerConfig(
            total_steps=steps,
            ckpt_dir=args.ckpt,
            ckpt_every=max(steps // 4, 10),
            log_every=10,
        ),
        DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch),
    )
    out = trainer.run()
    hist = out["history"]
    for h in hist[:: max(len(hist) // 20, 1)]:
        print(
            f"step {h['step']:5d}  loss {h['loss']:.4f}  "
            f"dt {h['dt']*1e3:7.1f}ms  fwd_bf16 {h['fwd_bf16']*100:5.1f}%  "
            f"bwd_bf16 {h['bwd_bf16']*100:5.1f}%"
        )
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    print(f"\nloss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    print(json.dumps({"final_loss": last, "steps": out["final_step"]}))


if __name__ == "__main__":
    main()
