"""Chip smoke run: the MoR trainer and the quantized server on one TPU,
at the published widths of nemotron3-8b.

    python chip_smoke.py                # one chip: train + serve phases
    python chip_smoke.py --four-chips   # 2x2-mesh train step vs one chip

Configuration: nemotron3-8b widths as published (d_model 4096, 32 MHA
heads of 128, d_ff 16384 squared-ReLU, rope theta 1e4), depth cut to 2
layers (whole periods of its one-layer pattern), vocabulary sliced to
32,000 (an eighth of 256,000: the share of one of eight chips splitting
the vocabulary); token ids are drawn from the slice. Weights are random
from ``--seed``.

Phases (one process; a failed check exits non-zero and the ``ok`` line
is not printed):

1. device gate: a TPU backend, no ``REPRO_KERNEL_INTERPRET``, and the
   kernel backend resolving to ``pallas``;
2. train: ``Trainer`` step (``--policy mor_block``), sequence 2048,
   global batch 4, one compile step then 5 steps on one fixed batch;
   losses finite and falling, first loss against the same step built
   with ``backend="xla"``, Mosaic kernels present in the compiled HLO;
3. serve: ``Engine`` with sub3 quantized weights, 4 slots, max_seq
   2048, 4 requests of 256 prompt tokens and 32 new tokens; every
   request finishes, and one prefill's logits agree with the XLA
   lowering of the same quantized params;
4. report: smoke numbers, then the JSON line ``{"ok": true, ...}``.

With ``--four-chips`` only the sharded train step and its one-chip
comparison run (see :func:`four_chip_phase`).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
)

SEQ = 2048
BATCH = 4
TIMED_STEPS = 5
SLOTS = 4
MAX_SEQ = 2048
PROMPT = 256
NEW_TOKENS = 32

# |loss(pallas) - loss(xla)| on the first step. Both lowerings snap
# every element to the same fp8 value (bit-exact on a v5e); XLA keeps
# excess precision where Mosaic rounds a stored value to bf16, which
# moved this loss by 6.6e-4 on the chip. An XLA lowering that skipped
# the quantization moved it by 1.9e-3, so the bound sits between.
TRAIN_LOSS_ATOL = 1e-3
# max |logits(pallas) - logits(xla)| / max |logits(xla)| of one prefill
# on the same quantized weights. A single mixed GEMM is bit-identical
# in the two lowerings on a v5e, but the rest of each program rounds
# activations to bf16 in its own places: one-ulp flips (2^-8) amplified
# by 2 layers and the head. Either lowering differs from the CPU
# backend by 8e-3 to 9e-3 on this configuration; a wrong block decode
# moves logits by O(1).
SERVE_LOGITS_RTOL = 2e-2
# |loss(2x2 mesh) - loss(one chip)|: GSPMD splits the contractions over
# 'model' and reduces partial sums in another order; an element that
# lands on an fp8 rounding boundary then moves by one fp8 step.
MESH_LOSS_ATOL = 2e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def smoke_config():
    """nemotron3-8b at published widths, 2 layers, 32,000-token slice."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("nemotron3-8b"), name="nemotron3-8b-smoke",
        n_layers=2, vocab=32000,
    )


def smoke_policy(backend: str = "auto"):
    """The README's default policy (``--policy mor_block``) with the
    quantization events on ``backend``."""
    from repro.core import paper_default

    pol = paper_default(partition="block")
    return pol.replace(
        act=pol.act.replace(backend=backend),
        weight=pol.weight.replace(backend=backend),
        grad=pol.grad.replace(backend=backend),
    )


def smoke_train_config():
    from repro.optim import AdamWConfig
    from repro.train import TrainConfig

    return TrainConfig(optimizer=AdamWConfig(
        peak_lr=1e-3, final_lr=1e-4, warmup_steps=1,
        total_steps=TIMED_STEPS + 1,
    ))


def smoke_batch(cfg, seed: int):
    """One fixed (BATCH, SEQ) next-token batch, made on the device."""
    import jax

    ids = jax.random.randint(
        jax.random.PRNGKey(seed), (BATCH, SEQ + 1), 0, cfg.vocab
    )
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def _trainer(cfg, pol, seed):
    from repro.data import DataConfig
    from repro.train import Trainer, TrainerConfig

    return Trainer(
        cfg, pol, smoke_train_config(),
        TrainerConfig(total_steps=TIMED_STEPS + 1, seed=seed),
        DataConfig(vocab=cfg.vocab, seq_len=SEQ, global_batch=BATCH),
    )


def _fresh_state(cfg, seed):
    import jax

    from repro.models import init_params
    from repro.optim import init_opt_state

    params = init_params(cfg, jax.random.PRNGKey(seed))
    return params, init_opt_state(params)


def _mem(dev, key="peak_bytes_in_use") -> int:
    """A ``memory_stats()`` counter; -1 where the backend has none."""
    stats = dev.memory_stats() or {}
    return int(stats.get(key, -1))


def _shard_bytes(tree, devs):
    """Bytes of ``tree``'s array shards on each of ``devs``."""
    import jax

    held = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            held[s.device] += s.data.nbytes
    return [held[d] for d in devs]


def device_gate():
    check(
        "REPRO_KERNEL_INTERPRET" not in os.environ,
        "REPRO_KERNEL_INTERPRET is set: kernels would run interpreted",
    )
    import jax

    from repro.kernels import ops as kops

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: first device is {dev.platform}")
    check(kops.resolve_backend() == "pallas",
          "kernel backend does not resolve to pallas")
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"(jax {jax.__version__})")
    return dev


def train_phase(cfg, dev, seed):
    """Returns the step's numbers; checks what holds on any device."""
    import jax

    batch = smoke_batch(cfg, seed + 1)

    # Reference: the same first step with every quantization event on
    # the XLA lowering. Run first and freed, so only one training state
    # is ever on the chip.
    ref = _trainer(cfg, smoke_policy("xla"), seed)
    params, opt = _fresh_state(cfg, seed)
    compiled = ref.step_fn.lower(params, opt, batch).compile()
    ref_kernels = compiled.as_text().count("tpu_custom_call")
    out = compiled(params, opt, batch)
    ref_loss = float(out[2]["loss"])
    ref_fp8 = [1.0 - float(out[2][k]) for k in ("fwd_frac_bf16",
                                                 "bwd_frac_bf16")]
    del out, params, opt, ref, compiled
    gc.collect()

    tr = _trainer(cfg, smoke_policy(), seed)
    params, opt = _fresh_state(cfg, seed)
    t0 = time.perf_counter()
    compiled = tr.step_fn.lower(params, opt, batch).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()

    t0 = time.perf_counter()
    params, opt, m = compiled(params, opt, batch)
    first = float(m["loss"])
    first_s = time.perf_counter() - t0
    fp8_fwd = 1.0 - float(m["fwd_frac_bf16"])
    fp8_bwd = 1.0 - float(m["bwd_frac_bf16"])
    losses, times = [first], []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        params, opt, m = compiled(params, opt, batch)
        jax.block_until_ready((params, opt, m))
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    del params, opt, m, compiled, tr
    gc.collect()

    step_s = statistics.median(times)
    tokens = BATCH * SEQ
    print(f"train: compile {compile_s:.1f} s, first step {first_s:.2f} s, "
          f"tpu_custom_call {n_kernels} (xla lowering: {ref_kernels})")
    print(f"train: compiled temp {mem.temp_size_in_bytes} B, "
          f"argument {mem.argument_size_in_bytes} B")
    print(f"train: losses {[round(l, 5) for l in losses]}")
    print(f"train: step median {step_s * 1e3:.1f} ms over {TIMED_STEPS}, "
          f"{tokens / step_s:.0f} tokens/s")
    print(f"train: first loss pallas {first:.6f} xla {ref_loss:.6f} "
          f"|diff| {abs(first - ref_loss):.2e} (atol {TRAIN_LOSS_ATOL})")
    print(f"train: FP8 share of quantization events fwd {fp8_fwd:.4f} "
          f"bwd {fp8_bwd:.4f} (xla lowering: fwd {ref_fp8[0]:.4f} "
          f"bwd {ref_fp8[1]:.4f})")
    print(f"train: process peak_bytes_in_use {_mem(dev)}")

    check(all(map(lambda l: l == l and abs(l) < float("inf"), losses)),
          f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(abs(first - ref_loss) <= TRAIN_LOSS_ATOL,
          f"first-step loss pallas {first} vs xla {ref_loss}")
    return {"step_s": step_s, "tokens": tokens, "tpu_custom_call": n_kernels}


def serve_phase(cfg, dev, seed):
    import jax
    import numpy as np

    from repro.core import MoRPolicy
    from repro.models import init_params, make_prefill_fn
    from repro.serve import Engine, Request, ServeConfig

    params = init_params(cfg, jax.random.PRNGKey(seed + 2))
    t0 = time.perf_counter()
    eng = Engine(
        cfg, smoke_policy(), params,
        ServeConfig(slots=SLOTS, max_seq=MAX_SEQ, prefill_chunk=PROMPT),
        quantize=MoRPolicy(recipe="sub3"),
    )
    del params
    gc.collect()
    quant_s = time.perf_counter() - t0
    qs = list(eng.qstats.values())
    fp8 = sum(s["frac_e4m3"] + s["frac_e5m2"] for s in qs) / len(qs)

    rng = np.random.default_rng(seed)

    def run(first_rid):
        reqs = [
            Request(first_rid + i,
                    rng.integers(0, cfg.vocab, PROMPT).astype(np.int32),
                    max_tokens=NEW_TOKENS)
            for i in range(SLOTS)
        ]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        dt = time.perf_counter() - t0
        for r in reqs:
            check(r.done and r.error is None and len(r.out) == NEW_TOKENS,
                  f"request {r.rid}: done={r.done} out={len(r.out)} "
                  f"error={r.error}")
        return reqs, dt

    _, warm_s = run(0)  # compiles the chunk and decode step shapes
    reqs, dt = run(SLOTS)
    check(not eng.quarantined and not eng.rejected and not eng.unfinished,
          f"quarantined {len(eng.quarantined)} rejected "
          f"{len(eng.rejected)} unfinished {len(eng.unfinished)}")

    prompt = {"tokens": jax.numpy.asarray(reqs[0].prompt)[None]}
    pal = jax.jit(make_prefill_fn(cfg, smoke_policy()))(
        eng.params, eng.tokens, prompt)[0]
    ref = jax.jit(make_prefill_fn(cfg, smoke_policy("xla")))(
        eng.params, eng.tokens, prompt)[0]
    pal = np.asarray(pal, np.float32).reshape(-1)[: cfg.vocab]
    ref = np.asarray(ref, np.float32).reshape(-1)[: cfg.vocab]
    rel = float(np.max(np.abs(pal - ref)) / np.max(np.abs(ref)))

    print(f"serve: quantize {quant_s:.1f} s, FP8 share of weight blocks "
          f"{fp8:.4f}, first run (compiles) {warm_s:.1f} s")
    print(f"serve: {SLOTS} requests x {NEW_TOKENS} tokens in {dt:.2f} s, "
          f"{SLOTS * NEW_TOKENS / dt:.1f} generated tokens/s, "
          f"{eng.decode_steps} decode steps in all")
    print(f"serve: prefill logits max|diff|/max|xla| {rel:.2e} "
          f"(rtol {SERVE_LOGITS_RTOL}), finite "
          f"{bool(np.isfinite(pal).all())}")
    print(f"serve: process peak_bytes_in_use {_mem(dev)}")
    check(np.isfinite(pal).all() and np.isfinite(ref).all(),
          "non-finite prefill logits")
    check(rel <= SERVE_LOGITS_RTOL, f"prefill logits differ: {rel}")


def four_chip_phase(cfg, seed):
    """The train step on a (data=2, model=2) mesh of four chips with
    ``rules.param_specs`` placement (and ``rules.opt_state_specs``, the
    ZeRO-1 layout, for the optimizer state), against the same step on
    one of those chips on the same batch. Quantization events use the XLA
    lowering in both: GSPMD cannot partition a Mosaic kernel (a jitted
    step over sharded operands would need each kernel inside a
    shard_map), so the comparison isolates the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_local_mesh
    from repro.models import init_params
    from repro.models.common import use_mesh
    from repro.optim import init_opt_state
    from repro.sharding import rules

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    pol = smoke_policy("xla")
    mesh = make_local_mesh(data=2, model=2, devices=devs)
    key = jax.random.PRNGKey(seed)
    with use_mesh(mesh):
        shapes = jax.eval_shape(lambda k: init_params(cfg, k), key)
        shard = rules.named_shardings(mesh, rules.param_specs(cfg, shapes))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=shard)(key)
        # ZeRO-1 optimizer state: the param spec plus 'data' sharding.
        ospecs = rules.opt_state_specs(
            cfg, jax.eval_shape(init_opt_state, shapes), mesh=mesh
        )
        opt = jax.jit(
            init_opt_state,
            out_shardings=rules.named_shardings(mesh, ospecs),
        )(params)
        batch = jax.device_put(
            smoke_batch(cfg, seed + 1), NamedSharding(mesh, P("data", None))
        )
        placed = _shard_bytes((params, opt, batch), devs)
        in_use = [_mem(d, "bytes_in_use") for d in devs]
        tr = _trainer(cfg, pol, seed)
        t0 = time.perf_counter()
        out = tr.step_fn(params, opt, batch)
        mesh_loss = float(out[2]["loss"])
        mesh_s = time.perf_counter() - t0
    peaks = [_mem(d) for d in devs]
    del out, params, opt, batch, tr
    gc.collect()

    tr = _trainer(cfg, pol, seed)
    params, opt = _fresh_state(cfg, seed)
    batch = jax.device_put(smoke_batch(cfg, seed + 1), devs[0])
    one_loss = float(tr.step_fn(params, opt, batch)[2]["loss"])

    print(f"four-chip: mesh {dict(mesh.shape)} first step (compiles) "
          f"{mesh_s:.1f} s")
    print(f"four-chip: state+batch shard bytes per device {placed}")
    print(f"four-chip: bytes_in_use per device after placement {in_use}")
    print(f"four-chip: peak_bytes_in_use per device after the step {peaks}")
    print(f"four-chip: loss mesh {mesh_loss:.6f} one chip {one_loss:.6f} "
          f"|diff| {abs(mesh_loss - one_loss):.2e} (atol {MESH_LOSS_ATOL})")
    check(abs(mesh_loss - one_loss) <= MESH_LOSS_ATOL,
          f"mesh loss {mesh_loss} vs one-chip loss {one_loss}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) train step and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.mesh import hw_peaks

        dev = device_gate()
        import jax

        print(f"compile cache: {enable_compile_cache()}")
        cfg = smoke_config()
        print(f"config: {cfg.name} d_model {cfg.d_model} heads "
              f"{cfg.n_heads}x{cfg.head_dim} d_ff {cfg.d_ff} layers "
              f"{cfg.n_layers} vocab {cfg.vocab} params "
              f"{cfg.param_count()}")
        if args.four_chips:
            four_chip_phase(cfg, args.seed)
        else:
            res = train_phase(cfg, dev, args.seed)
            check(res["tpu_custom_call"] > 0,
                  "no tpu_custom_call in the compiled train step")
            mfu = (6 * cfg.param_count() * res["tokens"] / res["step_s"]
                   / hw_peaks(dev.device_kind).peak_flops_bf16)
            print(f"train: model FLOP/s utilization (6*N*tokens/s over "
                  f"the bf16 peak) {mfu:.4f}")
            serve_phase(cfg, dev, args.seed)
    except Exception as e:  # any failed phase: report and exit non-zero
        print(f"FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
