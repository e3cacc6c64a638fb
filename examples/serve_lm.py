"""Serve a small LM with batched requests, MoR-quantized (real FP8)
weights, and continuous batching.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma-2b --requests 6
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core import MoRPolicy, TENSOR_MOR
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import Engine, Request, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-tokens", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(reduced(get_config(args.arch)), vocab=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    bytes_bf16 = sum(
        l.size * 2 for l in jax.tree.leaves(params) if hasattr(l, "size")
    )

    # Ahead-of-time per-block MoR decision -> sub-tensor QTensor storage;
    # every matmul against a quantized weight runs through the
    # mixed-representation block GEMM kernel.
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=args.slots, max_seq=128),
                 quantize=MoRPolicy(recipe="sub3"), quantize_min_size=1024)
    qstats = eng.qstats or {}
    n_q = sum(s["quantized"] for s in qstats.values())
    print(f"weights quantized to mixed fp8 storage: {int(n_q)}/{len(qstats)} "
          f"({100 * n_q / max(len(qstats), 1):.1f}%)")
    bytes_mixed = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(eng.params)
        if hasattr(l, "size")
    )
    print(f"param bytes bf16={bytes_bf16/1e6:.2f}MB -> "
          f"mixed={bytes_mixed/1e6:.2f}MB (actual stored bytes)")
    rng = np.random.default_rng(0)
    reqs = [
        Request(i, rng.integers(0, cfg.vocab, 8).astype(np.int32),
                max_tokens=args.max_tokens)
        for i in range(args.requests)
    ]
    t0 = time.time()
    for r in reqs:
        eng.submit(r)
    steps = eng.run_to_completion()
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in reqs)
    print(f"{args.requests} requests, {total_tokens} tokens in {steps} "
          f"decode steps, {dt:.1f}s "
          f"({total_tokens/dt:.1f} tok/s on CPU)")
    for r in reqs[:3]:
        print(f"req {r.rid}: prompt={r.prompt[:4].tolist()}... "
              f"-> {r.out}")


if __name__ == "__main__":
    main()
