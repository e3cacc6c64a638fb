"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
against 512 placeholder host devices, and extract roofline inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single --out out.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The two env lines below MUST run before any other import (jax locks the
device count at first init).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

# ruff: noqa: E402
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    SHAPES,
    cell_is_runnable,
    get_config,
    input_specs,
    list_archs,
)
from repro.core import BF16_BASELINE, TENSOR_MOR, paper_default
from repro.launch.mesh import hw_peaks, make_production_mesh
from repro.models import (
    cache_specs,
    init_params,
    make_decode_fn,
    make_prefill_fn,
    make_tokens,
)
from repro.models.common import use_mesh
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.sharding import rules
from repro.train.train_step import TrainConfig, make_train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes_from_hlo(hlo: str) -> Dict[str, Any]:
    """Sum operand bytes of collective ops in the partitioned HLO.

    Shapes in the partitioned module are per-device, so the totals here
    are per-device traffic per step.
    """
    per_op = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo.splitlines():
        ls = line.strip()
        m = re.search(r"=\s+\S+\s+(all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start)?\(", ls)
        if not m:
            continue
        op = m.group(1)
        # Operand shapes: everything inside the call parens.
        args = ls[m.end():]
        operands = _SHAPE_RE.findall(args.split("),")[0] + ")")
        total = 0
        for dt, dims in operands:
            total += _shape_bytes(f"{dt}[{dims}]")
        per_op[op] += total
        counts[op] += 1
    return {
        "bytes_per_op": per_op,
        "counts": counts,
        "total_bytes": sum(per_op.values()),
    }


def _attach(struct_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)
        ),
        struct_tree,
        spec_tree,
    )


def _replicated(struct_tree, mesh):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())
        ),
        struct_tree,
    )


def _policy(name: str):
    if name == "bf16":
        return BF16_BASELINE
    if name == "mor":
        return TENSOR_MOR
    if name == "mor_channel":
        return paper_default(partition="channel")
    if name == "mor_tensor":
        return paper_default(partition="tensor")
    if name == "sub2":
        return paper_default("sub2")
    if name in ("sub3", "sub4"):
        return paper_default(name)
    raise ValueError(name)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               policy_name: str = "mor", train_cfg: TrainConfig = None,
               kv_fp8: bool = False):
    """Lower + compile one cell. Returns (lowered, compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        raise SkipCell(why)

    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = _policy(policy_name)
    bspec = rules.batch_spec(multi_pod) if shape.global_batch > 1 else P()

    with use_mesh(mesh):
        pshape = jax.eval_shape(
            lambda k: init_params(cfg, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        pspecs = rules.param_specs(cfg, pshape)
        p_structs = _attach(pshape, pspecs, mesh)

        ins = input_specs(cfg, shape)

        if shape.kind == "train":
            if train_cfg is None:
                # Auto microbatching: big models need smaller live
                # activation footprints to fit 16 GB HBM.
                n = cfg.param_count()
                accum = 4 if n > 20e9 else (2 if n > 3e9 else 1)
                train_cfg = TrainConfig(
                    optimizer=AdamWConfig(total_steps=100000),
                    grad_accum=accum,
                )
            tcfg = train_cfg
            step = make_train_step(cfg, policy, tcfg)
            oshape = jax.eval_shape(init_opt_state, pshape)
            ospecs_master = rules.opt_state_spec_from_param(cfg, pshape)
            ospecs = type(oshape)(
                master=ospecs_master, m=ospecs_master, v=ospecs_master,
                step=P(),
            )
            o_structs = _attach(oshape, ospecs, mesh)
            batch_structs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(mesh, bspec)
                ),
                ins,
            )
            lowered = jax.jit(step).lower(
                p_structs, o_structs, batch_structs
            )
        elif shape.kind == "prefill":
            fn = make_prefill_fn(cfg, policy)

            def step(params, batch):
                return fn(params, make_tokens(cfg), batch)

            batch_structs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(mesh, bspec)
                ),
                ins,
            )
            lowered = jax.jit(step).lower(p_structs, batch_structs)
        else:  # decode
            fn = make_decode_fn(cfg, policy)

            def step(params, cache, token, cur_index):
                return fn(params, make_tokens(cfg), cache, token, cur_index)

            cshape = cache_specs(cfg, shape.global_batch, shape.seq_len,
                                 kv_fp8=kv_fp8)
            cspecs = rules.cache_specs_tree(cfg, cshape, multi_pod)
            if shape.global_batch == 1:
                cspecs = jax.tree.map(
                    lambda sp: P(*(
                        None if (e == "data" or e == ("pod", "data")
                                 or e == "batch") else e
                        for e in sp
                    )),
                    cspecs,
                    is_leaf=lambda x: isinstance(x, P),
                )
            c_structs = _attach(cshape, cspecs, mesh)
            tok_struct = jax.ShapeDtypeStruct(
                ins["token"].shape, jnp.int32,
                sharding=NamedSharding(mesh, bspec),
            )
            idx_struct = jax.ShapeDtypeStruct(
                ins["cur_index"].shape, jnp.int32,
                sharding=NamedSharding(mesh, P()),
            )
            # Serving donates the cache: the update happens in place
            # instead of temp-buffering a second full cache.
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                p_structs, c_structs, tok_struct, idx_struct
            )

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "policy": policy_name,
        "kind": shape.kind,
        "compile_seconds": round(compile_s, 1),
    }
    return lowered, compiled, meta


class SkipCell(Exception):
    pass


def analyze(lowered, compiled, meta, cfg, shape) -> Dict[str, Any]:
    from repro.launch.hlo_analysis import analyze_hlo

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    chips = meta["chips"]
    # Trip-count-aware walk (XLA's cost_analysis counts while bodies once;
    # scan-over-layers models need the corrected numbers).
    walked = analyze_hlo(hlo, n_partitions=chips)
    coll = {
        "operand_bytes_per_op": walked.coll_operand_bytes,
        "traffic_bytes_per_op": walked.coll_traffic_bytes,
        "counts": walked.coll_counts,
        "total_operand_bytes": walked.total_coll_operand_bytes,
        "total_bytes": walked.total_coll_traffic_bytes,
    }
    flops_dev = walked.flops
    bytes_dev = walked.bytes
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    # Model (useful) FLOPs: 6*N*D train, 2*N*D prefill, 2*N*B decode.
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * shape.global_batch

    # The cells model a v5e pod; the compile itself ran on host devices.
    hw = hw_peaks("TPU v5 lite")
    compute_s = flops_dev / hw.peak_flops_bf16
    memory_s = bytes_dev / hw.hbm_bw
    collective_s = coll["total_bytes"] / hw.ici_bw
    dominant = max(
        ("compute", compute_s), ("memory", memory_s),
        ("collective", collective_s), key=lambda kv: kv[1],
    )[0]

    out = {
        **meta,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
            "fits_16gb": bool(
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < hw.hbm_bytes
            ),
        },
        "cost": {
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "flops_global": flops_dev * chips,
            "xla_flops_per_device_unrolled": xla_flops,
            "xla_bytes_per_device_unrolled": xla_bytes,
        },
        "collectives": coll,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "model_flops": float(model_flops),
            "useful_flops_ratio": (
                float(model_flops) / (flops_dev * chips)
                if flops_dev else 0.0
            ),
        },
    }
    return out


def run_cell(arch, shape_name, multi_pod, policy_name="mor", out=None,
             kv_fp8=False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    try:
        lowered, compiled, meta = lower_cell(
            arch, shape_name, multi_pod, policy_name, kv_fp8=kv_fp8
        )
        meta["kv_fp8"] = kv_fp8
        result = analyze(lowered, compiled, meta, cfg, shape)
        result["status"] = "ok"
    except SkipCell as e:
        result = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "policy": policy_name, "status": "skip", "reason": str(e),
        }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="mor")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kv-fp8", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = (
        [False, True] if args.mesh == "both"
        else [args.mesh == "multi"]
    )
    cells = []
    if args.all:
        for a in list_archs():
            if a == "nemotron3-8b":
                continue  # paper model: quality benches, not an assigned cell
            for s in SHAPES:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch} x {shape_name} x {'multi' if mp else 'single'}"
            try:
                res = run_cell(arch, shape_name, mp, args.policy, args.out,
                               kv_fp8=args.kv_fp8)
                if res["status"] == "ok":
                    r = res["roofline"]
                    print(
                        f"[ok]   {tag}: dominant={r['dominant']} "
                        f"compute={r['compute_s']:.3f}s "
                        f"memory={r['memory_s']:.3f}s "
                        f"collective={r['collective_s']:.3f}s "
                        f"fits={res['memory']['fits_16gb']}"
                    )
                    print(json.dumps(res["memory"]))
                    print(json.dumps(res["cost"]))
                else:
                    print(f"[skip] {tag}: {res['reason']}")
            except Exception:
                failures += 1
                print(f"[FAIL] {tag}")
                traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
