"""Sequential dry-run sweep over every (arch x shape x mesh) cell.

One process, cells ordered cheap-to-expensive so results bank early;
per-cell JSON lands in experiments/dryrun/ and a progress line in the log.
jax caches are cleared between cells to bound memory.

  PYTHONPATH=src python -m repro.launch.sweep [--mesh single|multi|both]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

# ruff: noqa: E402
import argparse
import gc
import json
import time
import traceback

import jax

from repro.configs.base import SHAPES, cell_is_runnable, get_config
from repro.launch import dryrun

ARCHS_BY_COST = [
    "whisper-tiny",
    "granite-moe-1b-a400m",
    "xlstm-350m",
    "hymba-1.5b",
    "gemma-2b",
    "minitron-4b",
    "paligemma-3b",
    "llama3-8b",
    "moonlight-16b-a3b",
    "deepseek-coder-33b",
]
SHAPES_BY_COST = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--policy", default="mor")
    ap.add_argument("--only-arch", default=None)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]

    cells = []
    for mp in meshes:
        for shape in SHAPES_BY_COST:
            for arch in ARCHS_BY_COST:
                if args.only_arch and arch != args.only_arch:
                    continue
                cells.append((arch, shape, mp))

    done = fails = skips = 0
    for arch, shape_name, mp in cells:
        mesh_tag = "multi" if mp else "single"
        out = os.path.join(
            args.out, f"{arch}__{shape_name}__{mesh_tag}.json"
        )
        if os.path.exists(out):
            done += 1
            continue
        cfg = get_config(arch)
        ok, why = cell_is_runnable(cfg, SHAPES[shape_name])
        t0 = time.time()
        try:
            res = dryrun.run_cell(arch, shape_name, mp, args.policy, out)
            dt = time.time() - t0
            if res["status"] == "ok":
                done += 1
                r = res["roofline"]
                print(
                    f"[{done+fails+skips:3d}] ok   {arch} {shape_name} "
                    f"{mesh_tag} ({dt:.0f}s) dom={r['dominant']} "
                    f"c={r['compute_s']:.2f} m={r['memory_s']:.2f} "
                    f"x={r['collective_s']:.2f} "
                    f"fits={res['memory']['fits_16gb']}",
                    flush=True,
                )
            else:
                skips += 1
                print(
                    f"[{done+fails+skips:3d}] skip {arch} {shape_name} "
                    f"{mesh_tag}: {res['reason']}",
                    flush=True,
                )
        except Exception as e:
            fails += 1
            with open(out + ".fail", "w") as f:
                f.write(traceback.format_exc())
            print(
                f"[{done+fails+skips:3d}] FAIL {arch} {shape_name} "
                f"{mesh_tag} ({time.time()-t0:.0f}s): {e}",
                flush=True,
            )
        jax.clear_caches()
        gc.collect()
    print(f"sweep complete: ok={done} skip={skips} fail={fails}",
          flush=True)


if __name__ == "__main__":
    main()
