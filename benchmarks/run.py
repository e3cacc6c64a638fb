"""Benchmark orchestrator: one section per paper table/figure + kernel
microbench. Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150,
                    help="training steps per quality config")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. table2,kernels")
    args = ap.parse_args()

    from . import (
        bench_fig10,
        bench_fig11,
        bench_kernels,
        bench_table2,
        bench_table3,
        bench_table4,
    )

    sections = {
        "table2": lambda: bench_table2.main(steps=args.steps),
        "table3": lambda: bench_table3.main(steps=args.steps),
        "table4": lambda: bench_table4.main(steps=args.steps),
        "fig10": lambda: bench_fig10.main(steps=max(args.steps // 2, 30)),
        "fig11": lambda: bench_fig11.main(steps=max(args.steps // 3, 20)),
        "kernels": bench_kernels.main,
    }
    chosen = (
        {k: sections[k] for k in args.only.split(",")}
        if args.only
        else sections
    )

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in chosen.items():
        try:
            rows, _ = fn()
            for row in rows:
                print(row)
            sys.stdout.flush()
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
