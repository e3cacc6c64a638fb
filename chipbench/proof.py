"""Readings that set a cell's limits (not part of a benchmark run).

    python3 -m chipbench.proof --workload <name> --seeds 1,2,3 [--seconds s]

For each seed it prints one JSON line with the numbers the cell
compares, read three ways against the float32 reference: from the
program as a run drives it, from the control (the reference at int4, in
the program's place) and, for training, from the reference with a
planted fault (half of the batch left out, the mean over the rest). A
state left unchanged reads 1 on ``change_norm_gap`` by construction and
needs no run. ``limits/<workload>.json`` is set between the program's
largest reading and the smallest reading of the control or fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time


def train_seed(cell, seed: int) -> dict:
    from . import check, train

    sess = train.TrainSession(cell, seed)
    prog = sess.checked_steps()
    sess.free()
    del sess
    gc.collect()
    ref = train.reference_readings(cell, seed)
    out = {"program": check.train_numbers(prog, ref)}
    out["control_int4"] = check.train_numbers(
        train.reference_readings(cell, seed, bits=4), ref)
    half = cell.traffic["global_batch"] // 2
    out["fault_half_batch"] = check.train_numbers(
        train.reference_readings(cell, seed, rows=half), ref)
    return out


def serve_seed(cell, seed: int, seconds: float) -> dict:
    from . import check, reference, serve

    cfg, eng = serve.make_engine(cell, seed)
    serve.warm_up(eng, cfg, cell.traffic)
    plans = serve.schedule(cell.traffic, seed, seconds, cfg.vocab)
    win = serve.Window(eng, plans, cell.traffic).run(
        seconds, cell.traffic["drain_s"])
    e2e = win.end_to_end()
    del eng
    gc.collect()
    chk = cell.traffic["check"]
    sample = serve.check_sample(win, seed, chk["tokens"],
                                chk["max_requests"])
    seqs, picks, served = serve.check_inputs(sample)
    pad = cell.traffic["engine"]["max_seq"]
    fam = cell.family
    ref = reference.serve_logits(fam, cfg, seed, seqs, picks, pad_to=pad)
    ctl = reference.serve_logits(fam, cfg, seed, seqs, picks, bits=4,
                                 pad_to=pad)
    return {
        "program": check.serve_numbers(served, ref),
        "control_int4": check.serve_numbers(
            [c.argmax(axis=-1) for c in ctl], ref),
        "failed": len(win.failed()), "requests": len(plans), **e2e,
    }


def main(argv=None) -> int:
    from .run import GateError, device_gate, use_program

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    try:
        use_program()
        from . import spec

        cell = spec.load_cell(args.workload)
        device_gate(cell.chips)
    except GateError as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = (train_seed(cell, seed) if cell.kind == "train"
               else serve_seed(cell, seed, args.seconds))
        out.update(seed=seed, took_s=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
