"""Runs one benchmark cell and prints its result as the last line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The run checks the device (a TPU, as many chips as the cell asks for,
kernels on Pallas), makes weights and inputs from the seed, warms up the
cell's shapes (set-up), measures for ``--seconds``, then compares what
the timed path produced with the plain reference. With ``--trace 1`` a
part of the window is traced and the cell's per-layer metrics are read
from the trace; otherwise its end-to-end metrics are printed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


class GateError(RuntimeError):
    """The machine cannot run the cell: no result is printed."""


def use_program():
    """Puts the checkout's program on the path; a checkout without it
    cannot run a cell."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise GateError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def device_gate(chips: int):
    """A TPU with at least ``chips`` chips and the kernels on Pallas;
    anything else is refused, never run on the CPU instead."""
    if os.environ.get("REPRO_KERNEL_INTERPRET"):
        raise GateError("REPRO_KERNEL_INTERPRET is set")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise GateError(f"no TPU: the first device is {devs[0].platform}")
    if len(devs) < chips:
        raise GateError(f"the cell needs {chips} chips, found {len(devs)}")
    from repro.kernels import ops

    if ops.resolve_backend() != "pallas":
        raise GateError("the kernel backend does not resolve to pallas")
    return devs[:chips]


class ProgramCounter:
    """Counts the programs JAX lowers (a compile or a cache load each),
    so that a run can show that nothing was built inside its window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax._src import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def emit(result, table) -> None:
    """The numbers compared, beside their limits, as the last lines on
    standard error; then the result's line, with them under ``check``."""
    for name, row in table.items():
        print(f"check {name}: {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    result["check"] = {k: {"value": _finite(v["value"]),
                           "limit": v["limit"]} for k, v in table.items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run_train(cell, args, devs, counters, programs):
    from . import check, train
    from .trace import Tracer

    sess = train.TrainSession(cell, args.seed)
    prog = sess.checked_steps()
    counters["setup_s"] = time.perf_counter() - T_START
    tracer = (Tracer(cell, steps=cell.traffic["trace_steps"])
              if args.trace else None)
    built = programs.n
    steps, wall, losses = train.window(sess, args.seconds, tracer)
    counters["window_programs"] = programs.n - built
    tokens = steps * cell.traffic["global_batch"] * cell.traffic["seq_len"]
    counters.update(train_tokens_per_s=tokens / wall, steps=steps,
                    window_s=wall, step_flops=train.step_flops(cell))
    counters["memory_peak_bytes"] = memory_peak(devs)
    failed = int(sum(1 for l in losses if not math.isfinite(l)))
    sess.free()
    del sess
    gc.collect()
    ref = train.reference_readings(cell, args.seed)
    return steps, failed, check.train_numbers(prog, ref), tracer


def run_serve(cell, args, devs, counters, programs):
    from . import check, reference, serve
    from .trace import Tracer

    cfg, eng = serve.make_engine(cell, args.seed)
    serve.warm_up(eng, cfg, cell.traffic)
    plans = serve.schedule(cell.traffic, args.seed, args.seconds, cfg.vocab)
    counters["setup_s"] = time.perf_counter() - T_START
    tracer = (Tracer(cell, seconds=cell.traffic["trace_seconds"])
              if args.trace else None)
    built = programs.n
    win = serve.Window(eng, plans, cell.traffic).run(
        args.seconds, cell.traffic["drain_s"], tracer)
    counters["window_programs"] = programs.n - built
    counters.update(win.end_to_end())
    counters.update(win.counters())
    counters["memory_peak_bytes"] = memory_peak(devs)
    failed = len(win.failed())
    del eng
    gc.collect()
    chk = cell.traffic["check"]
    sample = serve.check_sample(win, args.seed, chk["tokens"],
                                chk["max_requests"])
    if not sample:
        return len(plans), failed, {"logit_gap": math.inf}, tracer
    seqs, picks, served = serve.check_inputs(sample)
    ref = reference.serve_logits(cell.family, cfg, args.seed, seqs, picks,
                                 pad_to=cell.traffic["engine"]["max_seq"])
    return len(plans), failed, check.serve_numbers(served, ref), tracer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from . import spec

    try:
        use_program()
        cell = spec.load_cell(args.workload)
        if cell.limits is None:
            raise spec.SpecError(f"no limits file for {cell.name}")
        devs = device_gate(cell.chips)
    except (GateError, spec.SpecError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result, table = run_cell(cell, args, devs)
    emit(result, table)
    return 0


def run_cell(cell, args, devs):
    """Set-up, window, check; returns (result line, compared numbers)."""
    from . import check, metrics

    counters = {}
    runner = run_train if cell.kind == "train" else run_serve
    attempted, failed, numbers, tracer = runner(cell, args, devs, counters,
                                                ProgramCounter())
    print(f"programs built inside the window: "
          f"{counters['window_programs']}", file=sys.stderr)
    if "late_p90_ms" in counters:
        print(f"generator late p90: {counters['late_p90_ms']:.3f} ms",
              file=sys.stderr)
    print("check detail: " + json.dumps(
        {k: v for k, v in numbers.items() if k.startswith("_")}),
        file=sys.stderr)
    ok, table = check.judge(numbers, cell.limits)
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": counters["memory_peak_bytes"]}
    result = {"correct": bool(ok and failed == 0),
              "attempted": int(attempted), "failed": int(failed)}
    if args.trace:
        red = tracer.reduce(len(devs))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metrics.read_all(cell, red, counters,
                                             d0.device_kind, len(devs))
        result["breakdown"] = red.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": counters[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = device
    return result, table


if __name__ == "__main__":
    sys.exit(main())
