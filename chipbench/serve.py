"""Serving cells: ``Engine.step`` under an open-loop schedule.

One general generator reads the traffic file: arrivals, prompt and
output lengths and the share of greedy requests. The schedule (each
request's due time, prompt and output length, greedy or sampled) comes
from the file's own ``workload_seed``, so every ``--seed`` offers the
same work; ``--seed`` draws the prompt tokens and each request's
sampling seed. With a few dozen requests in a window, an order drawn
from ``--seed`` moved the tails by 30-100 % from seed to seed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import weights as W


@dataclasses.dataclass
class Planned:
    rid: int
    due: float
    prompt: np.ndarray
    max_tokens: int
    greedy: bool
    seed: int


def _lengths(rng, spec: Dict[str, float], n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Planned]:
    """The requests due in a window of ``seconds``, by due time. Sizes,
    greedy flags and arrival times come from the file's
    ``workload_seed`` alone, so every seed offers the same work at the
    same moments; ``--seed`` draws the prompt tokens and each request's
    sampling seed."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    base = np.random.default_rng(traffic["workload_seed"])
    prompts = _lengths(base, traffic["prompt"], n)
    outs = _lengths(base, traffic["output"], n)
    greedy = base.permutation(n) < int(round(traffic["greedy_share"] * n))
    gaps = base.exponential(1.0, n + 1)
    due = np.cumsum(gaps)[:n] * seconds / gaps.sum()
    rng = np.random.default_rng(seed)
    return [
        Planned(rid=i, due=float(due[i]),
                prompt=rng.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32),
                max_tokens=int(outs[i]), greedy=bool(greedy[i]),
                seed=int(rng.integers(0, 2**31)))
        for i in range(n)
    ]


def make_engine(cell, seed: int):
    """The engine of the cell, on weights made from the seed. The dense
    weights are dropped once the engine holds its quantized copy."""
    from repro.core import MoRPolicy, paper_default
    from repro.serve import Engine, ServeConfig

    cfg = cell.family.arch_config(cell.config)
    eng_spec = cell.traffic["engine"]
    params = W.make_params(cell.family, cfg, seed)
    W.check_tree(cfg, params)
    quant = eng_spec.get("quantize")
    eng = Engine(
        cfg, paper_default(partition="block"), params,
        ServeConfig(slots=eng_spec["slots"], max_seq=eng_spec["max_seq"],
                    prefill_chunk=eng_spec["prefill_chunk"]),
        quantize=MoRPolicy(recipe=quant) if quant else None,
    )
    del params
    gc.collect()
    return cfg, eng


def warm_up(eng, cfg, traffic) -> None:
    """Compiles the two programs the window drives: one prompt chunk
    (B=1) and one batched decode step. Sampling runs on the host."""
    from repro.serve import Request

    chunk = traffic["engine"]["prefill_chunk"]
    req = Request(-1, np.zeros(chunk + 1, np.int32), max_tokens=3)
    eng.submit(req)
    eng.run_to_completion()
    if not req.done or len(req.out) != 3:
        raise RuntimeError(f"warm-up request failed: {req.error}")


@dataclasses.dataclass
class Track:
    plan: Planned
    req: Any = None
    admitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)


class Window:
    """Drives the engine through the schedule and times every token as
    the host sees it after each ``Engine.step``."""

    def __init__(self, eng, plans: List[Planned], traffic):
        self.eng, self.traffic = eng, traffic
        self.tracks = [Track(p) for p in plans]
        self.decode_tokens = 0
        self.decode_steps = 0
        self.late_s: List[float] = []
        self.traced_calls: List = []

    def _observe(self, now: float, active: List[Track]):
        """Tokens and admissions after one engine step; returns the
        tracks still in flight and the cache lengths the step's decode
        read (one per decoding slot)."""
        queued = {id(r) for r in self.eng.queue}
        still, lens = [], []
        for t in active:
            if t.admitted is None and id(t.req) not in queued:
                t.admitted = now
            n = len(t.req.out)
            new = n - len(t.times)
            decoded = new - (1 if new > 0 and not t.times else 0)
            if decoded > 0:
                lens.append(len(t.plan.prompt) + n - 1)
            self.decode_tokens += max(decoded, 0)
            t.times.extend([now] * max(new, 0))
            if not t.req.done:
                still.append(t)
        return still, lens

    def run(self, seconds: float, drain_s: float, tracer=None):
        from repro.serve import Request

        eng = self.eng
        steps0 = eng.decode_steps
        nxt, active = 0, []
        t0 = time.perf_counter()
        end = seconds + drain_s
        # Trace from the middle of the window: an open-loop queue starts
        # empty, and its first seconds show the start, not the load.
        trace_from = seconds / 2
        while True:
            now = time.perf_counter() - t0
            while nxt < len(self.tracks) and self.tracks[nxt].plan.due <= now:
                t = self.tracks[nxt]
                p = t.plan
                self.late_s.append(now - p.due)
                t.req = Request(
                    p.rid, p.prompt, max_tokens=p.max_tokens,
                    temperature=0.0 if p.greedy else
                    self.traffic["temperature"],
                    top_k=0 if p.greedy else self.traffic["top_k"],
                    seed=p.seed,
                )
                eng.submit(t.req)
                active.append(t)
                nxt += 1
            if nxt == len(self.tracks) and not active:
                break
            if now >= end:
                break
            if tracer is not None and not tracer.done and not tracer.on \
                    and now >= trace_from:
                tracer.start()
            busy = eng.queue or any(r is not None for r in eng.slot_req)
            if not busy and nxt == len(self.tracks):
                break
            traced = tracer is not None and tracer.on
            chunks0, dec0 = eng.prefill_chunks, eng.decode_steps
            with (tracer.span("engine_step" if busy else "wait_arrival")
                  if traced else contextlib.nullcontext()):
                if busy:
                    eng.step()
                else:
                    time.sleep(max(0.0, min(
                        self.tracks[nxt].plan.due - now, 0.002)))
            if busy:
                active, lens = self._observe(time.perf_counter() - t0,
                                             active)
                if traced:
                    # The engine launches its step program once per
                    # prompt chunk, then once for the batched decode.
                    self.traced_calls += [("prefill", None)] * (
                        eng.prefill_chunks - chunks0)
                    if eng.decode_steps > dec0:
                        self.traced_calls.append(("decode", lens))
            if tracer is not None and tracer.on and \
                    time.perf_counter() - t0 >= trace_from + tracer.seconds:
                tracer.stop(eng.pool.tree)
        self.wall = time.perf_counter() - t0
        if tracer is not None and tracer.on:
            tracer.stop(eng.pool.tree)
        self.eng = None  # the window's results no longer need the engine
        self.decode_steps = eng.decode_steps - steps0
        self.seconds = seconds
        return self

    # -------------------------------------------------------- results --
    def failed(self) -> List[Track]:
        return [t for t in self.tracks
                if t.req is None or not t.req.done or t.req.error
                or len(t.req.out) != t.plan.max_tokens]

    def end_to_end(self) -> Dict[str, float]:
        bad = {id(t) for t in self.failed()}
        ttft, itl = [], []
        tokens = 0
        for t in self.tracks:
            if id(t) in bad or not t.times:
                ttft.append(self.wall - t.plan.due)
                continue
            ttft.append(t.times[0] - t.plan.due)
            itl.extend(np.diff(t.times).tolist())
        for t in self.tracks:
            tokens += sum(1 for x in t.times if x <= self.seconds)
        return {
            "serve_tokens_per_s": tokens / self.seconds,
            "ttft_p90_ms": 1e3 * pct(ttft, 90),
            "itl_p95_ms": 1e3 * pct(itl, 95),
        }

    def counters(self) -> Dict[str, float]:
        waits = [(t.admitted if t.admitted is not None else self.wall)
                 - t.plan.due for t in self.tracks]
        return {
            "admit_wait_p90_ms": 1e3 * pct(waits, 90),
            "decode_occupancy": (self.decode_tokens / self.decode_steps
                                 if self.decode_steps else None),
            "late_p90_ms": 1e3 * pct(self.late_s, 90),
            "traced_calls": self.traced_calls,
        }


def pct(values, q: float) -> float:
    """The q-th percentile, nearest-rank: a value that was observed."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    k = max(0, int(np.ceil(q / 100.0 * v.size)) - 1)
    return float(v[k])


def check_sample(win: Window, seed: int, target_tokens: int,
                 max_requests: int) -> List[Track]:
    """Finished greedy requests to compare with the reference: the
    longest, then others drawn from the seed, until ``target_tokens``
    served tokens or ``max_requests`` requests."""
    bad = {id(t) for t in win.failed()}
    done = [t for t in win.tracks if t.plan.greedy and id(t) not in bad]
    if not done:
        return []
    done.sort(key=lambda t: (len(t.req.out), len(t.plan.prompt)))
    pick = [done.pop()]
    rng = np.random.default_rng(seed ^ 0x5EED)
    for j in rng.permutation(len(done)):
        if sum(len(t.req.out) for t in pick) >= target_tokens or \
                len(pick) >= max_requests:
            break
        pick.append(done[j])
    return pick


def check_inputs(sample: List[Track]):
    """For each sampled request: the sequence the reference reads (the
    prompt and every served token but the last), the positions whose
    next-token logits it wants, and the served tokens."""
    seqs = [np.concatenate([t.plan.prompt,
                            np.asarray(t.req.out[:-1], np.int32)])
            for t in sample]
    picks = [len(t.plan.prompt) - 1 + np.arange(len(t.req.out))
             for t in sample]
    served = [np.asarray(t.req.out) for t in sample]
    return seqs, picks, served
