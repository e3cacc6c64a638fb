"""The comparison that decides ``correct``, and the numbers it prints.

Each compared number has a limit of its own in
``limits/<workload>.json``, set from the readings of sound runs and of
the control (PERF.md gives both for each limit).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

# A leaf whose reference gradient is below this share of the median
# leaf's moves under Adam by round-off alone (a key's bias under
# softmax, say); such leaves are left out of the norm gaps.
NEGLIGIBLE_GRAD = 1e-3


def _norm_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep: Dict[str, np.ndarray]) -> Tuple[float, str]:
    """Worst leaf's |norm(program) - norm(reference)|, over the larger
    of the reference's norm of that leaf and of the median leaf."""
    kept = np.concatenate([ref[p][keep[p]] for p in ref])
    med = float(np.median(kept)) if kept.size else 0.0
    worst, where = 0.0, ""
    for path in ref:
        for i in np.flatnonzero(keep[path]):
            r, p = float(ref[path][i]), float(prog[path][i])
            gap = abs(p - r) / max(r, med, 1e-30)
            if not math.isfinite(p):
                gap = math.inf
            if gap > worst or not where:
                worst, where = gap, f"{path}[{i}]"
    return worst, where


def train_numbers(prog, ref) -> Dict[str, float]:
    """The numbers a training cell compares: the largest loss gap over
    the checked steps, and the worst leaf's gap of the first gradient's
    norm and of the change's norm."""
    g_ref = ref["grad_norms"]
    med = float(np.median(np.concatenate(list(g_ref.values()))))
    keep = {p: g_ref[p] >= NEGLIGIBLE_GRAD * med for p in g_ref}
    loss_gap = max(
        (abs(a - b) if math.isfinite(a) else math.inf)
        for a, b in zip(prog["losses"], ref["losses"])
    )
    grad_gap, grad_at = _norm_gap(prog["grad_norms"], g_ref, keep)
    change_gap, change_at = _norm_gap(prog["change_norms"],
                                      ref["change_norms"], keep)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad_gap,
        "change_norm_gap": change_gap,
        "_grad_at": grad_at,
        "_change_at": change_at,
        "_excluded": int(sum((~k).sum() for k in keep.values())),
    }


def serve_numbers(served: List[np.ndarray], ref_logits: List[np.ndarray]
                  ) -> Dict[str, float]:
    """Widest gap by which a served (greedy) token's reference logit lies
    below the reference's best at that position."""
    worst, n = 0.0, 0
    for toks, lg in zip(served, ref_logits):
        best = lg.max(axis=-1)
        got = lg[np.arange(len(toks)), toks]
        worst = max(worst, float(np.max(best - got)))
        n += len(toks)
    return {"logit_gap": worst, "_tokens": n}


def judge(numbers: Dict[str, float], limits: Dict[str, object]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {value, limit}}): correct when no compared number
    exceeds its limit (a NaN exceeds every limit). Every compared number
    needs a limit in the cell's limits file; one without is an error."""
    table = {}
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        if name not in limits["limits"]:
            raise KeyError(f"the limits file has no limit for {name!r}")
        table[name] = {"value": value, "limit": limits["limits"][name]}
    ok = all(row["value"] <= row["limit"] for row in table.values())
    return ok, table
