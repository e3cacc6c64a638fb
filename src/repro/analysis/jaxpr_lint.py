"""Precision-flow checker: payload-lane taint analysis over jaxprs.

A :class:`~repro.kernels.ref.MixedOperand` carries six lanes whose
*bytes are not numbers*: ``payload_q`` (raw fp8 bit patterns in uint8),
``payload_nib`` (two E2M1 codes per byte), ``micro_scales`` (E4M3 bit
patterns), plus the ``tags``/``scales``/``payload_bf16`` metadata and
value lanes. Any XLA op that treats those buffers as arithmetic values
outside a sanctioned decode site is silently wrong math -- the class of
bug this checker makes statically impossible.

The walk: flatten the entry point's arguments with key paths, seed
taint on every leaf whose path names a payload lane, then interpret the
closed jaxpr abstractly --

* **structural** primitives (reshape/slice/gather/scatter/concat/...)
  move bytes without reading them: taint propagates through.
* **kernel** calls (``pallas_call`` -- the fused select/pack, the mixed
  GEMM, flash) are the sanctioned consumers: taint stops there (and,
  optionally, their uint8 *outputs* are seeded, which is how the
  producer side of a quantize_pack -> mixed_gemm chain is covered
  inside a single jaxpr).
* **higher-order** primitives (pjit/scan/while/cond/custom_vjp/remat)
  recurse with the taint mapped through their sub-jaxpr signatures
  (loop carries run to a fixpoint).
* any other **compute** primitive consuming a tainted value must come
  from a sanctioned module (``repro/kernels/``, the attention decode
  sites, the moment/QTensor decoders, the paged pool) -- judged by the
  equation's source traceback -- otherwise it is reported.

Contracts attach a taint spec per entry point
(:mod:`repro.analysis.contracts`); ``tests/test_analysis.py`` holds the
positive/negative witnesses and the end-to-end
quantize_pack -> mixed_gemm -> decode chain check.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax import tree_util as jtu
from jax._src import source_info_util as _siu
from jax.extend import core as jcore

__all__ = [
    "PAYLOAD_LANE_REGEX",
    "SANCTIONED_MODULES",
    "TaintViolation",
    "TaintReport",
    "lint_payload_flow",
]

# Default taint seed: argument tree paths naming MixedOperand lanes
# (named key paths via the register_pytree_with_keys registrations of
# MixedOperand / QTensor / PackedMoment).
PAYLOAD_LANE_REGEX = (
    r"payload_q|payload_bf16|payload_nib|micro_scales|\.tags|\.scales"
)

# Source-file fragments whose equations may *consume* payload bytes:
# the kernel implementations themselves, the attention decode sites
# (``_mor_kv_values`` & co), the QTensor/moment decoders, and the paged
# pool (whose gathers/scatters are structural anyway). An equation is
# sanctioned when any frame of its traceback lands in one of these --
# i.e. the consumption happens inside, or on behalf of, a whitelisted
# decode site.
SANCTIONED_MODULES = (
    "repro/kernels/",
    "repro/models/attention.py",
    "repro/optim/moments.py",
    "repro/serve/paged.py",
    "repro/serve/quantized.py",
)

# Primitives that move bytes without interpreting them: taint flows
# through to every output. (select_n mixes whole elements; pad/copy/
# transpose relayout; gather/scatter/dynamic slices relocate.)
STRUCTURAL_PRIMS = frozenset({
    "broadcast_in_dim", "concatenate", "copy", "device_put",
    "dynamic_slice", "dynamic_update_slice", "expand_dims", "gather",
    "pad", "reshape", "rev", "scatter", "scatter-add", "select_n",
    "slice", "squeeze", "stop_gradient", "transpose",
})

# Kernel-call primitives: sanctioned consumers of payload bytes.
KERNEL_PRIMS = frozenset({"pallas_call", "tpu_custom_call", "custom_call"})

_HIGHER_ORDER = frozenset({
    "pjit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "scan", "while", "cond", "shard_map", "custom_partitioning",
})


@dataclasses.dataclass(frozen=True)
class TaintViolation:
    prim: str
    lane: str
    where: str

    def render(self) -> str:
        return (
            f"payload lane {self.lane!r} consumed by `{self.prim}` "
            f"outside sanctioned modules at {self.where}"
        )


@dataclasses.dataclass
class TaintReport:
    seeded: List[str]
    violations: List[TaintViolation]
    n_eqns: int

    @property
    def ok(self) -> bool:
        return not self.violations

    def render(self) -> str:
        head = (
            f"payload-flow: {len(self.seeded)} lane(s) seeded, "
            f"{self.n_eqns} eqn(s) walked, "
            f"{len(self.violations)} violation(s)"
        )
        return "\n".join([head] + [v.render() for v in self.violations])


def _eqn_source_files(eqn) -> List[str]:
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return []
    try:
        return [f.file_name for f in tb.frames]
    except Exception:  # pragma: no cover - exotic jaxlib traceback
        return []


def _eqn_summary(eqn) -> str:
    return _siu.summarize(eqn.source_info)


def _is_sanctioned(eqn, sanctioned: Sequence[str]) -> bool:
    for fname in _eqn_source_files(eqn):
        norm = fname.replace("\\", "/")
        if any(frag in norm for frag in sanctioned):
            return True
    return False


def _sub_jaxprs(eqn):
    """(params key, ClosedJaxpr-or-Jaxpr) pairs of an equation."""
    out = []
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                out.append((key, v))
    return out


def _inner(j):
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


class _Walker:
    def __init__(self, sanctioned, seed_kernel_outputs):
        self.sanctioned = tuple(sanctioned)
        self.seed_kernel_outputs = seed_kernel_outputs
        self.violations: List[TaintViolation] = []
        self.n_eqns = 0

    # -- generic recursion: map outer taint onto inner invars 1:1 ------
    def _recurse(self, jaxpr, in_labels) -> List[Optional[str]]:
        jaxpr = _inner(jaxpr)
        env: Dict[jcore.Var, str] = {}
        n = min(len(jaxpr.invars), len(in_labels))
        for v, lbl in zip(jaxpr.invars[:n], in_labels[:n]):
            if lbl:
                env[v] = lbl
        self._walk(jaxpr, env)
        return [
            env.get(v) if isinstance(v, jcore.Var) else None
            for v in jaxpr.outvars
        ]

    def _walk(self, jaxpr: jcore.Jaxpr, env: Dict[jcore.Var, str]):
        for eqn in jaxpr.eqns:
            self.n_eqns += 1
            name = eqn.primitive.name
            in_labels = [
                env.get(v) if isinstance(v, jcore.Var) else None
                for v in eqn.invars
            ]
            tainted = [lbl for lbl in in_labels if lbl]

            if name in KERNEL_PRIMS or name.endswith("custom_call"):
                # Sanctioned consumer. Optionally treat its uint8
                # outputs as freshly minted payload bytes.
                if self.seed_kernel_outputs:
                    for ov in eqn.outvars:
                        aval = getattr(ov, "aval", None)
                        if aval is not None and getattr(
                            aval, "dtype", None
                        ) is not None and str(aval.dtype) == "uint8":
                            env[ov] = f"{name}:uint8_out"
                continue

            subs = _sub_jaxprs(eqn)
            if subs and (name in _HIGHER_ORDER or not tainted):
                self._recurse_higher_order(eqn, name, in_labels, env)
                continue

            if not tainted:
                continue

            if name in STRUCTURAL_PRIMS:
                for ov in eqn.outvars:
                    env[ov] = tainted[0]
                continue

            if subs:
                self._recurse_higher_order(eqn, name, in_labels, env)
                continue

            if _is_sanctioned(eqn, self.sanctioned):
                # A whitelisted decode: outputs are real numbers again.
                continue

            self.violations.append(TaintViolation(
                prim=name, lane=tainted[0], where=_eqn_summary(eqn)
            ))

    # -- higher-order plumbing ----------------------------------------
    def _recurse_higher_order(self, eqn, name, in_labels, env):
        if name == "scan":
            out_labels = self._run_loop(
                eqn.params["jaxpr"], in_labels
            )
        elif name == "while":
            out_labels = self._run_while(eqn, in_labels)
        elif name == "cond":
            out_labels = self._run_cond(eqn, in_labels)
        else:
            # pjit / closed_call / custom_* / remat / shard_map: the
            # single sub-jaxpr's invars align with eqn.invars (custom_*
            # primitives put the primal jaxpr first; extra symbolic-
            # zero tangent args simply stay untainted).
            subs = _sub_jaxprs(eqn)
            out_labels = self._recurse(subs[0][1], in_labels)
        for ov, lbl in zip(eqn.outvars, out_labels):
            if lbl:
                env[ov] = lbl

    def _run_loop(self, jaxpr, in_labels) -> List[Optional[str]]:
        # scan: invars = [consts..., carry..., xs...]; outvars =
        # [carry..., ys...]. Taint can travel carry-out -> carry-in
        # across iterations: iterate to a fixpoint (bounded by the
        # carry length).
        labels = list(in_labels)
        n_in = len(_inner(jaxpr).invars)
        for _ in range(max(len(labels), 1)):
            out_labels = self._recurse(jaxpr, labels)
            # Feed carries back: scan's carry block sits right after
            # the consts in invars and leads outvars.
            n_carry = min(len(out_labels), n_in)
            new = list(labels)
            changed = False
            offset = n_in - len(out_labels) if n_in >= len(out_labels) \
                else 0
            for i in range(n_carry):
                j = offset + i
                if j < len(new) and out_labels[i] and not new[j]:
                    new[j] = out_labels[i]
                    changed = True
            labels = new
            if not changed:
                break
        return self._recurse(jaxpr, labels)

    def _run_while(self, eqn, in_labels) -> List[Optional[str]]:
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        carry = list(in_labels[cn + bn:])
        body_consts = list(in_labels[cn:cn + bn])
        self._recurse(cond_j, list(in_labels[:cn]) + carry)
        for _ in range(max(len(carry), 1)):
            out = self._recurse(body_j, body_consts + carry)
            changed = False
            for i in range(min(len(out), len(carry))):
                if out[i] and not carry[i]:
                    carry[i] = out[i]
                    changed = True
            if not changed:
                break
        return self._recurse(body_j, body_consts + carry)

    def _run_cond(self, eqn, in_labels) -> List[Optional[str]]:
        branches = eqn.params["branches"]
        operand_labels = list(in_labels[1:])  # invars[0] is the index
        merged: List[Optional[str]] = []
        for br in branches:
            out = self._recurse(br, operand_labels)
            if not merged:
                merged = list(out)
            else:
                merged = [
                    a or b for a, b in
                    zip(merged, out + [None] * len(merged))
                ]
        return merged


def lint_payload_flow(
    fn: Callable,
    args: Tuple,
    *,
    taint: str = PAYLOAD_LANE_REGEX,
    seed_kernel_outputs: bool = False,
    sanctioned: Sequence[str] = SANCTIONED_MODULES,
) -> TaintReport:
    """Trace ``fn(*args)`` to a jaxpr and lint the payload-lane flow.

    ``taint`` is a regex matched against each flattened argument's key
    path (``jax.tree_util.keystr``); matching leaves seed the taint
    set. ``seed_kernel_outputs=True`` additionally taints every uint8
    output of a kernel call, covering chains where the payload is
    *produced* inside the traced function (quantize_pack ->
    mixed_gemm). Returns a :class:`TaintReport`; ``report.ok`` is the
    pass/fail.
    """
    leaves_with_paths, treedef = jtu.tree_flatten_with_path(args)
    paths = [jtu.keystr(p) for p, _ in leaves_with_paths]
    leaves = [leaf for _, leaf in leaves_with_paths]

    def flat_fn(*flat):
        return fn(*jtu.tree_unflatten(treedef, flat))

    closed = jax.make_jaxpr(flat_fn)(*leaves)
    pat = re.compile(taint)
    env: Dict[jcore.Var, str] = {}
    seeded: List[str] = []
    for var, path in zip(closed.jaxpr.invars, paths):
        if pat.search(path):
            env[var] = path
            seeded.append(path)

    walker = _Walker(sanctioned, seed_kernel_outputs)
    walker._walk(closed.jaxpr, env)
    return TaintReport(
        seeded=seeded,
        violations=walker.violations,
        n_eqns=walker.n_eqns,
    )
