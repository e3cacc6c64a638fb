"""Declarative structural contracts for the hot entry points.

One registry, three consumers: ``tests/`` (the HLO-contract tests in
``test_quantize_pack.py`` / ``test_nvfp4.py`` / ``test_mixed_gemm.py``
/ ``test_serve_engine.py`` and the clean-pass suite in
``test_analysis.py``), ``benchmarks/`` (``bench_kernels.py`` /
``bench_serve.py`` assert the same pins and emit the
``kernel/analysis_contracts`` row), and CI's blocking ``lint`` job
(``tools/lint_repro.py --contracts``). The acceptance literals live
*only* here -- deleting a contract or loosening a constant breaks every
consumer at once, which is the point.

A :class:`Contract` names an entry point plus the rules it must
satisfy; :func:`check_contract` evaluates the rules with the
primitives in :mod:`repro.analysis.hlo_rules` (TPU cross-lowering
structure, forbidden op families, donation markers) and
:mod:`repro.analysis.jaxpr_lint` (payload-lane taint flow,
accumulation dtypes). Registering a new entry point is one
:func:`register` call -- see docs/analysis.md.

A cross-lowering that fails raises: a kernel the TPU lowering refuses
is a broken contract, not a missing lane.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from . import hlo_rules
from .jaxpr_lint import lint_payload_flow

__all__ = [
    "SINGLE_LAUNCH",
    "MAX_PACK_OPS_OVER_SELECT",
    "MOR_DOT_FWD_LAUNCHES",
    "MOR_DOT_GRAD_LAUNCHES",
    "DECODE_ROW_BLOCK",
    "ENGINE_MIN_DONATED_ARGS",
    "ContractCase",
    "Contract",
    "ContractReport",
    "AnalysisSummary",
    "REGISTRY",
    "register",
    "get",
    "check_contract",
    "check",
    "assert_contract",
    "check_all",
    "engine_decode_case",
    "engine_decode_report",
]

# ----------------------------------------------------------------------
# The acceptance literals. Every bench/test structural pin reads these;
# nothing else in the repo may restate them.
# ----------------------------------------------------------------------

# A real-quantization event (fused select+pack), a mixed block GEMM, a
# serving qdot and a flash call are each ONE tpu_custom_call.
SINGLE_LAUNCH: Tuple[int, int] = (1, 1)

# The fused pack adds ZERO operand-sized XLA ops over bare selection
# (the pre-PR-5 lowering re-blocked / re-scaled / re-cast the operand
# in XLA after the select).
MAX_PACK_OPS_OVER_SELECT = 0

# mor_dot(fuse_gemm=True) forward: 2 selection kernels + 1 GEMM; the
# two selection events share one lowered body when jax dedups nested
# jits (2), or lower separately (3). Anything else means the GEMM
# stopped being a single fused kernel.
MOR_DOT_FWD_LAUNCHES: Tuple[int, int] = (2, 3)

# Full fwd+bwd (dgrad+wgrad) of the fused mor_dot: fwd events plus the
# two grad-operand selections and two grad GEMMs, with the same
# dedup latitude (measured 5 on the pinned jax; 4..7 covers the
# dedup/no-dedup corners without letting an unfused GEMM through).
MOR_DOT_GRAD_LAUNCHES: Tuple[int, int] = (4, 7)

# Decode activations are (slots, K) with slots << 128: the skinny-M
# lane packs activation rows at the 16-row sublane tile, never padded
# toward the 128 MXU tile (PR 6's serving contract).
DECODE_ROW_BLOCK = 16

# The engine's jitted decode step donates (at least) the KV pool tree.
ENGINE_MIN_DONATED_ARGS = 1


# ----------------------------------------------------------------------
# Contract model
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ContractCase:
    """A concrete (fn, args) instantiation of an entry point.

    ``fn`` may be a plain callable or an already-jitted function (the
    engine's donating step); ``operand_shape`` feeds the operand-sized
    pass counter; ``baseline_fn`` is the reference lowering for
    pack-ops-over-baseline rules (same args)."""

    fn: Callable
    args: Tuple
    operand_shape: Optional[Tuple[int, int]] = None
    baseline_fn: Optional[Callable] = None
    donate_argnums: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class Contract:
    """Declarative rules for one entry point. ``None`` disables a rule;
    every enabled rule counts toward ``rules_evaluated``."""

    name: str
    build: Callable[[], ContractCase]
    custom_calls: Optional[Tuple[int, int]] = None
    max_pack_ops_over_baseline: Optional[int] = None
    forbid_f64: bool = True
    forbid_host_transfers: bool = False
    require_f32_accum: bool = False
    min_donated_args: Optional[int] = None
    taint: Optional[str] = None          # arg-path regex to seed
    seed_kernel_outputs: bool = False
    notes: str = ""


@dataclasses.dataclass
class ContractReport:
    name: str
    violations: List[str]
    rules_evaluated: int
    counters: Dict[str, int]

    @property
    def ok(self) -> bool:
        return not self.violations

    def render(self) -> str:
        status = "ok" if self.ok else "FAIL"
        head = (
            f"{self.name}: {status} ({self.rules_evaluated} rule(s), "
            f"counters {self.counters})"
        )
        return "\n".join([head] + [f"  {v}" for v in self.violations])


@dataclasses.dataclass
class AnalysisSummary:
    contracts_checked: int
    rules_evaluated: int
    violations: List[str]
    reports: List[ContractReport]

    @property
    def ok(self) -> bool:
        return not self.violations


REGISTRY: Dict[str, Contract] = {}
_CASE_CACHE: Dict[str, ContractCase] = {}


def register(contract: Contract) -> Contract:
    if contract.name in REGISTRY:
        raise ValueError(f"duplicate contract {contract.name!r}")
    REGISTRY[contract.name] = contract
    return contract


def get(name: str) -> Contract:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no contract {name!r}; registered: {sorted(REGISTRY)}"
        ) from None


def _case_for(contract: Contract) -> ContractCase:
    case = _CASE_CACHE.get(contract.name)
    if case is None:
        case = contract.build()
        _CASE_CACHE[contract.name] = case
    return case


# ----------------------------------------------------------------------
# Rule engine
# ----------------------------------------------------------------------
def _default_lowering(case: ContractCase) -> str:
    fn = case.fn
    if hasattr(fn, "trace"):  # already jitted (donation preserved)
        return fn.trace(*case.args).lower().as_text()
    return hlo_rules.lowering_text(
        fn, *case.args, donate_argnums=case.donate_argnums
    )


def _jaxpr_of(case: ContractCase):
    return jax.make_jaxpr(case.fn)(*case.args)


def _low_precision_accum_dots(jaxpr: jcore.Jaxpr, acc: List[str]):
    """dot_general equations (recursively, pallas kernel bodies
    included) whose accumulator dtype is narrower than f32."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            for ov in eqn.outvars:
                dt = getattr(getattr(ov, "aval", None), "dtype", None)
                if dt is not None and jnp.issubdtype(
                    dt, jnp.floating
                ) and jnp.finfo(dt).bits < 32:
                    acc.append(f"dot_general accumulates in {dt}")
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                if isinstance(v, jcore.ClosedJaxpr):
                    _low_precision_accum_dots(v.jaxpr, acc)
                elif isinstance(v, jcore.Jaxpr):
                    _low_precision_accum_dots(v, acc)


def _jaxpr_f64(jaxpr: jcore.Jaxpr, acc: List[str]):
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            dt = getattr(getattr(ov, "aval", None), "dtype", None)
            if dt is not None and str(dt) == "float64":
                acc.append(f"{eqn.primitive.name} produces float64")
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                if isinstance(v, jcore.ClosedJaxpr):
                    _jaxpr_f64(v.jaxpr, acc)
                elif isinstance(v, jcore.Jaxpr):
                    _jaxpr_f64(v, acc)


def check_contract(contract: Contract) -> ContractReport:
    """Evaluate every enabled rule; never raises on rule failure."""
    case = _case_for(contract)
    violations: List[str] = []
    counters: Dict[str, int] = {}
    rules = 0

    tpu_txt: Optional[str] = None
    wants_tpu = (
        contract.custom_calls is not None
        or contract.max_pack_ops_over_baseline is not None
    )
    if wants_tpu:
        tpu_txt = hlo_rules.tpu_lowering_text(case.fn, *case.args)

    if contract.custom_calls is not None:
        rules += 1
        lo, hi = contract.custom_calls
        n = hlo_rules.count_custom_calls(tpu_txt)
        counters["tpu_kernel_launches"] = n
        if not lo <= n <= hi:
            violations.append(f"custom calls: {n} outside [{lo}, {hi}]")

    if contract.max_pack_ops_over_baseline is not None:
        rules += 1
        if case.baseline_fn is None:
            violations.append("max_pack_ops_over_baseline needs a baseline")
        else:
            base_txt = hlo_rules.tpu_lowering_text(
                case.baseline_fn, *case.args
            )
            shape = case.operand_shape
            extra = (
                hlo_rules.operand_sized_ops(tpu_txt, shape)
                - hlo_rules.operand_sized_ops(base_txt, shape)
            )
            counters["tpu_pack_ops"] = max(extra, 0)
            if extra > contract.max_pack_ops_over_baseline:
                violations.append(
                    f"pack ops over baseline: {extra} > "
                    f"{contract.max_pack_ops_over_baseline}"
                )
            # Forbidden packing families must not grow either: no new
            # operand-sized convert/pad/bitcast beyond the baseline.
            new_packing = (
                len(hlo_rules.operand_sized_packing_ops(tpu_txt, shape))
                - len(hlo_rules.operand_sized_packing_ops(
                    base_txt, shape
                ))
            )
            if new_packing > 0:
                violations.append(
                    f"{new_packing} new operand-sized "
                    f"convert/pad/bitcast packing op(s) over baseline"
                )

    needs_default_lowering = (
        contract.forbid_host_transfers
        or contract.min_donated_args is not None
    )
    low_txt = _default_lowering(case) if needs_default_lowering else None

    if contract.forbid_host_transfers:
        rules += 1
        hits = hlo_rules.host_transfer_lines(low_txt)
        counters["host_transfer_ops"] = len(hits)
        if hits:
            violations.append(
                f"host transfers in lowering: {hits[:3]}"
            )

    if contract.min_donated_args is not None:
        rules += 1
        n = hlo_rules.donated_arg_count(low_txt)
        counters["donated_args"] = n
        if n < contract.min_donated_args:
            violations.append(
                f"donated args: {n} < {contract.min_donated_args} "
                "(buffer donation lost)"
            )

    closed = None
    if contract.forbid_f64 or contract.require_f32_accum:
        closed = _jaxpr_of(case)

    if contract.forbid_f64:
        rules += 1
        acc: List[str] = []
        _jaxpr_f64(closed.jaxpr, acc)
        counters["f64_ops"] = len(acc)
        if acc:
            violations.append(f"f64 in jaxpr: {acc[:3]}")

    if contract.require_f32_accum:
        rules += 1
        acc = []
        _low_precision_accum_dots(closed.jaxpr, acc)
        counters["low_precision_accum_dots"] = len(acc)
        if acc:
            violations.append(f"accumulation dtype: {acc[:3]}")

    if contract.taint is not None:
        rules += 1
        rep = lint_payload_flow(
            case.fn, case.args,
            taint=contract.taint,
            seed_kernel_outputs=contract.seed_kernel_outputs,
        )
        counters["tainted_lanes"] = len(rep.seeded)
        if not rep.ok:
            violations.extend(
                v.render() for v in rep.violations[:5]
            )

    return ContractReport(
        name=contract.name,
        violations=violations,
        rules_evaluated=rules,
        counters=counters,
    )


def check(name: str) -> ContractReport:
    return check_contract(get(name))


def assert_contract(name: str) -> ContractReport:
    """check() that raises AssertionError with the rendered report --
    the one-liner tests and benches call."""
    report = check(name)
    if not report.ok:
        raise AssertionError(report.render())
    return report


def check_all(names: Optional[Sequence[str]] = None) -> AnalysisSummary:
    """Evaluate every registered contract (the CI lint job, the
    ``kernel/analysis_contracts`` bench row and ``test_analysis.py``
    all run exactly this)."""
    reports = [check(n) for n in (names or sorted(REGISTRY))]
    return AnalysisSummary(
        contracts_checked=len(reports),
        rules_evaluated=sum(r.rules_evaluated for r in reports),
        violations=[
            f"{r.name}: {v}" for r in reports for v in r.violations
        ],
        reports=reports,
    )


# ----------------------------------------------------------------------
# Entry-point registrations
# ----------------------------------------------------------------------
# Payload taint is seeded by lane name in the flattened argument paths
# (jaxpr_lint.PAYLOAD_LANE_REGEX); pool-tree leaves are keyed by lane
# name too, so the bare-name alternatives cover dict-keyed trees.
_TAINT = (
    r"payload_q|payload_bf16|payload_nib|micro_scales"
    r"|\.tags|\.scales|\['tags'\]|\['scales'\]"
)


def _rng2d(shape, seed, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _quantize_pack_case(recipe: str) -> ContractCase:
    from repro.core.mor import quantize_for_gemm
    from repro.core.policy import MoRPolicy
    from repro.kernels import ops as kops

    pol = MoRPolicy(recipe=recipe, partition="block", backend="pallas")
    part = __import__(
        "repro.core.partition", fromlist=["Partition"]
    ).Partition("block", (128, 128), align=(2, 16))
    x = jnp.zeros((256, 256), jnp.bfloat16)
    return ContractCase(
        fn=lambda a: quantize_for_gemm(a, pol),
        args=(x,),
        operand_shape=(256, 256),
        baseline_fn=lambda a: kops.mor_select(
            a, part, recipe, "gam", backend="pallas"
        ).y,
    )


register(Contract(
    name="quantize_pack_sub3",
    build=lambda: _quantize_pack_case("sub3"),
    custom_calls=SINGLE_LAUNCH,
    max_pack_ops_over_baseline=MAX_PACK_OPS_OVER_SELECT,
    notes="fused one-pass selection+packing (PR 5 acceptance)",
))

register(Contract(
    name="quantize_pack_sub4",
    build=lambda: _quantize_pack_case("sub4"),
    custom_calls=SINGLE_LAUNCH,
    max_pack_ops_over_baseline=MAX_PACK_OPS_OVER_SELECT,
    notes="four-way NVFP4 fused pack stays one launch, no XLA "
          "nibble-packing pass",
))


def _mor_quantize_case() -> ContractCase:
    from repro.core import mor_quantize
    from repro.core.policy import MoRPolicy

    pol = MoRPolicy(recipe="sub4", backend="pallas")
    return ContractCase(
        fn=lambda a: mor_quantize(a, pol)[0],
        args=(_rng2d((256, 256), 14),),
        operand_shape=(256, 256),
    )


register(Contract(
    name="mor_quantize_sub4",
    build=_mor_quantize_case,
    custom_calls=SINGLE_LAUNCH,
    notes="fake-quantization event: fused four-way selection",
))


def _mixed_gemm_case() -> ContractCase:
    from repro.core.mor import quantize_for_gemm
    from repro.core.policy import MoRPolicy
    from repro.kernels import ops as kops

    pol = MoRPolicy(recipe="sub3", backend="interpret")
    amo, _ = quantize_for_gemm(_rng2d((256, 256), 0), pol)
    bmo, _ = quantize_for_gemm(_rng2d((128, 256), 1), pol)
    return ContractCase(
        fn=lambda a, b: kops.mixed_gemm(a, b, backend="pallas"),
        args=(amo, bmo),
        operand_shape=(256, 256),
    )


register(Contract(
    name="mixed_gemm",
    build=_mixed_gemm_case,
    custom_calls=SINGLE_LAUNCH,
    require_f32_accum=True,
    taint=_TAINT,
    notes="mixed-representation block GEMM: one launch, payload lanes "
          "only enter the kernel, f32 accumulation",
))


def _qdot_case(recipe: str) -> ContractCase:
    from repro.core.policy import MoRPolicy
    from repro.serve.quantized import qdot, quantize_weight

    w = _rng2d((256, 256), 15)
    qt, _ = quantize_weight(
        w, MoRPolicy(recipe=recipe, partition="block", backend="xla")
    )
    x = _rng2d((64, 256), 16)
    return ContractCase(
        fn=lambda a, q: qdot(a, q, backend="pallas"),
        args=(x, qt),
        operand_shape=(256, 256),
    )


register(Contract(
    name="qdot_sub3",
    build=lambda: _qdot_case("sub3"),
    custom_calls=SINGLE_LAUNCH,
    require_f32_accum=True,
    taint=_TAINT,
    notes="serving GEMM against a sub3 QTensor is one fused kernel",
))

register(Contract(
    name="qdot_sub4",
    build=lambda: _qdot_case("sub4"),
    custom_calls=SINGLE_LAUNCH,
    require_f32_accum=True,
    taint=_TAINT,
    notes="serving GEMM against an NVFP4 QTensor is one fused kernel",
))


def _mor_dot_policy():
    from repro.core import paper_default

    p = paper_default("sub3").replace(fuse_gemm=True)
    return p.replace(
        act=p.act.replace(backend="pallas"),
        weight=p.weight.replace(backend="pallas"),
        grad=p.grad.replace(backend="pallas"),
    )


def _mor_dot_fwd_case() -> ContractCase:
    from repro.core import mor_dot, new_token

    p = _mor_dot_policy()
    return ContractCase(
        fn=lambda a, b: mor_dot(a, b, new_token(), p)[0],
        args=(_rng2d((128, 256), 4), _rng2d((256, 128), 5)),
        operand_shape=(128, 256),
    )


register(Contract(
    name="mor_dot_fused_fwd",
    build=_mor_dot_fwd_case,
    custom_calls=MOR_DOT_FWD_LAUNCHES,
    notes="2 selection events (may dedup to one lowered body) + 1 "
          "fused GEMM",
))


def _mor_dot_grads_case() -> ContractCase:
    from repro.core import mor_dot, new_token

    p = _mor_dot_policy()

    def loss(a, b):
        return mor_dot(a, b, new_token(), p)[0].astype(
            jnp.float32
        ).sum()

    return ContractCase(
        fn=lambda a, b: jax.grad(loss, argnums=(0, 1))(a, b),
        args=(_rng2d((128, 256), 4), _rng2d((256, 128), 5)),
        operand_shape=(128, 256),
    )


register(Contract(
    name="mor_dot_fused_grads",
    build=_mor_dot_grads_case,
    custom_calls=MOR_DOT_GRAD_LAUNCHES,
    require_f32_accum=True,
    notes="dgrad+wgrad keep fused selection + fused GEMMs",
))


def _flash_case() -> ContractCase:
    from repro.kernels import ops as kops

    q = _rng2d((2, 128, 4, 64), 6)
    k = _rng2d((2, 128, 2, 64), 7)
    return ContractCase(
        fn=lambda a, b, c: kops.flash_attention(
            a, b, c, backend="pallas"
        ),
        args=(q, k, k),
        operand_shape=(2 * 4 * 128, 64),
    )


register(Contract(
    name="flash_attention",
    build=_flash_case,
    custom_calls=SINGLE_LAUNCH,
    require_f32_accum=True,
    notes="GQA flash fwd is one fused kernel with f32 accumulation",
))


def _compress_grads_case() -> ContractCase:
    from repro.core.policy import MoRPolicy
    from repro.optim.compress import compress_grads

    pol = MoRPolicy(recipe="sub3", backend="interpret")
    g = {"w": _rng2d((128, 128), 8, jnp.float32)}
    return ContractCase(
        fn=lambda grads: compress_grads(grads, "mor", policy=pol)[0],
        args=(g,),
    )


register(Contract(
    name="compress_grads_mor",
    build=_compress_grads_case,
    taint=_TAINT,
    seed_kernel_outputs=True,
    notes="gradient compression round-trip: packed bytes only decoded "
          "in sanctioned modules, no f64",
))


def _adamw_case() -> ContractCase:
    from repro.optim.adamw import AdamWConfig, adamw_update, \
        init_opt_state
    from repro.optim.moments import FP8_MOMENTS

    cfg = AdamWConfig()
    params = {"w": _rng2d((64, 64), 9)}
    moments = FP8_MOMENTS.replace(min_leaf=0)
    opt = init_opt_state(params, moments=moments)
    grads = {"w": _rng2d((64, 64), 10, jnp.float32)}
    return ContractCase(
        fn=lambda g, o: adamw_update(cfg, g, o, moments=moments)[:2],
        args=(grads, opt),
    )


register(Contract(
    name="adamw_packed_moments",
    build=_adamw_case,
    taint=_TAINT,
    seed_kernel_outputs=True,
    notes="packed Adam moments decode only in optim.moments; update "
          "math stays f64-free",
))


# ------------------------------------------------------------ engine --
def engine_decode_case(eng=None) -> ContractCase:
    """The engine's jitted batched-decode step as a contract case.

    With ``eng=None`` a tiny quantized kv_mor engine is built (reduced
    gemma-2b, 128-token vocab -- the test-suite workhorse config);
    passing a live engine lets ``tests/test_serve_engine.py`` and
    benches evaluate the same rules on *their* engine.
    """
    if eng is None:
        eng = _tiny_engine()
    slots = eng.scfg.slots
    bt = jnp.asarray(np.asarray(eng.pool.block_table, np.int32))
    toks = jnp.zeros((slots, 1), jnp.int32)
    cur = jnp.zeros((slots,), jnp.int32)
    return ContractCase(
        fn=eng._step_fn,  # jitted: donation markers intact
        args=(eng.params, eng.tokens, eng.pool.tree, bt, toks, cur),
    )


_TINY_ENGINE: List = []


def _tiny_engine():
    if _TINY_ENGINE:  # shared by the decode + prefill cases
        return _TINY_ENGINE[0]
    import dataclasses as _dc

    from repro.configs import get_config, reduced
    from repro.core import TENSOR_MOR, MoRPolicy
    from repro.models import init_params
    from repro.serve import Engine, ServeConfig

    cfg = _dc.replace(reduced(get_config("gemma-2b")), vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(slots=4, max_seq=64, page_size=16, kv_mor=True)
    _TINY_ENGINE.append(Engine(
        cfg, TENSOR_MOR, params, scfg,
        quantize=MoRPolicy(recipe="sub3", backend="interpret"),
        quantize_min_size=0,
    ))
    return _TINY_ENGINE[0]


_ENGINE_CONTRACT_KW = dict(
    forbid_host_transfers=True,
    min_donated_args=ENGINE_MIN_DONATED_ARGS,
    taint=_TAINT,
    notes="jitted decode step: no host round-trips, KV pool donated, "
          "payload lanes only consumed by sanctioned decode sites",
)

register(Contract(
    name="engine_decode_step",
    build=engine_decode_case,
    **_ENGINE_CONTRACT_KW,
))


def engine_decode_report(eng) -> ContractReport:
    """Evaluate the ``engine_decode_step`` rules against a live engine
    (same Contract object, caller-supplied case)."""
    contract = get("engine_decode_step")
    case = engine_decode_case(eng)
    probe = dataclasses.replace(
        contract, name=f"engine_decode_step[{type(eng).__name__}]",
        build=lambda: case,
    )
    return check_contract(probe)


def _engine_prefill_case() -> ContractCase:
    eng = _tiny_engine()
    prompt = jnp.zeros((1, 8), jnp.int32)
    return ContractCase(
        fn=eng._prefill,
        args=(eng.params, eng.tokens, {"tokens": prompt}),
    )


register(Contract(
    name="engine_prefill",
    build=_engine_prefill_case,
    forbid_host_transfers=True,
    taint=_TAINT,
    notes="jitted prefill: no host round-trips; quantized weights' "
          "payload lanes stay in sanctioned consumers",
))


# ------------------------------------------------------------ robust --
def _robust_guard_case() -> ContractCase:
    """The PR-10 guard acceptance: a full real-quantization event with
    the layout-v4 guard lanes *consumed* (stats returned alongside the
    pack) lowers with zero operand-sized XLA ops beyond the bare fused
    selection -- nonfinite detection rides the amax / per-block error
    sums the event already computes, so the clean path's structure is
    byte-for-byte the PR-5 one-pass contract."""
    from repro.core.mor import quantize_for_gemm
    from repro.core.partition import Partition
    from repro.core.policy import MoRPolicy
    from repro.kernels import ops as kops

    pol = MoRPolicy(recipe="sub3", partition="block", backend="pallas")
    part = Partition("block", (128, 128))
    x = jnp.zeros((256, 256), jnp.bfloat16)
    return ContractCase(
        fn=lambda a: quantize_for_gemm(a, pol),
        args=(x,),
        operand_shape=(256, 256),
        baseline_fn=lambda a: kops.mor_select(
            a, part, "sub3", "gam", backend="pallas"
        ).y,
    )


register(Contract(
    name="robust_guard_event",
    build=_robust_guard_case,
    custom_calls=SINGLE_LAUNCH,
    max_pack_ops_over_baseline=MAX_PACK_OPS_OVER_SELECT,
    taint=_TAINT,
    notes="stats-v4 guard lanes (guard_flags/fallback_count) cost zero "
          "operand-sized passes on the clean path (docs/robustness.md)",
))


def _train_step_case() -> ContractCase:
    """The *whole* training step -- loss, grads, MoR gradient
    compression, packed-moment AdamW -- as one taint case: every MoR
    payload lane born anywhere in the step (compressed grads, packed
    moments) must reach only sanctioned kernels/decoders. The PR-9
    item this closes ran the walk over single events; this traces the
    full composition on the reduced llama config."""
    import dataclasses as _dc

    from repro.configs import get_config, reduced
    from repro.core import paper_default
    from repro.models import init_params
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.optim.moments import MomentPolicy
    from repro.robust import GuardPolicy
    from repro.train import TrainConfig, make_train_step

    cfg = _dc.replace(reduced(get_config("llama3-8b")), vocab=64)
    pol = paper_default("sub3")
    pol = pol.replace(
        act=pol.act.replace(backend="xla"),
        weight=pol.weight.replace(backend="xla"),
        grad=pol.grad.replace(backend="xla"),
    )
    xla_sub3 = lambda **kw: __import__(
        "repro.core.policy", fromlist=["MoRPolicy"]
    ).MoRPolicy(recipe="sub3", backend="xla", **kw)
    moments = MomentPolicy(m=xla_sub3(), v=xla_sub3(threshold=0.02))
    tcfg = TrainConfig(
        optimizer=AdamWConfig(warmup_steps=5, total_steps=50),
        compress_grads="mor_ef",
        grad_policy=xla_sub3(),
        moments=moments,
        # Guarded: the walk also covers the skip-step selects over the
        # packed-moment payload lanes (docs/robustness.md).
        guard=GuardPolicy(),
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params, moments=moments, ef=True)
    step = make_train_step(cfg, pol, tcfg)
    batch = {
        "tokens": jnp.zeros((4, 32), jnp.int32),
        "labels": jnp.zeros((4, 32), jnp.int32),
    }
    return ContractCase(fn=step, args=(params, opt, batch))


register(Contract(
    name="train_step_taint",
    build=_train_step_case,
    taint=_TAINT,
    seed_kernel_outputs=True,
    notes="payload-lane taint walk over the full train step (grads "
          "compressed mor_ef + packed Adam moments): packed bytes only "
          "decode in sanctioned modules, no f64 anywhere in the step",
))
