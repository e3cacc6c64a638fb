"""The tiny train cell's compiled step and the layer each of its
instructions names, for the scope coverage tests (CPU and a described
TPU)."""
from __future__ import annotations

import re
from typing import Iterator, Optional, Tuple

CHECKED = ("dot", "convolution", "custom-call", "reduce", "fusion")

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(")
_CALLED = re.compile(
    r"\b(calls|to_apply|select|scatter|comparator)=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


STEP_LAYERS = {"embed", "stack", "norm", "residual", "attn/qkv", "attn/rope",
               "attn/core", "mlp/act", "mor_quant", "gemm", "head", "loss",
               "optim"}
# The cell's policy (128 x 128 blocks, transpose-invariant) reuses the
# dgrad pack of dy for wgrad, so it has no wgrad_dy event.
LINEAR_SCOPES = {f"{sub}/{leaf}"
                 for sub in ("attn/qkv", "attn/proj", "mlp/fc1", "mlp/fc2")
                 for leaf in ("mor_quant/fwd_x", "mor_quant/fwd_w",
                              "mor_quant/dgrad_dy", "mor_quant/dgrad_w",
                              "mor_quant/wgrad_x", "gemm/fwd", "gemm/dgrad",
                              "gemm/wgrad")}


def check_step_scopes(text: str) -> None:
    """Every dot, custom call, reduce and fusion of the compiled step
    that carries an op_name names exactly one layer (quantization and
    product never nested), backward and recomputed ops included; every
    layer of the step and every event and product of each linear is
    there. Instructions XLA builds without metadata (copies, buffer
    allocations, some merged ops) name none and count as unattributed
    in a trace."""
    from chipbench.metrics._scopes import SKIP, _segments, layer_of

    checked = [(n, op) for n, code, op in instructions(text)
               if code in CHECKED]
    named = [op for _, op in checked if op is not None]
    assert len(named) > len(checked) / 2
    bad = [op for op in named if layer_of(op) is None
           or ("/mor_quant/" in op and "/gemm/" in op)]
    assert not bad, bad[:5]
    assert any("transpose(" in op for op in named)
    assert any("rematted_computation" in op for op in named)
    names = set(op_names(text))
    assert STEP_LAYERS <= {layer_of(op) for op in names}
    paths = {"/".join(s for s in _segments(op) if s not in SKIP)
             for op in names}
    missing = {want for want in LINEAR_SCOPES
               if not any(want in path for path in paths)}
    assert not missing, sorted(missing)


def train_step_hlo(cell, sharding=None, backend: str = "auto") -> str:
    """Compiled HLO text of the cell's train step, for the device of
    ``sharding`` (the CPU when None), with the quantizer's kernels on
    ``backend``."""
    import jax
    import jax.numpy as jnp

    from chipbench import train as T
    from repro.models import init_params
    from repro.optim import AdamWConfig, init_opt_state
    from repro.train.train_step import TrainConfig, make_train_step

    cfg, job = cell.family.arch_config(cell.config), cell.traffic
    pol = T.policy_of(job)
    pol = pol.replace(**{k: getattr(pol, k).replace(backend=backend)
                         for k in ("act", "weight", "grad")})
    step = make_train_step(
        cfg, pol, TrainConfig(optimizer=AdamWConfig(**job["optimizer"])))
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=sharding)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(init_opt_state, params)
    ids = jax.ShapeDtypeStruct((job["global_batch"], job["seq_len"]),
                               jnp.int32, sharding=sharding)
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        jax.tree.map(shape, params), jax.tree.map(shape, opt),
        {"tokens": ids, "labels": ids}).compile().as_text()


def instructions(text: str) -> Iterator[Tuple[str, str, Optional[str]]]:
    """(name, opcode, op_name or None) of each instruction the device
    runs: those of computations that are neither inside a fusion nor a
    reducer or comparator. A fusion carries its root's op_name."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    inner = set()
    for lines in comps.values():
        for line in lines:
            for m in _CALLED.finditer(line):
                if m.group(1) != "calls" or " fusion(" in line:
                    inner.add(m.group(2))
    for comp, lines in comps.items():
        if comp in inner:
            continue
        for line in lines:
            m = _INST.match(line)
            if m:
                on = _OP_NAME.search(line)
                yield m.group(1), m.group(2), on.group(1) if on else None


def op_names(text: str) -> Iterator[str]:
    return (m.group(1) for m in _OP_NAME.finditer(text))
