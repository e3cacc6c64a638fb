"""Grouped MoR quantization of an MoE layer's expert buffer
(``repro.core.grouped``): each expert's routed rows are one MoR tensor,
padding rows change nothing of it, the kernels agree with the XLA
lowering, and the dispatch lays every copy of a held expert out once."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grouped import grouped_quantize, make_groups
from repro.core.mor import mor_quantize
from repro.core.policy import MoRPolicy

BM = 128
# Stats lanes compared exactly: decision, amax, frac_e4m3, frac_bf16,
# nonzero_frac, group_mantissa. The mean relative error (lane 1) sums
# the same blocks' sums in another order.
LANES = [0, 2, 3, 5, 6, 7]
REL_ERR = 1


def _buffer(rows, K, seed, heavy=()):
    """Routed rows of each group laid out by group, each group padded
    with zero rows to a whole block, and a zero tail of two blocks. A
    group in ``heavy`` gets heavy-tailed values (the 'tensor' recipe
    rejects E4M3 for it)."""
    groups = make_groups(jnp.asarray(rows), BM)
    sizes = np.asarray(groups.sizes)
    R = int(sizes.sum()) + 2 * BM
    rng = np.random.default_rng(seed)
    x = np.zeros((R, K), np.float32)
    parts, at = [], 0
    for g, r in enumerate(rows):
        v = rng.standard_normal((r, K)).astype(np.float32)
        if g in heavy:
            v = v * np.exp(3.0 * rng.standard_normal((r, K)))
        x[at:at + r] = v
        parts.append(v)
        at += int(sizes[g])
    return jnp.asarray(x, jnp.bfloat16), groups, parts


def _policy(backend):
    return MoRPolicy(recipe="tensor", partition="block",
                     block_shape=(BM, BM), backend=backend)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_padding_rows_leave_each_experts_decision_and_sums(backend):
    """Each group's decision, error sum, amax, non-zero share and GAM
    mantissa, and its quantized rows, are those of ``mor_quantize`` on
    the group's routed rows alone: the padding rows and the tail count
    for nothing."""
    rows = [5, 130, 0, 256, 77]
    x, groups, parts = _buffer(rows, 256, 0, heavy=(3,))
    y, stats = jax.jit(lambda x: grouped_quantize(
        x, groups, _policy(backend)))(x)
    y, stats = np.asarray(y, np.float32), np.asarray(stats)
    sizes = np.asarray(groups.sizes)
    decisions = []
    at = 0
    for g, part in enumerate(parts):
        r = rows[g]
        if r:
            want_y, want = mor_quantize(jnp.asarray(part, jnp.bfloat16),
                                        _policy(backend))
            np.testing.assert_array_equal(stats[g, LANES],
                                          np.asarray(want)[LANES])
            np.testing.assert_allclose(stats[g, REL_ERR],
                                       np.asarray(want)[REL_ERR], rtol=1e-5)
            np.testing.assert_array_equal(
                y[at:at + r], np.asarray(want_y, np.float32))
            decisions.append(stats[g, 0])
        # Padding and tail rows stay zero.
        assert not y[at + r:at + sizes[g]].any()
        at += sizes[g]
    assert not y[at:].any()
    assert 0.0 in decisions and 1.0 in decisions, decisions


def test_kernels_equal_the_xla_lowering():
    rows = [300, 1, 128, 0, 40]
    x, groups, _ = _buffer(rows, 384, 1, heavy=(1,))
    (y_x, st_x), (y_k, st_k) = (jax.jit(lambda x, be=be: grouped_quantize(
        x, groups, _policy(be)))(x) for be in ("xla", "interpret"))
    np.testing.assert_array_equal(np.asarray(y_x, np.float32),
                                  np.asarray(y_k, np.float32))
    st_x, st_k = np.asarray(st_x), np.asarray(st_k)
    assert set(st_x[[0, 1, 2, 4], 0]) == {0.0, 1.0}
    np.testing.assert_array_equal(st_x[:, LANES], st_k[:, LANES])
    np.testing.assert_allclose(st_x[:, REL_ERR], st_k[:, REL_ERR], rtol=1e-5)


def test_dispatch_lays_each_held_copy_out_once():
    """Every token-copy that chose a held expert has one buffer row, in
    its expert's group, and that row holds its token; copies of experts
    held elsewhere have none; the groups are padded to whole blocks and
    fit the static bound."""
    from repro.configs import get_config, reduced
    from repro.models.blocks import _dispatch

    cfg = dataclasses.replace(reduced(get_config("moonlight-16b-a3b")),
                              n_experts=8, top_k=3, held_experts=4,
                              first_expert=2)
    T = 200
    idx = jax.random.randint(jax.random.PRNGKey(3), (T, 3), 0, 8)
    groups, src, dest = _dispatch(idx, cfg, T, BM)
    idx, src, dest = np.asarray(idx), np.asarray(src), np.asarray(dest)
    sizes, rows = np.asarray(groups.sizes), np.asarray(groups.rows)
    R = src.shape[0]
    assert R >= T * 3 + 4 * (BM - 1) and R % BM == 0
    starts = np.cumsum(sizes) - sizes
    local = idx - 2
    held = (local >= 0) & (local < 4)
    assert rows.sum() == held.sum() and (sizes % BM == 0).all()
    for t in range(T):
        for k in range(3):
            if not held[t, k]:
                assert dest[t, k] == R
                continue
            g = local[t, k]
            assert starts[g] <= dest[t, k] < starts[g] + rows[g]
            assert src[dest[t, k]] == t
    used = dest[held]
    assert len(set(used.tolist())) == len(used)
    assert (np.delete(src, used) == T).all()


def test_train_step_reports_the_moe_counters():
    """The step's metrics carry the MoE layers' dispatch counters: rows
    routed to held experts and padded rows summed over the layers, the
    busiest held expert's load over the mean the largest of any layer;
    every token-copy of the reduced model's held experts is counted."""
    from repro.configs import get_config, reduced
    from repro.core import BF16_BASELINE
    from repro.models import init_params
    from repro.optim import init_opt_state
    from repro.train.train_step import TrainConfig, make_train_step

    cfg = reduced(get_config("moonlight-16b-a3b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    step = jax.jit(make_train_step(cfg, BF16_BASELINE, TrainConfig()))
    _, _, m = step(params, init_opt_state(params), batch)
    n_moe = cfg.n_layers - cfg.n_lead
    # The reduced model holds every expert: each layer computes all of
    # its token-copies.
    assert cfg.n_held == cfg.n_experts
    assert float(m["moe_routed_rows"]) == n_moe * 2 * 64 * cfg.top_k
    assert float(m["moe_padded_rows"]) % 1 == 0
    assert float(m["moe_padded_rows"]) < n_moe * cfg.n_held * BM
    assert float(m["moe_load_max_over_mean"]) >= 1.0


@pytest.mark.parametrize("chunk", [128, 1024], ids=["chunks", "one"])
def test_grouped_ffn_matches_each_experts_own_ffn(monkeypatch, chunk):
    """With quantization off, ``grouped_ffn`` is each group's own FFN on
    its routed rows, the activation times each row's gate, in value and
    in every gradient (rows, both weights, gates): the loops over the
    live rows (in 128-row chunks they stop before the tail), and the
    first product computed again in the backward pass, change nothing;
    padding and tail rows stay zero."""
    from repro.core import BF16_BASELINE, grouped
    from repro.core.grouped import grouped_ffn
    from repro.core.linear import N_BWD_EVENTS
    from repro.core.mor import STATS_WIDTH

    rows = [200, 0, 1, 130]
    d, F = 64, 48
    x, groups, _ = _buffer(rows, d, 5)
    x = x.astype(jnp.float32)
    R = x.shape[0]
    monkeypatch.setattr(grouped, "LIVE_CHUNK", chunk)
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    G = len(rows)
    w1 = jax.random.normal(keys[0], (G, d, 2 * F)) * 0.1
    w2 = jax.random.normal(keys[1], (G, F, d)) * 0.1
    live = np.zeros((R,), bool)
    starts = np.cumsum(np.asarray(groups.sizes)) - np.asarray(groups.sizes)
    for g, r in enumerate(rows):
        live[starts[g]:starts[g] + r] = True
    gate = jnp.where(live, jax.random.uniform(keys[2], (R,)), 0.0)
    tok = jnp.zeros((G, N_BWD_EVENTS, STATS_WIDTH), jnp.float32)
    group_of = np.searchsorted(np.cumsum(np.asarray(groups.sizes)),
                               np.arange(R), side="right")

    def act(h):
        a, b = jnp.split(h, 2, axis=-1)
        return jax.nn.silu(a) * b

    def fused(x, w1, w2, gate):
        y, _, _ = grouped_ffn(x, w1, w2, gate, tok, tok, groups,
                              BF16_BASELINE, act)
        return y

    def plain(x, w1, w2, gate):
        g = jnp.asarray(np.minimum(group_of, G - 1))
        h = jnp.einsum("rd,rdf->rf", x, w1[g],
                       precision=jax.lax.Precision.HIGHEST)
        a = act(h) * gate[:, None]
        y = jnp.einsum("rf,rfd->rd", a, w2[g],
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.where(jnp.asarray(group_of < G)[:, None], y, 0.0)

    ct = jax.random.normal(keys[3], (R, d)) * jnp.asarray(live)[:, None]
    args = (x, w1, w2, gate)
    y_f, vjp_f = jax.vjp(jax.jit(fused), *args)
    y_p, vjp_p = jax.vjp(plain, *args)
    np.testing.assert_allclose(y_f, y_p, rtol=1e-4, atol=1e-5)
    assert not np.asarray(y_f)[~live].any()
    for got, want in zip(vjp_f(ct), vjp_p(ct)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
