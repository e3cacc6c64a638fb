"""Serving-engine regression suite: per-slot positions, the paged KV
pool, chunked prefill, admission/termination edges.

The anchor test pins the batched continuous-batching engine
token-for-token against a *dense sequential* reference -- one request
at a time through make_prefill_fn/make_decode_fn with a plain
init_cache, no engine code involved -- across staggered prompt lengths
and mid-stream admissions. The witness test reproduces the pre-paged
engine's shared ``cur = max(slot_pos)`` decode on the same traffic and
shows it diverges, which is why that engine corrupted mixed-length
batches.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import TENSOR_MOR
from repro.models import (
    init_cache,
    init_params,
    make_decode_fn,
    make_prefill_fn,
    make_tokens,
)
from repro.serve import (
    Engine,
    PagedKVPool,
    PromptTooLongError,
    Request,
    ServeConfig,
)


@pytest.fixture(scope="module")
def dense_model():
    cfg = dataclasses.replace(reduced(get_config("gemma-2b")), vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _splice_b1(full, part):
    """Pad a (n_units, 1, P, ...) prefill leaf out to the cache seq."""
    if full.ndim >= 4 and part.ndim == full.ndim and \
            full.shape[2] != part.shape[2]:
        part = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((part.shape[0], 1, full.shape[2], *part.shape[3:]),
                      full.dtype),
            part.astype(full.dtype), 0, axis=2,
        )
    return part.astype(full.dtype)


def _sequential_reference(cfg, params, prompt, n_tokens, max_seq):
    """Greedy-generate one request through the dense B=1 prefill+decode
    path -- the oracle the batched paged engine must reproduce."""
    toks = make_tokens(cfg)
    prefill = jax.jit(make_prefill_fn(cfg, TENSOR_MOR))
    decode = jax.jit(make_decode_fn(cfg, TENSOR_MOR))
    cache = init_cache(cfg, 1, max_seq)
    logits, pc, _ = prefill(
        params, toks, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
    )
    cache = jax.tree.map(_splice_b1, cache, pc)
    out = [int(jnp.argmax(logits[0, -1, : cfg.vocab]))]
    pos = len(prompt)
    while len(out) < n_tokens and pos < max_seq:
        lg, cache, _ = decode(
            params, toks, cache,
            jnp.asarray([[out[-1]]], jnp.int32),
            jnp.asarray([pos], jnp.int32),
        )
        out.append(int(jnp.argmax(lg[0, 0, : cfg.vocab])))
        pos += 1
    return out


# ----------------------------------------------------- headline bugfix --
def test_mixed_length_batched_matches_sequential(dense_model):
    """Staggered prompt lengths + admissions mid-stream: the batched
    paged engine is token-identical to the one-request-at-a-time dense
    reference. (Fails on the pre-paged engine, whose shared
    max(slot_pos) wrote short slots' KV past their true position.)"""
    cfg, params = dense_model
    max_seq, n_tok = 64, 5
    rng = np.random.default_rng(7)
    lengths = [3, 17, 9, 26, 5, 12]  # deliberately staggered
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32)
               for L in lengths]
    refs = [_sequential_reference(cfg, params, p, n_tok, max_seq)
            for p in prompts]

    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=3, max_seq=max_seq, page_size=16,
                             prefill_chunk=8))
    reqs = [Request(i, p, max_tokens=n_tok) for i, p in enumerate(prompts)]
    # Mid-stream admission: 6 requests > 3 slots, plus two submitted
    # only after the engine has started stepping.
    for r in reqs[:4]:
        eng.submit(r)
    steps = 0
    while eng.step() and steps < 200:
        steps += 1
        if steps == 3:
            eng.submit(reqs[4])
        if steps == 5:
            eng.submit(reqs[5])
    for r, ref in zip(reqs, refs):
        assert r.done and r.error is None
        assert r.out == ref, (
            f"req {r.rid} (P={len(r.prompt)}): {r.out} != {ref}"
        )


def test_shared_cur_index_decode_diverges(dense_model):
    """Witness for the headline bug: replaying the old engine's decode
    -- one shared cur = max(slot_pos) for a staggered batch -- produces
    different logits than per-slot positions, because the short slot's
    KV lands past its true position and the zero-filled hole is scored
    (exp(0) = 1 takes real softmax mass). This is what the anchor test
    would have caught on the pre-paged engine."""
    cfg, params = dense_model
    max_seq = 32
    toks = make_tokens(cfg)
    prefill = jax.jit(make_prefill_fn(cfg, TENSOR_MOR))
    decode = jax.jit(make_decode_fn(cfg, TENSOR_MOR))
    rng = np.random.default_rng(3)
    p_short = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    p_long = rng.integers(0, cfg.vocab, 20).astype(np.int32)

    cache = init_cache(cfg, 2, max_seq)
    nxt, pos = [], []
    for b, p in enumerate((p_short, p_long)):
        lg, pc, _ = prefill(
            params, toks, {"tokens": jnp.asarray(p, jnp.int32)[None]}
        )
        cache = jax.tree.map(
            lambda full, part, b=b: jax.lax.dynamic_update_slice_in_dim(
                full, _splice_b1(full, part), b, axis=1
            ),
            cache, pc,
        )
        nxt.append(int(jnp.argmax(lg[0, -1, : cfg.vocab])))
        pos.append(len(p))

    tok = jnp.asarray(nxt, jnp.int32)[:, None]
    lg_vec, _, _ = decode(
        params, toks, cache, tok, jnp.asarray(pos, jnp.int32)
    )
    lg_old, _, _ = decode(
        params, toks, cache, tok, jnp.asarray(max(pos), jnp.int32)
    )
    short = np.asarray(lg_vec[0, 0, : cfg.vocab])
    short_old = np.asarray(lg_old[0, 0, : cfg.vocab])
    assert not np.allclose(short, short_old, atol=1e-3), (
        "shared-max cur_index reproduced the per-slot logits; the "
        "witness lost its teeth"
    )
    # The long slot sits AT the shared position, so it agrees -- up to
    # cross-trace compilation noise: the two decode calls jit-compile
    # different programs (vector vs scalar cur_index), and XLA's float
    # reassociation between them varies with the process hash seed
    # (observed up to ~2e-3 across PYTHONHASHSEED values). 5e-3 clears
    # that noise while staying ~4x below the short slot's real
    # divergence (~2e-2). This is the repo's one known remaining
    # hash-seed sensitivity, carried on the lint allowlist:
    # docs/analysis.md#allowlist.
    np.testing.assert_allclose(
        np.asarray(lg_vec[1, 0, : cfg.vocab]),
        np.asarray(lg_old[1, 0, : cfg.vocab]), atol=5e-3,
    )


def test_chunked_prefill_spans_many_pages(dense_model):
    """A prompt much longer than both the chunk and the page still
    matches the dense reference (chunk padding is overwritten
    position-by-position before it is ever attended)."""
    cfg, params = dense_model
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 41).astype(np.int32)
    ref = _sequential_reference(cfg, params, prompt, 4, 64)
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=2, max_seq=64, page_size=8,
                             prefill_chunk=16))
    req = Request(0, prompt, max_tokens=4)
    eng.submit(req)
    eng.run_to_completion()
    assert req.done and req.out == ref
    assert eng.prefill_chunks == 3  # ceil(41 / 16), never re-prefilled


# ------------------------------------------------ admission/termination --
def test_admission_guard_boundaries(dense_model):
    cfg, params = dense_model
    max_seq = 32
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=1, max_seq=max_seq, prefill_chunk=8))
    ok = Request(0, np.arange(max_seq - 1) % cfg.vocab, max_tokens=2)
    eng.submit(ok)  # P == max_seq - 1: last admissible prompt
    with pytest.raises(PromptTooLongError):
        eng.submit(Request(1, np.arange(max_seq) % cfg.vocab))
    eng.run_to_completion()
    assert ok.done and len(ok.out) == 2 and ok.error is None

    # Truncate mode: clipped, surfaced, still completes.
    eng2 = Engine(cfg, TENSOR_MOR, params,
                  ServeConfig(slots=1, max_seq=max_seq, prefill_chunk=8,
                              on_long_prompt="truncate"))
    long_req = Request(2, np.arange(max_seq + 5) % cfg.vocab, max_tokens=2)
    eng2.submit(long_req)
    assert len(long_req.prompt) == max_seq - 1
    assert long_req.error and "truncated" in long_req.error
    eng2.run_to_completion()
    assert long_req.done and len(long_req.out) == 2


def test_termination_edges(dense_model):
    cfg, params = dense_model
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=2, max_seq=32, prefill_chunk=8))
    # max_tokens=1: the prefill-sampled token is the whole budget -- no
    # decode step may run for this request.
    one = Request(0, np.arange(5, dtype=np.int32), max_tokens=1)
    # Cache-bound: position max_seq - 1 is usable, so the request gets
    # one prefill-sampled token + (max_seq - P) decoded tokens.
    fill = Request(1, np.arange(28, dtype=np.int32), max_tokens=1000)
    eng.submit(one)
    eng.submit(fill)
    eng.run_to_completion()
    assert one.done and len(one.out) == 1
    assert fill.done and len(fill.out) == 32 - 28 + 1


def test_run_to_completion_reports_unfinished(dense_model):
    cfg, params = dense_model
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=1, max_seq=32, prefill_chunk=8))
    a = Request(0, np.arange(4, dtype=np.int32), max_tokens=20)
    b = Request(1, np.arange(4, dtype=np.int32), max_tokens=20)
    eng.submit(a)
    eng.submit(b)
    steps = eng.run_to_completion(max_steps=3)
    assert steps == 3
    assert not a.done and not b.done
    assert a in eng.unfinished and b in eng.unfinished
    assert a.error and "unfinished" in a.error
    # Draining afterwards clears the report.
    eng.run_to_completion()
    assert a.done and b.done and not eng.unfinished


def test_sampling_params_reproducible(dense_model):
    cfg, params = dense_model
    scfg = ServeConfig(slots=1, max_seq=32, prefill_chunk=8)

    def run(seed, temperature):
        eng = Engine(cfg, TENSOR_MOR, params, scfg)
        r = Request(0, np.arange(6, dtype=np.int32), max_tokens=6,
                    temperature=temperature, top_k=8, seed=seed)
        eng.submit(r)
        eng.run_to_completion()
        return r.out

    assert run(1, 1.0) == run(1, 1.0)  # same seed: reproducible
    outs = {tuple(run(s, 1.0)) for s in range(4)}
    assert len(outs) > 1  # temperature actually samples


# ------------------------------------------------------------ the pool --
def test_paged_pool_alloc_release_reuse(dense_model):
    cfg, _ = dense_model
    pool = PagedKVPool(cfg, slots=2, max_seq=64, page_size=16)
    assert pool.n_pages == 2 * 4 and pool.free_pages() == 8
    assert pool.alloc(0, 40)  # 3 pages
    assert pool.free_pages() == 5
    assert (pool.block_table[0, :3] != pool.trash).all()
    assert (pool.block_table[0, 3:] == pool.trash).all()
    assert pool.alloc(0, 40)  # idempotent: already covered
    assert pool.free_pages() == 5
    taken = list(pool.block_table[0, :3])
    pool.release(0)
    assert pool.free_pages() == 8
    assert (pool.block_table[0] == pool.trash).all()
    # Freed pages recycle (FIFO: they rejoin at the back of the list,
    # so the second full-sequence alloc drains down to them).
    assert pool.alloc(1, 64)
    assert pool.alloc(0, 64)
    assert pool.free_pages() == 0
    assert set(taken) <= set(pool.block_table[0])

    with pytest.raises(ValueError, match="MoR-block aligned"):
        PagedKVPool(cfg, slots=1, max_seq=96, page_size=48)
    with pytest.raises(ValueError, match="divide max_seq"):
        PagedKVPool(cfg, slots=1, max_seq=64, page_size=24)


def test_oversubscribed_pool_queues_and_completes(dense_model):
    """pool_pages < slots * pages_per_seq: admission waits on the free
    list instead of failing, and every request still finishes
    correctly."""
    cfg, params = dense_model
    max_seq, n_tok = 64, 4
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32)
               for L in (30, 21, 26)]
    refs = [_sequential_reference(cfg, params, p, n_tok, max_seq)
            for p in prompts]
    # 4 slots x 4 pages/seq would be 16; give the pool 6 (+ trash).
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=4, max_seq=max_seq, page_size=16,
                             prefill_chunk=16, pool_pages=6))
    reqs = [Request(i, p, max_tokens=n_tok)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r, ref in zip(reqs, refs):
        assert r.done and r.out == ref
    assert eng.pool.free_pages() == 6  # everything returned


def test_kv_fp8_paged_engine_smoke(dense_model):
    cfg, params = dense_model
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=2, max_seq=32, page_size=8,
                             prefill_chunk=8, kv_fp8=True))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, 6 + 7 * i).astype(
        np.int32), max_tokens=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r in reqs:
        assert r.done and len(r.out) == 4
        assert all(0 <= t < cfg.vocab for t in r.out)


def _run_engine(cfg, params, prompts, n_tok, **scfg_kw):
    kw = dict(slots=2, max_seq=64, page_size=8, prefill_chunk=8)
    kw.update(scfg_kw)
    eng = Engine(cfg, TENSOR_MOR, params, ServeConfig(**kw))
    reqs = [Request(i, p, max_tokens=n_tok) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r in reqs:
        assert r.done and r.error is None, (r.rid, r.error)
    return [r.out for r in reqs], eng


def test_kv_mor_paged_engine_matches_bf16_engine(dense_model):
    """Decode served from MoR-packed KV pages (uint8 payload + tag +
    scale lanes, gather/scatter moving packed bytes) is token-for-token
    against the bf16-cache engine on staggered mixed-length traffic."""
    cfg, params = dense_model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32)
               for L in (3, 17, 9, 26)]
    ref, eng_b = _run_engine(cfg, params, prompts, 6)
    out, eng_m = _run_engine(cfg, params, prompts, 6, kv_mor=True)
    assert out == ref
    # The MoR pool's per-position gather/scatter bytes beat bf16's.
    assert eng_m.pool.bytes_per_token() < eng_b.pool.bytes_per_token()
    assert eng_m.pool.free_pages() == eng_b.pool.free_pages()


def test_kv_mor_cold_sealing_recompresses_and_stays_exact(dense_model):
    """With the cold-page policy on, pages behind the write frontier
    are sub4-recompressed mid-stream (visible as NVFP4 tags in the
    cache census) and generation still matches the bf16 engine."""
    cfg, params = dense_model
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    ref, _ = _run_engine(cfg, params, [prompt], 24, slots=1)

    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=1, max_seq=64, page_size=8,
                             prefill_chunk=8, kv_mor=True, kv_mor_cold=2))
    r = Request(0, prompt, max_tokens=24)
    eng.submit(r)
    saw_cold = 0.0
    steps = 0
    while eng.step() and steps < 200:
        steps += 1
        st = eng.kv_cache_stats()
        if st.get("written"):
            saw_cold = max(saw_cold, st["frac_nvfp4"])
    assert r.done and r.out == ref[0]
    assert saw_cold > 0.5, "cold sealing never recompressed a page"
    assert not eng._sealed  # cleared when the slot finished
    assert eng.pool.free_pages() == eng.pool.n_pages


def test_kv_mor_config_validation(dense_model):
    cfg, params = dense_model
    with pytest.raises(ValueError, match="mutually exclusive"):
        Engine(cfg, TENSOR_MOR, params,
               ServeConfig(slots=1, max_seq=32, page_size=8,
                           kv_fp8=True, kv_mor=True))
    with pytest.raises(ValueError, match="kv_mor_cold"):
        Engine(cfg, TENSOR_MOR, params,
               ServeConfig(slots=1, max_seq=32, page_size=8,
                           kv_mor_cold=4))


# ------------------------------------------- recurrent-state fallback --
def test_hybrid_family_fallback_matches_sequential():
    """Hymba (attention + SSM state) can't chunk its prefill; the
    one-shot fallback must still match the dense sequential reference
    under mixed prompt lengths."""
    cfg = dataclasses.replace(reduced(get_config("hymba-1.5b")), vocab=128)
    params = init_params(cfg, jax.random.PRNGKey(1))
    max_seq, n_tok = 32, 3
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32)
               for L in (4, 13)]
    refs = [_sequential_reference(cfg, params, p, n_tok, max_seq)
            for p in prompts]
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=2, max_seq=max_seq, page_size=8,
                             prefill_chunk=8))
    assert not eng.chunked_prefill  # state leaves force the fallback
    reqs = [Request(i, p, max_tokens=n_tok)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r, ref in zip(reqs, refs):
        assert r.done and r.out == ref


# -------------------------------------------- structural contract --
def test_decode_step_contract(dense_model):
    """This engine's jitted decode step satisfies the registry's
    ``engine_decode_step`` contract (repro.analysis.contracts): no
    host round-trips inside the step, the KV pool buffers donated, no
    f64, and the quantized weights' payload lanes consumed only by
    sanctioned decode sites -- the same rules CI's lint job and the
    bench sweep evaluate on the registry's own probe engine."""
    from repro.analysis import engine_decode_report
    from repro.core import MoRPolicy

    cfg, params = dense_model
    eng = Engine(
        cfg, TENSOR_MOR, params,
        ServeConfig(slots=4, max_seq=64, page_size=16, kv_mor=True),
        quantize=MoRPolicy(recipe="sub3", backend="interpret"),
        quantize_min_size=0,
    )
    report = engine_decode_report(eng)
    assert report.ok, report.render()
    assert report.counters["donated_args"] >= 1
    assert report.counters["tainted_lanes"] > 0  # QTensor lanes seeded


# ---------------------------------------- unsatisfiable admission --
def test_unsatisfiable_reservation_rejected_not_starved(dense_model):
    """A request whose worst-case reservation exceeds the *total* pool
    can never be admitted -- no amount of eviction frees enough pages.
    Pre-fix, it sat at the queue head forever and starved everything
    behind it; now it is rejected with the condition surfaced and the
    queue behind it drains normally."""
    cfg, params = dense_model
    # 3-page pool (24 positions), oversubscribed vs max_seq = 64.
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=2, max_seq=64, page_size=8,
                             prefill_chunk=8, pool_pages=3))
    hog = Request(0, np.arange(16, dtype=np.int32) % cfg.vocab,
                  max_tokens=30)   # horizon 45 -> 6 pages > 3
    small = Request(1, np.arange(5, dtype=np.int32) % cfg.vocab,
                    max_tokens=4)  # horizon 8 -> 1 page
    eng.submit(hog)
    eng.submit(small)
    eng.run_to_completion()
    assert hog.done and not hog.out
    assert hog.error and "rejected at admission" in hog.error
    assert "6 pages" in hog.error and "3 total" in hog.error
    assert hog in eng.rejected
    assert small.done and small.error is None and len(small.out) == 4


def test_exact_fit_reservation_admitted(dense_model):
    """Boundary: a reservation of exactly the pool's total page count
    is satisfiable (once the pool drains) and must not be rejected."""
    cfg, params = dense_model
    eng = Engine(cfg, TENSOR_MOR, params,
                 ServeConfig(slots=2, max_seq=64, page_size=8,
                             prefill_chunk=8, pool_pages=3))
    fit = Request(0, np.arange(16, dtype=np.int32) % cfg.vocab,
                  max_tokens=9)    # horizon 24 -> exactly 3 pages
    eng.submit(fit)
    eng.run_to_completion()
    assert fit.done and fit.error is None and len(fit.out) == 9
    assert not eng.rejected


def test_engine_refuses_latent_attention():
    """Moonlight's latent attention and leading dense layer train and
    prefill, but the engine does not serve them: it says so at once."""
    cfg = reduced(get_config("moonlight-16b-a3b"))
    assert cfg.mla and cfg.n_lead
    with pytest.raises(NotImplementedError, match="latent attention"):
        Engine(cfg, TENSOR_MOR, params=None)
