"""Continuous-batching serving engine over a paged KV pool.

Every slot advances at its *own* position: the jitted step takes a
vector ``cur_index`` (one entry per slot), so mixed-prompt-length
batches read and write exactly their true cache rows. (The engine this
replaces shared one ``max(slot_pos)`` across the batch, which wrote
short slots' KV past their real position and left zero-filled holes the
decode mask treated as valid keys -- zero-score keys take real softmax
mass, so mixed-length batches produced wrong tokens.)

The loop is a real scheduler (docs/serving.md):

- KV lives in a ``PagedKVPool`` (block table + free list, page size
  aligned to the MoR ``Partition`` block grid); admission reserves a
  request's worst-case page span, eviction recycles it.
- Prefill is *chunked* and interleaved with decode: each engine step
  runs one fixed-size prompt chunk per prefilling slot (compiled once
  per chunk shape, never re-prefilling the whole sequence) plus one
  batched decode step over the decoding slots. Families with recurrent
  state (Hymba SSM, xLSTM cells) prefill in one shot at admission --
  their recurrence can't resume from a page -- and then join the same
  batched decode.
- Per-request ``max_tokens`` and sampling params (greedy by default;
  ``temperature`` / ``top_k`` / ``seed`` for stochastic decode).
- With quantized weights, decode GEMMs are (slots, K, N) with
  slots << 128: the engine pins the skinny-M lane in the ``GemmTile``
  autotune table so activations pack at the 16-row sublane tile, not
  a padded 128.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import MoRDotPolicy, MoRPolicy
from repro.models import make_decode_fn, make_prefill_fn, make_tokens
from repro.models.attention import quantize_kv, quantize_kv_mor

from .paged import PagedKVPool
from .quantized import quantize_params

__all__ = ["Request", "ServeConfig", "Engine", "PromptTooLongError"]


class PromptTooLongError(ValueError):
    """Prompt has no room in the cache (P >= max_seq): there would be
    nowhere to write even the first generated token's KV."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (P,) int32
    max_tokens: int = 16
    # Sampling: temperature <= 0 is greedy argmax; otherwise softmax
    # sampling at the given temperature, optionally top_k-truncated,
    # seeded per request (host-side RNG -> reproducible per rid/seed).
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Surfaced condition: explicit truncation at submit, or
    # "unfinished" when run_to_completion exhausts max_steps.
    error: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    slots: int = 4
    max_seq: int = 512
    # Paged pool: page_size must divide max_seq and tile the 128-row
    # MoR Partition block; pool_pages < slots * (max_seq / page_size)
    # oversubscribes (requests then queue on the free list).
    page_size: Optional[int] = None
    pool_pages: Optional[int] = None
    # Chunked prefill: tokens per chunk (must divide max_seq). One
    # chunk per prefilling slot per engine step.
    prefill_chunk: int = 32
    kv_fp8: bool = False
    # MoR cache tier (docs/numerics.md): per-(position, head) tag-select
    # E4M3/E5M2 payloads + GAM scales instead of the monolithic fp8
    # cast. Mutually exclusive with kv_fp8.
    kv_mor: bool = False
    # Cold-page policy: with kv_mor, a page is sub4-recompressed (E2M1
    # nibbles + micro scales, 0.5625 logical B/elt) once a slot's write
    # frontier is at least this many positions past the page's end.
    # None disables sealing. Requires head_dim % 16 == 0.
    kv_mor_cold: Optional[int] = None
    # P >= max_seq at submit: 'reject' raises PromptTooLongError,
    # 'truncate' keeps the first max_seq - 1 tokens and records the
    # truncation on request.error.
    on_long_prompt: str = "reject"
    # Robustness tier (docs/robustness.md): also run the pool's
    # KV-page guard over each decoding slot every decode step --
    # host-side finiteness checks of the float/scale lanes, so
    # corrupted pages quarantine the owning slot *before* the poison
    # reaches its logits. Off by default: the nonfinite-logits
    # quarantine below is free, this sweep fetches page lanes.
    kv_guard: bool = False


class Engine:
    def __init__(self, cfg: ArchConfig, policy: MoRDotPolicy, params,
                 scfg: ServeConfig = ServeConfig(),
                 quantize: Optional[MoRPolicy] = None,
                 quantize_min_size: int = 1 << 16,
                 mesh=None):
        """``quantize``: optional ahead-of-time MoR storage decision --
        weight leaves become sub-tensor QTensors (per-block E4M3 / E5M2
        / BF16 payloads) and every prefill/decode matmul against them
        runs through the mixed-representation block GEMM kernel; the
        engine also registers the skinny-M decode tile for each
        quantized weight's block grid (kernels.ops.register_decode_tiles).

        ``mesh``: optional jax Mesh for tensor-parallel serving. Params
        (dense *and* QTensor leaves -- payloads, tags and scales shard
        together on the block grid, see ``sharding.rules
        .quantized_param_specs``) are placed per the Megatron TP rules,
        so sharded serving never materializes a dequantized weight
        copy. Example::

            mesh = make_local_mesh(data=1, model=4)
            eng = Engine(cfg, policy, params,
                         quantize=MoRPolicy(recipe="sub3"), mesh=mesh)
        """
        if cfg.family in ("audio", "vlm"):
            raise NotImplementedError(
                f"family {cfg.family!r} needs a modality frontend the "
                "engine does not drive (frames/patches inputs)"
            )
        if cfg.mla or cfg.n_lead:
            raise NotImplementedError(
                f"{cfg.name}: the engine does not serve latent attention "
                "or leading dense layers (training and prefill only)"
            )
        if scfg.max_seq % scfg.prefill_chunk:
            raise ValueError(
                f"prefill_chunk {scfg.prefill_chunk} must divide "
                f"max_seq {scfg.max_seq}"
            )
        if scfg.kv_fp8 and scfg.kv_mor:
            raise ValueError("kv_fp8 and kv_mor are mutually exclusive")
        if scfg.kv_mor_cold is not None and not scfg.kv_mor:
            raise ValueError("kv_mor_cold needs kv_mor=True")
        self.cfg = cfg
        self.scfg = scfg
        self.qstats = None
        self.decode_row_block = None
        if quantize is not None:
            params, self.qstats = quantize_params(
                params, quantize, min_size=quantize_min_size
            )
            from repro.kernels import ops as kops

            # Decode activations are (slots, d): pin the skinny-M lane
            # so the GEMM autotune never pads the slots axis to 128.
            self.decode_tile_grids = kops.register_decode_tiles(
                params, scfg.slots
            )
            self.decode_row_block = kops.decode_row_block(scfg.slots)
        if mesh is not None:
            from repro.sharding import rules as _rules

            specs = _rules.quantized_param_specs(cfg, params, mesh)
            params = jax.device_put(
                params, _rules.named_shardings(mesh, specs)
            )
        self.params = params
        self.tokens = make_tokens(cfg)
        self.pool = PagedKVPool(
            cfg, scfg.slots, scfg.max_seq, page_size=scfg.page_size,
            kv_fp8=scfg.kv_fp8, n_pages=scfg.pool_pages,
            kv_mor=scfg.kv_mor,
        )
        self._sealed = set()  # (slot, page_index) sub4-recompressed
        # Chunked prefill needs every cache leaf positional (pageable);
        # recurrent-state families prefill in one shot at admission.
        self.chunked_prefill = self.pool.all_paged and self.pool.has_paged
        self._prefill = jax.jit(make_prefill_fn(cfg, policy))
        decode = make_decode_fn(cfg, policy)
        pool = self.pool

        def step_fn(params, tokens, ptree, bt, toks, cur):
            cache = pool.gather(ptree, bt)
            logits, new_cache, _ = decode(params, tokens, cache, toks, cur)
            S = toks.shape[1]
            positions = (
                cur[:, None] - (S - 1) + jnp.arange(S, dtype=jnp.int32)[None]
            )
            return logits, pool.scatter(ptree, new_cache, bt, positions)

        # One compiled variant per token-block shape: (slots, 1) decode
        # and (1, prefill_chunk) chunked prefill.
        self._step_fn = jax.jit(step_fn, donate_argnums=(2,))

        n = scfg.slots
        self.slot_req: List[Optional[Request]] = [None] * n
        self.slot_pos = np.zeros(n, np.int32)   # next cache write position
        self.slot_next = np.zeros(n, np.int32)  # next input token id
        self.slot_state = ["idle"] * n          # idle | prefill | decode
        self.slot_filled = np.zeros(n, np.int32)  # prompt tokens consumed
        self.queue: Deque[Request] = collections.deque()
        self.unfinished: List[Request] = []
        # Graceful degradation (docs/robustness.md): requests finished
        # early because their slot produced nonfinite logits or failed
        # the KV-page guard, and requests rejected at admission because
        # their worst-case page reservation can never be satisfied.
        self.quarantined: List[Request] = []
        self.rejected: List[Request] = []
        self.steps = 0
        self.decode_steps = 0
        self.prefill_chunks = 0

    # ------------------------------------------------------------- admin --
    def submit(self, req: Request):
        """Queue a request. Prompts with P >= max_seq cannot fit (the
        first generated token's KV is written at position P): per
        ``ServeConfig.on_long_prompt`` they are rejected here or
        explicitly truncated with the event surfaced on ``req.error``."""
        P = len(req.prompt)
        if P < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        limit = self.scfg.max_seq - 1
        if P > limit:
            if self.scfg.on_long_prompt == "truncate":
                req.prompt = np.asarray(req.prompt)[:limit]
                req.error = (
                    f"prompt truncated {P} -> {limit} tokens "
                    f"(max_seq={self.scfg.max_seq})"
                )
            else:
                raise PromptTooLongError(
                    f"request {req.rid}: prompt of {P} tokens exceeds "
                    f"the max_seq - 1 = {limit} limit (set "
                    "on_long_prompt='truncate' to clip instead)"
                )
        self.queue.append(req)

    def _horizon(self, req: Request) -> int:
        """Highest cache position + 1 this request can touch: chunked
        prefill writes (padded) whole chunks; decode writes the
        (max_tokens - 1) sampled continuations after the prompt."""
        P = len(req.prompt)
        C = self.scfg.prefill_chunk
        span = -(-P // C) * C if self.chunked_prefill else P
        return min(max(span, P + req.max_tokens - 1), self.scfg.max_seq)

    def _admit(self):
        # Single scan over the slot list per engine step; pages are
        # reserved all-or-nothing so admitted requests never starve
        # mid-flight when the pool is oversubscribed.
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        for slot in free:
            req = None
            while self.queue and req is None:
                head = self.queue[0]
                need = self.pool.pages_for(self._horizon(head))
                if need > self.pool.n_pages:
                    # No amount of eviction can ever free enough pages:
                    # waiting on this head would starve the whole queue
                    # behind an unsatisfiable reservation. Reject it
                    # with the condition surfaced, like submit-side
                    # truncation.
                    self.queue.popleft()
                    head.error = (
                        f"rejected at admission: worst-case reservation "
                        f"of {need} pages exceeds the pool's "
                        f"{self.pool.n_pages} total pages (page_size="
                        f"{self.pool.page_size}); shrink the prompt or "
                        "max_tokens, or grow pool_pages"
                    )
                    head.done = True
                    self.rejected.append(head)
                    continue
                req = head
            if req is None:
                return
            if not self.pool.alloc(slot, self._horizon(req)):
                return  # wait for evictions to refill the free list
            self.queue.popleft()
            self.slot_req[slot] = req
            self.slot_filled[slot] = 0
            if self.chunked_prefill:
                self.slot_state[slot] = "prefill"
            else:
                self._full_prefill(slot, req)

    # ----------------------------------------------------------- prefill --
    def _full_prefill(self, slot: int, req: Request):
        """One-shot prefill for recurrent-state families: the cache the
        model emits is spliced into this slot's pages / state row."""
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        logits, pcache, _ = self._prefill(
            self.params, self.tokens, {"tokens": prompt}
        )
        by_key: Dict[str, jnp.ndarray] = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(pcache)
        for path, leaf in flat:
            key = "/".join(
                str(getattr(k, "key", getattr(k, "idx", k))) for k in path
            )
            by_key[key] = leaf
        if self.scfg.kv_fp8:
            for key in list(by_key):
                last = key.rsplit("/", 1)[-1]
                if last in ("k", "v"):
                    pay, sc = quantize_kv(by_key[key])
                    by_key[key] = pay
                    by_key[key + "_scale"] = sc
        elif self.scfg.kv_mor:
            for key in list(by_key):
                last = key.rsplit("/", 1)[-1]
                if last in ("k", "v"):
                    pay, tags, sc = quantize_kv_mor(by_key[key])
                    by_key[key] = pay
                    by_key[key + "_tags"] = tags
                    by_key[key + "_scale"] = sc
        self.pool.splice(slot, by_key, len(req.prompt))
        self._start_decode(slot, req, len(req.prompt),
                           np.asarray(logits[0, -1], np.float32))

    def _prefill_chunk_step(self, slot: int, req: Request):
        """Advance one prompt chunk for a prefilling slot (B=1 call
        against this slot's page-table row)."""
        C = self.scfg.prefill_chunk
        start = int(self.slot_filled[slot])
        P = len(req.prompt)
        chunk = np.zeros(C, np.int32)
        real = min(C, P - start)
        chunk[:real] = np.asarray(req.prompt)[start:start + real]
        bt = self.pool.table_rows([slot])
        logits, tree = self._step_fn(
            self.params, self.tokens, self.pool.tree, bt,
            jnp.asarray(chunk[None]), jnp.asarray([start + C - 1], jnp.int32),
        )
        self.pool.update(tree)
        self.prefill_chunks += 1
        self.slot_filled[slot] = start + real
        if start + real >= P:
            # The chunk's logits at the last *real* prompt token seed
            # generation (padded tail positions are written but masked
            # until real tokens overwrite them).
            row = np.asarray(logits[0, real - 1], np.float32)
            self._start_decode(slot, req, P, row)

    def _start_decode(self, slot: int, req: Request, P: int,
                      logits_row: np.ndarray):
        tok = self._sample(req, logits_row)
        req.out.append(tok)
        self.slot_pos[slot] = P
        self.slot_next[slot] = tok
        self.slot_state[slot] = "decode"
        # The prefill-sampled token counts toward max_tokens: a
        # max_tokens=1 request is complete right here, before any
        # decode step runs.
        if len(req.out) >= req.max_tokens:
            self._finish(slot)

    # ------------------------------------------------------------ decode --
    def _decode_batch(self, dec: List[int]):
        n = self.scfg.slots
        mask = np.zeros(n, bool)
        mask[dec] = True
        # Non-decoding slots ride along in the batched call with their
        # rows pointed at the trash page: their writes can't touch real
        # pages and their (garbage) logits are discarded.
        bt = np.where(
            mask[:, None], self.pool.block_table, self.pool.trash
        ).astype(np.int32)
        toks = np.where(mask, self.slot_next, 0).astype(np.int32)[:, None]
        cur = np.where(mask, self.slot_pos, 0).astype(np.int32)
        logits, tree = self._step_fn(
            self.params, self.tokens, self.pool.tree, jnp.asarray(bt),
            jnp.asarray(toks), jnp.asarray(cur),
        )
        self.pool.update(tree)
        self.decode_steps += 1
        rows = np.asarray(logits[:, 0], np.float32)
        for i in dec:
            r = self.slot_req[i]
            # Slot quarantine (docs/robustness.md): the logits row is
            # already on the host for sampling, so the finiteness check
            # is free; a poisoned slot (corrupted KV page, overflowed
            # cache lane) finishes early with the condition surfaced
            # instead of sampling garbage forever. Decode rows are
            # slot-independent (each attends only over its own pages),
            # so every other slot's tokens are unaffected. The optional
            # page sweep runs *first*: when both would fire, the error
            # should name the corrupted page (the root cause), not the
            # nonfinite logits downstream of it -- and it also catches
            # corruption in reserved-but-not-yet-attended pages the
            # logits cannot see yet.
            if self.scfg.kv_guard:
                bad = self.pool.guard_check(i)
                if bad is not None:
                    self._quarantine(i, bad)
                    continue
            if not np.isfinite(rows[i][: self.cfg.vocab]).all():
                self._quarantine(
                    i,
                    f"nonfinite logits at position "
                    f"{int(self.slot_pos[i])}",
                )
                continue
            tok = self._sample(r, rows[i])
            r.out.append(tok)
            self.slot_pos[i] += 1
            self.slot_next[i] = tok
            # Done when the budget is spent or the *next* write
            # position would overflow the cache (position max_seq - 1
            # is still usable -- stopping at slot_pos + 1 >= max_seq
            # would waste it).
            if len(r.out) >= r.max_tokens or \
                    self.slot_pos[i] >= self.scfg.max_seq:
                self._finish(i)

    def _sample(self, req: Request, row: np.ndarray) -> int:
        V = self.cfg.vocab
        row = row[:V]
        if req.temperature <= 0.0:
            return int(row.argmax())
        rng = getattr(req, "_rng", None)
        if rng is None:
            rng = np.random.default_rng((req.seed, req.rid))
            req._rng = rng
        z = row.astype(np.float64) / req.temperature
        if req.top_k and req.top_k < V:
            kth = np.partition(z, -req.top_k)[-req.top_k]
            z = np.where(z >= kth, z, -np.inf)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(V, p=p))

    def _quarantine(self, slot: int, reason: str):
        """Finish a poisoned slot early: surface the condition on
        ``req.error``, keep whatever tokens were already emitted, and
        release the pages back to the free list via the normal finish
        path (so queued requests can take the slot next tick)."""
        req = self.slot_req[slot]
        note = f"quarantined: {reason}"
        req.error = f"{req.error}; {note}" if req.error else note
        self.quarantined.append(req)
        self._finish(slot)

    def _finish(self, slot: int):
        self.slot_req[slot].done = True
        self.slot_req[slot] = None
        self.slot_state[slot] = "idle"
        self.slot_pos[slot] = 0
        self.slot_next[slot] = 0
        self.slot_filled[slot] = 0
        self.pool.release(slot)
        self._sealed = {(s, j) for s, j in self._sealed if s != slot}

    # ---------------------------------------------------- MoR cold tier --
    def _seal_cold_pages(self):
        """Sub4-recompress pages a decode slot's write frontier has
        left at least ``kv_mor_cold`` positions behind. Sealed pages
        are never written again while owned (positions only grow), so
        the one-way fp8 -> NVFP4 recompression is safe; the set resets
        when the slot's pages are released."""
        lag = self.scfg.kv_mor_cold
        ps = self.pool.page_size
        cold: List[int] = []
        for i in range(self.scfg.slots):
            if self.slot_state[i] != "decode":
                continue
            frontier = int(self.slot_pos[i])
            for j, page in enumerate(self.pool.block_table[i]):
                if page == self.pool.trash or (i, j) in self._sealed:
                    continue
                if (j + 1) * ps + lag <= frontier:
                    cold.append(int(page))
                    self._sealed.add((i, j))
        if cold:
            self.pool.recompress_pages(cold)

    def kv_cache_stats(self):
        """Tag census / bytes-per-element of the live cache (kv_mor)."""
        return self.pool.kv_cache_stats()

    # -------------------------------------------------------------- step --
    def step(self) -> bool:
        """One scheduler tick: admit, one prefill chunk per prefilling
        slot, one batched decode step over decoding slots. Returns
        False once no request is queued or in flight."""
        self._admit()
        worked = False
        for i in range(self.scfg.slots):
            if self.slot_state[i] == "prefill":
                self._prefill_chunk_step(i, self.slot_req[i])
                worked = True
        dec = [i for i in range(self.scfg.slots)
               if self.slot_state[i] == "decode"]
        if dec:
            self._decode_batch(dec)
            worked = True
        if worked and self.scfg.kv_mor_cold is not None:
            self._seal_cold_pages()
        if worked:
            self.steps += 1
        return worked or bool(self.queue)

    def run_to_completion(self, max_steps: int = 1024) -> int:
        """Drive steps until drained (or ``max_steps``). Requests still
        queued or in flight at exhaustion are reported: each gets
        ``error`` set and they are collected on ``self.unfinished``
        (with ``done`` left False) instead of silently dropped."""
        steps = 0
        while (self.queue or any(self.slot_req)) and steps < max_steps:
            self.step()
            steps += 1
        self.unfinished = list(self.queue) + [
            r for r in self.slot_req if r is not None
        ]
        for r in self.unfinished:
            note = f"unfinished after {max_steps} engine steps"
            r.error = f"{r.error}; {note}" if r.error else note
        return steps
