"""Serving engine: continuous batching over a PAGED shared KV cache.

One engine = one (architecture, mesh) "runtime instance" in Hardless terms:
cold start is jit compilation + weight materialization; after that the
engine serves events (batches of generation requests) from the node manager.

Two cache layouts share the same scheduler surface:

* **paged** (default): global-attention K/V lives in a fixed pool of
  ``page_size``-token pages (`serve/paging.py` owns the free list and the
  per-request block tables); requests admit the moment a slot AND pages
  are free, a finished request's pages free immediately, and pool
  exhaustion mid-decode preempts the youngest request (free its pages,
  requeue, re-prefill prompt+output later — recompute preemption).  Long
  prompts optionally prefill in ``prefill_chunk``-token pieces interleaved
  with the decode steps of active slots, so admission never stalls decode.
* **dense** (``page_size=0``): the seed's per-slot cache — every slot
  reserves ``max_len`` positions.  Kept as the differential reference the
  paged engine is proven token-exact against (`tests/test_paged_engine.py`)
  and as the equal-KV-budget baseline of `benchmarks/bench_serving.py`.

Sampling keys fold (seed, req_id, attempt, position) so an at-least-once
re-dispatch (new attempt) draws fresh randomness while a preemption resume
(same attempt) reproduces the stream exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.tokenizer import EOS
from repro.models import model as M
from repro.obs import TRACER
from repro.serve.paging import BlockAllocator, pages_for

DEFAULT_PAGE_SIZE = 16

# slot lifecycle (paged scheduler)
IDLE, PREFILL, DECODE = "idle", "prefill", "decode"


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    req_id: int = 0
    # at-least-once delivery attempt (folded into the sampling key so a
    # re-dispatched event does not replay the previous attempt's draws)
    attempt: int = 0
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: Optional[float] = None    # wall clock, for TTFT accounting
    t_first: Optional[float] = None


def _slot_batch_axis(path) -> int:
    """Cache leaves under blocks/ are (n_periods, B, ...); others (B, ...)."""
    return 1 if any(getattr(p, "key", None) == "blocks" for p in path) else 0


def write_slot(cache, slot_cache, idx: int):
    """Insert a B=1 cache into slot ``idx`` of the engine cache."""
    flat_c, treedef = jax.tree_util.tree_flatten_with_path(cache)
    flat_s = [l for _, l in jax.tree_util.tree_flatten_with_path(slot_cache)[0]]
    out = []
    for (path, big), small in zip(flat_c, flat_s):
        ax = _slot_batch_axis(path)
        out.append(jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), idx, axis=ax))
    return jax.tree.unflatten(treedef, out)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 4,
                 max_len: int = 256, impl: Optional[str] = None,
                 greedy: bool = True,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 kv_pool_tokens: Optional[int] = None,
                 prefill_chunk: int = 0,
                 sample_seed: int = 0):
        """``page_size=0`` selects the dense per-slot cache (the
        differential reference); otherwise global-attention K/V is paged.
        ``kv_pool_tokens`` sizes the shared pool (default: max_slots *
        max_len — capacity-equivalent to the dense layout); smaller pools
        oversubscribe and rely on preemption.  ``prefill_chunk`` > 0
        prefills prompts longer than the chunk in chunk-sized pieces
        interleaved with decode (supported block patterns only — see
        ``models.model.chunked_prefill_supported``)."""
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.impl = impl
        self.greedy = greedy
        self.paged = page_size > 0
        self.prefill_chunk = int(prefill_chunk)
        self.sample_seed = sample_seed

        self.pos = np.zeros((max_slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * max_slots
        self.last_token = np.zeros((max_slots,), np.int32)
        self.waiting: Deque[Request] = deque()
        self.n_prefills = 0
        self.n_prefill_chunks = 0
        self.n_decode_steps = 0
        self.n_evictions = 0

        if self.paged:
            self.page = int(page_size)
            self.pages_per_seq = pages_for(max_len, self.page)
            pool = (pages_for(kv_pool_tokens, self.page) if kv_pool_tokens
                    else max_slots * self.pages_per_seq)
            self.num_pages = pool + 1           # + the reserved scratch page
            self.allocator = BlockAllocator(self.num_pages, self.page,
                                            reserved=(0,))
            self.cache = M.init_paged_cache(cfg, max_slots, max_len,
                                            self.num_pages, self.page)
            self._paged_flags = M.paged_leaf_flags(cfg, self.cache)
            self._chunk_ok = (self.prefill_chunk > 0
                              and M.chunked_prefill_supported(cfg))
            self._state = [IDLE] * max_slots
            self._seq: Dict[int, List[int]] = {}      # slot -> prefill seq
            self._progress: Dict[int, int] = {}       # slot -> prefilled upto
            self._admit_order: List[int] = []         # eviction priority
            # slot indices are TRACED scalars (dynamic_slice starts), so
            # these compile once per shape, never once per slot
            # the cache is DONATED through every step (callers always
            # reassign self.cache from the result): XLA updates pool
            # buffers in place instead of copying the whole pool per call
            self._decode_paged = jax.jit(self._decode_paged_impl,
                                         donate_argnums=(1,))
            # chunk steps and prefill installs each run as ONE dispatch:
            # view/compute/merge are traced together so XLA sees the
            # whole slot update (three dispatches per chunk measurably
            # dominated the paged engine's prefill cost)
            self._chunk_batch = jax.jit(self._chunk_batch_impl,
                                        donate_argnums=(1,))
            self._prefill_install = jax.jit(self._prefill_install_impl,
                                            donate_argnums=(1,))
        else:
            self.cache = M.init_cache(cfg, max_slots, max_len)

        self._decode = jax.jit(functools.partial(M.decode_step, cfg,
                                                 impl=impl),
                               donate_argnums=(1,))
        self._prefill = jax.jit(
            functools.partial(M.prefill, cfg, cache_len=max_len, impl=impl),
            static_argnames=())
        self._write_slot = jax.jit(write_slot, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # sampling: key folds (seed, req_id, attempt, position) — fresh draws
    # per attempt (at-least-once), reproducible draws per position
    # (preemption resume replays the identical stream)
    # ------------------------------------------------------------------
    def _sample_token(self, logits_row: jax.Array, req: Request) -> int:
        if self.greedy:
            return int(jnp.argmax(logits_row))
        key = jax.random.PRNGKey(self.sample_seed)
        for v in (req.req_id, req.attempt,
                  len(req.prompt) + len(req.output)):
            key = jax.random.fold_in(key, v)
        return int(jax.random.categorical(key, logits_row))

    def _record_token(self, slot: int, req: Request, tok: int) -> None:
        req.output.append(tok)
        if req.t_first is None:
            req.t_first = time.perf_counter()
        self.last_token[slot] = tok

    # ------------------------------------------------------------------
    # paged-cache tree surgery (leaf order fixed by tree_flatten_with_path;
    # self._paged_flags marks pooled leaves)
    # ------------------------------------------------------------------
    def _slot_view_impl(self, cache, slot: int):
        """B=1 view of ``slot``: per-slot leaves sliced, pools whole."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        out = []
        for (path, leaf), paged in zip(flat, self._paged_flags):
            out.append(leaf if paged else jax.lax.dynamic_slice_in_dim(
                leaf, slot, 1, axis=_slot_batch_axis(path)))
        return treedef.unflatten(out)

    def _slot_merge_impl(self, cache, slot_cache, slot: int):
        """Inverse of the view: pools replaced, per-slot leaves written."""
        flat_c, treedef = jax.tree_util.tree_flatten_with_path(cache)
        flat_s = [l for _, l in
                  jax.tree_util.tree_flatten_with_path(slot_cache)[0]]
        out = []
        for (path, big), small, paged in zip(flat_c, flat_s,
                                             self._paged_flags):
            out.append(small if paged else jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), slot,
                axis=_slot_batch_axis(path)))
        return treedef.unflatten(out)

    def _install_impl(self, cache, slot_cache, pages, slot: int):
        """Install a dense B=1 prefill cache: global-attention K/V rows
        scatter into this sequence's pool pages, everything else writes
        into the slot (identical to the dense engine's write_slot)."""
        flat_c, treedef = jax.tree_util.tree_flatten_with_path(cache)
        flat_s = [l for _, l in
                  jax.tree_util.tree_flatten_with_path(slot_cache)[0]]
        npages = pages.shape[0]
        out = []
        for (path, big), small, paged in zip(flat_c, flat_s,
                                             self._paged_flags):
            ax = _slot_batch_axis(path)
            if not paged:
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), slot, axis=ax))
                continue
            page = big.shape[-3]
            span = npages * page
            if ax == 1:     # stacked: big (n_p, NB, page, KV, hd)
                seg = small[:, 0]                        # (n_p, L, KV, hd)
                if span > seg.shape[1]:
                    seg = jnp.pad(seg, ((0, 0), (0, span - seg.shape[1]),
                                        (0, 0), (0, 0)))
                seg = seg[:, :span].reshape(seg.shape[0], npages, page,
                                            *seg.shape[2:])
                out.append(big.at[:, pages].set(seg.astype(big.dtype)))
            else:           # remainder layer: big (NB, page, KV, hd)
                seg = small[0]
                if span > seg.shape[0]:
                    seg = jnp.pad(seg, ((0, span - seg.shape[0]),
                                        (0, 0), (0, 0)))
                seg = seg[:span].reshape(npages, page, *seg.shape[1:])
                out.append(big.at[pages].set(seg.astype(big.dtype)))
        return treedef.unflatten(out)

    def _chunk_batch_impl(self, params, cache, pieces, pos, tables, slots):
        """One prefill chunk for a GROUP of slots as a single fused
        graph: per-slot leaves gather along the batch axis, rows advance
        together, results scatter back.  Duplicate padding rows re-write
        identical values, so pow-2 row bucketing is safe."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        view = []
        for (path, leaf), paged in zip(flat, self._paged_flags):
            view.append(leaf if paged else jnp.take(
                leaf, slots, axis=_slot_batch_axis(path)))
        logits, new_view = M.prefill_chunk(self.cfg, params,
                                           treedef.unflatten(view),
                                           pieces, pos, tables,
                                           impl=self.impl)
        flat_n = [l for _, l in
                  jax.tree_util.tree_flatten_with_path(new_view)[0]]
        out = []
        for (path, big), small, paged in zip(flat, flat_n,
                                             self._paged_flags):
            if paged:
                out.append(small)
            elif _slot_batch_axis(path) == 0:
                out.append(big.at[slots].set(small.astype(big.dtype)))
            else:
                out.append(big.at[:, slots].set(small.astype(big.dtype)))
        return logits, treedef.unflatten(out)

    def _prefill_install_impl(self, params, cache, prompt, pages, slot):
        """Full prompt prefill + pool install as a single fused graph."""
        logits, slot_cache = M.prefill(self.cfg, params,
                                       {"tokens": prompt},
                                       cache_len=self.max_len,
                                       impl=self.impl)
        return logits, self._install_impl(cache, slot_cache, pages, slot)

    def _decode_paged_impl(self, params, cache, tokens, pos, tables, mask):
        """One paged decode step; rows where ``mask`` is False (idle or
        mid-prefill slots) keep their per-slot cache state untouched —
        their pool writes land in the reserved scratch page."""
        logits, new_cache = M.decode_step(self.cfg, params, cache, tokens,
                                          pos, block_tables=tables,
                                          impl=self.impl)
        flat_o, treedef = jax.tree_util.tree_flatten_with_path(cache)
        flat_n = [l for _, l in
                  jax.tree_util.tree_flatten_with_path(new_cache)[0]]
        out = []
        for (path, old), new, paged in zip(flat_o, flat_n,
                                           self._paged_flags):
            if paged:
                out.append(new)
                continue
            ax = _slot_batch_axis(path)
            shape = [1] * old.ndim
            shape[ax] = mask.shape[0]
            out.append(jnp.where(mask.reshape(shape), new, old))
        return logits, treedef.unflatten(out)

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    def submit(self, req: Request) -> None:
        """Queue a request; the scheduler admits it when a slot and pages
        free up (paged mode rejects requests that could NEVER fit)."""
        if self.paged:
            if len(req.prompt) >= self.max_len:
                raise ValueError(
                    f"prompt length {len(req.prompt)} >= max_len "
                    f"{self.max_len}")
            need = pages_for(min(len(req.prompt) + req.max_new_tokens,
                                 self.max_len), self.page)
            if need > self.num_pages - 1:
                raise ValueError(
                    f"request footprint of {need} pages exceeds the pool "
                    f"({self.num_pages - 1} pages); it could never run")
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        self.waiting.append(req)

    # ------------------------------------------------------------------
    def admit(self, req: Request) -> bool:
        """Place ``req`` into a free slot now (False: no slot / no pages).
        Paged mode starts chunked prefill for long prompts; otherwise the
        whole prompt prefills before this returns (seed semantics)."""
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        slots = self.free_slots()
        if not slots:
            return False
        slot = slots[0]

        if not self.paged:
            t0 = TRACER.now() if TRACER.enabled else 0.0
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            logits, slot_cache = self._prefill(self.params,
                                               {"tokens": prompt})
            self.cache = self._write_slot(self.cache, slot_cache, slot)
            tok = self._sample_token(logits[0, -1], req)
            self._record_token(slot, req, tok)
            self.active[slot] = req
            self.pos[slot] = len(req.prompt)
            self.n_prefills += 1
            self._trace_span("prefill", t0, len(req.prompt))
            return True

        # resume-aware: a preempted request re-prefills prompt + all
        # output but the last sampled token (which is the next decode
        # input, not yet in the cache)
        seq = list(req.prompt) + list(req.output[:-1])
        if not self.allocator.ensure(slot, len(seq)):
            return False
        self.active[slot] = req
        self._admit_order.append(slot)
        if self._chunk_ok and len(seq) > self.prefill_chunk:
            self._state[slot] = PREFILL
            self._seq[slot] = seq
            self._progress[slot] = 0
            self.pos[slot] = 0
            return True
        self._full_prefill(slot, req, seq)
        return True

    def _full_prefill(self, slot: int, req: Request, seq: List[int]) -> None:
        t0 = TRACER.now() if TRACER.enabled else 0.0
        prompt = jnp.asarray(seq, jnp.int32)[None, :]
        pages = jnp.asarray(
            self.allocator.table(slot)[:pages_for(len(seq), self.page)],
            jnp.int32)
        logits, self.cache = self._prefill_install(
            self.params, self.cache, prompt, pages, slot)
        self.n_prefills += 1
        self._trace_span("prefill", t0, len(seq))
        self._finish_prefill(slot, req, seq, logits)

    def _finish_prefill(self, slot: int, req: Request, seq: List[int],
                        logits) -> None:
        self._state[slot] = DECODE
        self.pos[slot] = len(seq)
        if req.output:                       # preemption resume
            self.last_token[slot] = req.output[-1]
        else:
            self._record_token(slot, req,
                               self._sample_token(logits[0, -1], req))

    # ------------------------------------------------------------------
    def _advance_chunks(self) -> None:
        """Advance every mid-prefill slot by one chunk — the interleave
        that keeps long prompts from stalling active decodes.  Slots
        whose next chunk shares a (pos, length, table-width) signature
        (e.g. prompts admitted the same step) advance in ONE batched
        dispatch."""
        groups: Dict[tuple, List[int]] = {}
        for slot in self._admit_order:
            if self._state[slot] != PREFILL:
                continue
            seq, p = self._seq[slot], self._progress[slot]
            C = min(self.prefill_chunk, len(seq) - p)
            width = min(_next_pow2(pages_for(p + C, self.page)),
                        max(self.pages_per_seq, 1))
            groups.setdefault((p, C, width), []).append(slot)
        for (p, C, width), members in groups.items():
            with TRACER.phase("serve.chunk"):
                self._chunk_group(members, p, C, width)

    def _trace_span(self, name: str, t0: float, tokens: int) -> None:
        """Close one engine span against the batch executor's thread-local
        trace context (how prefill/decode steps land under the owning
        invocation's ``execute`` span); no-op untraced."""
        if TRACER.enabled and TRACER.current() is not None:
            TRACER.complete(name, t0, TRACER.now(),
                            attrs={"tokens": int(tokens)})

    def _chunk_group(self, members: List[int], p: int, C: int,
                     width: int) -> None:
        kb = _next_pow2(len(members))
        rows = members + [members[-1]] * (kb - len(members))
        piece = np.zeros((kb, C), np.int32)
        table = np.zeros((kb, width), np.int32)
        for r, slot in enumerate(rows):
            piece[r] = self._seq[slot][p:p + C]
            tab = self.allocator.table(slot)[:width]
            table[r, :len(tab)] = tab
        t0 = TRACER.now() if TRACER.enabled else 0.0
        logits, self.cache = self._chunk_batch(
            self.params, self.cache, jnp.asarray(piece),
            jnp.asarray(p, jnp.int32), jnp.asarray(table),
            jnp.asarray(rows, jnp.int32))
        self.n_prefill_chunks += len(members)
        self._trace_span("prefill_chunk", t0, C * len(members))
        finished = [(r, s) for r, s in enumerate(members)
                    if p + C == len(self._seq[s])]
        for r, slot in enumerate(members):
            if p + C < len(self._seq[slot]):
                self._progress[slot] = p + C
        if finished:
            logits = jax.device_get(logits)
            for r, slot in finished:
                req, seq = self.active[slot], self._seq[slot]
                del self._seq[slot], self._progress[slot]
                self.n_prefills += 1
                self._finish_prefill(slot, req, seq, logits[r:r + 1])

    # ------------------------------------------------------------------
    def _pick_victim(self, exclude: int) -> Optional[int]:
        for i in reversed(self._admit_order):
            if i != exclude:
                return i
        return None

    def _evict(self, slot: int) -> None:
        """Recompute preemption: free the slot's pages and requeue the
        request at the FRONT of the waiting queue (its generated tokens
        are kept; re-admission re-prefills prompt + output)."""
        req = self.active[slot]
        self.allocator.free(slot)
        self.active[slot] = None
        self._state[slot] = IDLE
        self._admit_order.remove(slot)
        self._seq.pop(slot, None)
        self._progress.pop(slot, None)
        self.pos[slot] = 0
        self.last_token[slot] = 0
        self.waiting.appendleft(req)
        self.n_evictions += 1

    def _release(self, slot: int) -> None:
        self.active[slot] = None
        self._state[slot] = IDLE
        self._admit_order.remove(slot)
        self.allocator.free(slot)
        self.pos[slot] = 0
        self.last_token[slot] = 0

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One scheduler step; returns requests finished by it.

        Paged: admit waiting requests into free slots, advance one prefill
        chunk, then one jitted decode step for every decoding slot (with
        page growth / preemption beforehand).  Dense: the seed behavior —
        one decode step over the active slots.

        With the tracer on, the step and its phases (``serve.admit``,
        ``serve.chunk``, ``serve.prep``, ``serve.readback``,
        ``serve.emit``) are written to the tracer and the profiler.
        """
        with TRACER.phase("serve.step"):
            return self._step()

    def _step(self) -> List[Request]:
        if not self.paged:
            return self._step_decode_dense()
        with TRACER.phase("serve.admit"):
            while self.waiting and self.free_slots():
                if not self.admit(self.waiting[0]):
                    break
                self.waiting.popleft()
        self._advance_chunks()
        return self._decode_once()

    def _step_decode_dense(self) -> List[Request]:
        if all(r is None for r in self.active):
            return []
        with TRACER.phase("serve.prep"):
            t0 = TRACER.now() if TRACER.enabled else 0.0
            n_active = sum(r is not None for r in self.active)
            tokens = jnp.asarray(self.last_token, jnp.int32)[:, None]
            pos = jnp.asarray(self.pos, jnp.int32)
        logits, self.cache = self._decode(self.params, self.cache, tokens,
                                          pos)
        self.n_decode_steps += 1
        with TRACER.phase("serve.readback"):
            greedy_tok = np.asarray(jnp.argmax(logits[:, 0], axis=-1),
                                    np.int32)
        self._trace_span("decode", t0, n_active)

        with TRACER.phase("serve.emit"):
            finished = []
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                self.pos[i] += 1
                tok = int(greedy_tok[i]) if self.greedy else \
                    self._sample_token(logits[i, 0], req)
                self._record_token(i, req, tok)
                if tok == EOS or len(req.output) >= req.max_new_tokens or \
                        int(self.pos[i]) >= self.max_len - 1:
                    req.done = True
                    finished.append(req)
                    self.active[i] = None
        return finished

    def _decode_once(self) -> List[Request]:
        decoding = [i for i in range(self.max_slots)
                    if self._state[i] == DECODE]
        if not decoding:
            return []
        with TRACER.phase("serve.prep"):
            # page growth for this step's writes; preempt youngest on
            # exhaustion
            skipped = set()
            for i in list(decoding):
                if self._state[i] != DECODE:
                    continue                # evicted by an earlier growth
                while not self.allocator.ensure(i, int(self.pos[i]) + 1):
                    victim = self._pick_victim(exclude=i)
                    if victim is None:
                        victim = i          # alone and out of pages
                    self._evict(victim)
                    if victim == i:
                        skipped.add(i)
                        break
            decoding = [i for i in decoding
                        if self._state[i] == DECODE and i not in skipped]
            if not decoding:
                return []

            mask = np.zeros((self.max_slots,), bool)
            mask[decoding] = True
            width = min(
                _next_pow2(max(self.allocator.pages_used(i)
                               for i in decoding)),
                max(self.pages_per_seq, 1))
            tables = np.zeros((self.max_slots, width), np.int32)
            for i in decoding:
                tab = self.allocator.table(i)
                tables[i, :len(tab)] = tab
            tokens = np.where(mask, self.last_token, 0).astype(np.int32)
            pos = np.where(mask, self.pos, 0).astype(np.int32)

            t0 = TRACER.now() if TRACER.enabled else 0.0
            args = (jnp.asarray(tokens)[:, None], jnp.asarray(pos),
                    jnp.asarray(tables), jnp.asarray(mask))
        logits, self.cache = self._decode_paged(self.params, self.cache,
                                                *args)
        self.n_decode_steps += 1
        with TRACER.phase("serve.readback"):
            greedy_tok = np.asarray(jnp.argmax(logits[:, 0], axis=-1),
                                    np.int32)
        self._trace_span("decode", t0, len(decoding))

        with TRACER.phase("serve.emit"):
            finished = []
            for i in decoding:
                req = self.active[i]
                self.pos[i] += 1
                tok = int(greedy_tok[i]) if self.greedy else \
                    self._sample_token(logits[i, 0], req)
                self._record_token(i, req, tok)
                if tok == EOS or len(req.output) >= req.max_new_tokens or \
                        int(self.pos[i]) >= self.max_len - 1:
                    req.done = True
                    finished.append(req)
                    self._release(i)
        return finished

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        """Instance-lifetime counters (surfaced by the gateway's engine
        backend next to the per-invocation timestamps)."""
        s = {"n_prefills": self.n_prefills,
             "n_decode_steps": self.n_decode_steps,
             "active_slots": sum(r is not None for r in self.active),
             "max_slots": self.max_slots}
        if self.paged:
            s.update({"paged": 1, "page_size": self.page,
                      "n_pages": self.num_pages - 1,
                      "pages_free": self.allocator.n_free,
                      "n_prefill_chunks": self.n_prefill_chunks,
                      "n_evictions": self.n_evictions,
                      "waiting": len(self.waiting)})
        else:
            s["paged"] = 0
        return s

    # ------------------------------------------------------------------
    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a list of requests to completion (continuous batching)."""
        if not self.paged:
            now = time.perf_counter()
            for r in requests:          # queueing counts toward TTFT
                if r.t_submit is None:
                    r.t_submit = now
            waiting = list(requests)
            done: List[Request] = []
            while waiting or any(r is not None for r in self.active):
                with TRACER.phase("serve.step"):
                    with TRACER.phase("serve.admit"):
                        while waiting and self.free_slots():
                            self.admit(waiting.pop(0))
                    done.extend(self._step_decode_dense())
            return done

        for req in requests:
            self.submit(req)
        done = []
        while self.waiting or any(s != IDLE for s in self._state):
            before = (self.n_prefills, self.n_prefill_chunks,
                      self.n_decode_steps, len(self.waiting))
            done.extend(self.step())
            after = (self.n_prefills, self.n_prefill_chunks,
                     self.n_decode_steps, len(self.waiting))
            if after == before:     # no admission, no chunk, no decode
                raise RuntimeError("paged scheduler stalled "
                                   f"(stats: {self.stats()})")
        return done

    # ------------------------------------------------------------------
    def generate_many(self, groups: List[List[Request]]
                      ) -> List[List[Request]]:
        """Serve several invocations' request groups through ONE shared
        continuous-batching stream.

        All groups' requests compete for the same decode slots, so one
        jitted decode step advances every active request regardless of
        which invocation submitted it — this is what the gateway engine
        dispatcher calls when it micro-batches compatible events.  Returns
        finished requests regrouped per input group (completion order
        within each group, like :meth:`generate`).
        """
        owner: Dict[int, int] = {}
        merged: List[Request] = []
        for gi, group in enumerate(groups):
            for req in group:
                owner[id(req)] = gi
                merged.append(req)
        done = self.generate(merged)
        out: List[List[Request]] = [[] for _ in groups]
        for req in done:
            out[owner[id(req)]].append(req)
        return out
