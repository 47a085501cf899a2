"""Serving API v2: the streaming :class:`Engine`.

One scheduler, pluggable cache backends, request-level control:

  * ``submit(prompt, *, max_new=None, temperature=None, stream=False)``
    → :class:`RequestHandle` — admission is *mid-flight*: submit at any
    time, the next ``step()`` fills whatever slots are free.
  * ``step()`` → ``list[TokenEvent]`` — ONE scheduler tick: apply
    pending cancellations, admit queued requests into free slots
    (per-slot prefill, zero host syncs; whole-batch wave prefill when
    every slot is free), then run ONE on-device decode chunk and make
    the single device→host fetch.  All tokens the tick produced come
    back in emission order.
  * ``cancel(handle)`` — takes effect at the next chunk boundary: the
    slot is retired, its pages return to the pool, and the request
    never emits another token.
  * ``run()`` / ``generate()`` — drain-the-queue convenience wrappers
    over ``step()`` (what the deprecated ``Server`` shim calls).
  * ``register_prefix(tokens)`` → :class:`~repro.serving.prefix
    .PrefixHandle` — pin a shared prompt head in the paged backend's
    prefix index; ``submit(..., prefix=handle)`` prepends it.  Sharing
    itself is automatic (content-hashed at admission) whenever
    ``ServeConfig.prefix_cache`` is on.
  * ``stats()`` → :class:`~repro.serving.state.EngineStats` — the typed
    counter snapshot (``stats[...]`` dict access stays for one release
    with a ``DeprecationWarning``).
  * iterating a handle streams its tokens in order, driving ``step()``
    on demand — single-threaded streaming with no background thread.

The scheduler is cache-layout agnostic: everything monolithic-vs-paged
lives behind the :class:`~repro.serving.backends.CacheBackend` the
engine builds from ``ServeConfig``.  Temperature is per-request on the
plain decode loops (a traced per-slot vector — greedy and sampled
requests batch together); the speculative loop runs the uniform
``scfg.temperature`` because residual acceptance needs draft and verify
distributions at one temperature.

Sync contract: ``step()`` performs exactly one device→host transfer
when any slot is live (the token block) and zero otherwise; admission
and prefill perform none — the first sampled token rides back in the
next chunk's block.  Greedy output is bit-identical to the pre-v2
``Server`` for monolithic, paged and speculative configs.
"""

from __future__ import annotations

import os
import sys
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro import models as MZ
from repro.distributed import sharding as SH
from repro.models.config import ModelConfig
from repro.serving.backends import CacheBackend, make_backend
from repro.serving.config import ServeConfig
from repro.serving.faults import FaultTolerance
from repro.serving.journal import Journal, recover_engine, snapshot_engine
from repro.serving.prefix import PrefixHandle
from repro.serving.sharded import build_plans, model_extent, place_params
from repro.serving.state import (TERMINAL_STATUSES, Request, RequestHandle,
                                 RequestStatus, TokenEvent, _device_fetch,
                                 _fresh_stats, _StatsAccessor,
                                 init_decode_state)

__all__ = ["Engine", "RequestHandle"]


def _fetch(tree: Any) -> Any:
    """The single device→host transfer.  When the deprecated
    ``repro.serving.engine`` module is already imported, resolve through
    its ``_device_fetch`` attribute so tests that monkeypatch it keep
    intercepting every sync; pure-v2 processes never import the shim
    (and so never trigger its deprecation warning)."""
    compat = sys.modules.get("repro.serving.engine")
    if compat is not None:
        return compat._device_fetch(tree)
    return _device_fetch(tree)


class Engine(FaultTolerance):
    """Slot-based continuous batching on one mesh, request-level API.

    Every slot carries its own position counter, done mask, token budget
    and sampling temperature — all device-resident between host syncs.
    Finished (or cancelled) slots are refilled at the next chunk
    boundary by a per-slot prefill that writes only that slot's cache
    rows; in-flight slots never stall.

    ``stats`` records per-chunk wall time and pool pages in use;
    ``sync_count`` counts device→host transfers (the one-per-chunk
    contract); per-request TTFT lives on the :class:`Request` records.

    Under ``jax.profiler`` every tick leaves host spans on the trace's
    host plane (nothing is recorded when the profiler is off):
    ``serve.step`` (arg ``tick``) holding ``serve.admit`` (cancellations,
    deadlines, admission; one ``serve.prefill`` per prefill dispatch,
    args ``uid`` and ``rows``), ``serve.launch`` (page-table upload, key
    split, decode-loop dispatch), ``serve.fetch`` (the device→host
    wait), ``serve.collect`` (guard, token distribution, retirement;
    args ``pages`` in use and ``pool`` size) and, with a journal,
    ``serve.journal`` (the chunk-boundary fsync).
    """

    def __init__(self, cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                 params: Any, draft_params: Any = None):
        scfg.validate()
        self.cfg, self.mesh, self.scfg = cfg, mesh, scfg
        self.params = params
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self._uid_next = 0
        self._tick = 0                  # completed scheduler ticks
        self._key = jax.random.key(scfg.seed)
        self.sync_count = 0
        self._stats: Dict[str, Any] = _fresh_stats()
        self.stats = _StatsAccessor(self)

        if scfg.prefix_cache and MZ.family(cfg) != "lm":
            raise ValueError(
                "prefix_cache shares KV pages by position; the "
                f"'{MZ.family(cfg)}' family carries per-request state "
                "outside the page pool (recurrent/cross caches) — only "
                "decoder-only ('lm') models can share prefixes")

        if scfg.spec and draft_params is None:
            if scfg.spec_draft == "pack":
                from repro.core.sparse_linear import make_draft_params
                draft_params = make_draft_params(params, cfg)
            else:                                   # "self"
                draft_params = params
        self.draft_params = draft_params

        # multi-device model axis: place the (packed) weights per the
        # sharding rules up front — idempotent for already-placed trees,
        # so callers may pre-shard (checkpoints restore sharded)
        if model_extent(mesh) > 1:
            self_draft = self.draft_params is params
            params = place_params(params, cfg, mesh)
            self.params = params
            if self.draft_params is not None:
                self.draft_params = (params if self_draft else
                                     place_params(self.draft_params, cfg,
                                                  mesh))

        plans = build_plans(params, self.draft_params, cfg, scfg, mesh=mesh)
        self.prefill_plan = plans["prefill"]
        self.decode_plan = plans["decode"]
        self.draft_plan = plans["draft"]
        self.verify_plan = plans["verify"]
        self.dispatch_plan = self.prefill_plan      # back-compat alias

        self._abstract_params = jax.eval_shape(lambda: params)
        self._abstract_draft = (jax.eval_shape(lambda: self.draft_params)
                                if scfg.spec else None)
        self._abstract_cache = jax.eval_shape(
            lambda: MZ.init_cache(cfg, scfg.slots, scfg.max_len,
                                  page_size=scfg.page_size,
                                  num_pages=scfg.pool_pages))
        cspecs = SH.cache_specs(self._abstract_cache, cfg, mesh,
                                kv_mode=scfg.kv_mode)
        # hoisted: jitted once here, not per wave inside the serve loop
        self._init_cache = jax.jit(
            lambda: MZ.init_cache(cfg, scfg.slots, scfg.max_len,
                                  page_size=scfg.page_size,
                                  num_pages=scfg.pool_pages),
            out_shardings=SH.named(mesh, cspecs))

        self._backend: CacheBackend = make_backend(
            cfg, mesh, scfg, self._abstract_params, self._abstract_draft,
            self._abstract_cache, self._stats)
        self._slot_req: List[Optional[Request]] = [None] * scfg.slots
        self._temps = np.full((scfg.slots,), scfg.temperature, np.float32)
        self._cache = None
        self._state = None
        # --- fault tolerance: overridable seams + degraded flag --------
        # instance attributes so the chaos harness (serving.chaos) can
        # wrap them per engine without monkeypatching modules
        self.degraded = False
        self._clean_chunks = 0          # consecutive fault-free chunks
        self._device_fetch = _fetch
        self._chaos = None
        # --- crash safety: pinned prefixes by pid + the WAL ------------
        self._pins: Dict[int, PrefixHandle] = {}
        self._pin_next = 0
        self.journal: Optional[Journal] = None
        if scfg.journal_path:
            self.journal = Journal(scfg.journal_path)
            self.journal.log_config(scfg)
        if os.environ.get("REPRO_CHAOS_SEED"):
            from repro.serving.chaos import ChaosConfig, ChaosMonkey
            ChaosMonkey(self, ChaosConfig.from_env()).attach()

    # --- introspection / stats ----------------------------------------

    @property
    def num_live(self) -> int:
        """Slots currently decoding a request."""
        return sum(r is not None for r in self._slot_req)

    @property
    def num_queued(self) -> int:
        return len(self.queue)

    def reset_stats(self) -> None:
        """Zero the serving counters — including the speculative
        drafted/accepted tallies and the prefix-sharing tallies —
        (benchmarks call this after their compile warm-up pass)."""
        self.sync_count = 0
        self._stats.clear()                 # in place: the backend and
        self._stats.update(_fresh_stats())  # callers hold references

    def acceptance_rate(self) -> float:
        """Deprecated: read ``engine.stats().acceptance_rate``."""
        warnings.warn(
            "Engine.acceptance_rate() is deprecated; read "
            "engine.stats().acceptance_rate",
            DeprecationWarning, stacklevel=2)
        return self._stats["accepted"] / max(self._stats["drafted"], 1)

    def _cache_nbytes(self) -> int:
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree.leaves(self._abstract_cache))

    def cache_bytes(self) -> int:
        """Deprecated: read ``engine.stats().cache_bytes`` (the buffers
        ``init_cache`` materializes — pool + tables for paged, the full
        ``slots × max_len`` block for monolithic)."""
        warnings.warn(
            "Engine.cache_bytes() is deprecated; read "
            "engine.stats().cache_bytes",
            DeprecationWarning, stacklevel=2)
        return self._cache_nbytes()

    def ttfts_s(self) -> List[float]:
        """TTFT of every finished request that emitted a token."""
        return [r.ttft_s for r in self.finished if r.ttft_s is not None]

    # --- request intake -----------------------------------------------

    def _coerce_prompt(self, prompt: Union[Sequence[int], np.ndarray]
                       ) -> np.ndarray:
        arr = np.asarray(prompt)
        if arr.ndim != 1:
            raise ValueError(
                f"prompt must be 1-D (one request), got shape {arr.shape}")
        if arr.size == 0:
            raise ValueError("prompt is empty — nothing to prefill")
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"prompt must hold integer token ids, got dtype "
                f"{arr.dtype}")
        if arr.size > self.scfg.max_len - 1:
            raise ValueError(
                f"prompt of {arr.size} tokens cannot fit max_len="
                f"{self.scfg.max_len} with room to decode (limit is "
                f"max_len - 1 = {self.scfg.max_len - 1})")
        return arr.astype(np.int32)

    def register_prefix(self, tokens: Union[Sequence[int], np.ndarray]
                        ) -> PrefixHandle:
        """Pin a shared prompt head; returns its :class:`PrefixHandle`.

        ``tokens`` must be a whole number of pages (``len %
        page_size == 0``) — they are computed once into index-owned
        pages (reusing any blocks already resident) and every page takes
        a refcount the handle holds, so the head stays warm across slot
        churn and eviction until :meth:`PrefixHandle.release`.

        Contract: the registered tokens occupy prompt rows ``[0, len)``.
        Because prompts are left-padded to their bucket width, a
        submission shares these pages exactly when its *padded* head
        equals them — i.e. the full prompt (prefix + suffix) fills its
        bucket, or the caller registers the padded head it will submit.
        ``submit(..., prefix=handle)`` prepends the handle's tokens for
        you.  Hash-matched sharing between plain submissions needs no
        handle; registration adds *pinning* (residence guarantees), not
        matching.
        """
        scfg = self.scfg
        if not scfg.prefix_cache:
            raise ValueError(
                "register_prefix needs ServeConfig.prefix_cache=True "
                "(and the paged layout, page_size > 0)")
        arr = self._coerce_prompt(tokens)
        if len(arr) % scfg.page_size:
            raise ValueError(
                f"a registered prefix must be a whole number of pages: "
                f"got {len(arr)} tokens with page_size={scfg.page_size}")
        with jax.set_mesh(self.mesh):
            self._ensure_device_state()
            nodes, page_row = self._backend.register_prefix(arr)
            if page_row is not None:
                fill = self._backend.prefix_fill_step(len(arr))
                self._cache = fill(self.params,
                                   {"tokens": jnp.asarray(arr[None])},
                                   self._cache, jnp.asarray(page_row))
        h = PrefixHandle(self, arr.copy(), nodes)
        h._pid = self._pin_next
        self._pin_next += 1
        self._pins[h._pid] = h
        if self.journal is not None:
            self.journal.log_pin(h._pid, arr)
        return h

    def _release_prefix(self, handle: PrefixHandle) -> None:
        self._backend.release_prefix(handle._nodes)
        if handle._pid is not None:
            self._pins.pop(handle._pid, None)
            if self.journal is not None:
                self.journal.log_unpin(handle._pid)

    def submit(self, prompt: Union[Sequence[int], np.ndarray], *,
               max_new: Optional[int] = None,
               temperature: Optional[float] = None,
               stream: bool = False,
               prefix: Optional[PrefixHandle] = None,
               priority: int = 0,
               deadline_ms: Optional[float] = None) -> RequestHandle:
        """Queue one request; returns its :class:`RequestHandle`.

        ``prompt`` may be a Python list or any 1-D integer array.
        Prompts longer than the prefill window are *left-truncated* to
        their most recent ``prompt_rows`` tokens (the v1 behavior —
        standard context-window semantics); prompts that cannot fit the
        cache at all (> ``max_len - 1``) are rejected here.  ``max_new``
        defaults to ``scfg.max_new_tokens``; ``temperature`` defaults to
        ``scfg.temperature`` and may differ per request on the
        non-speculative loops (0 → greedy).  Admission happens at the
        next ``step()`` — submitting mid-run is the point.

        ``prefix`` prepends a :meth:`register_prefix` handle's tokens to
        ``prompt`` (the session posture: register the system prompt
        once, submit only the user turn).  Admission maps the pinned
        pages whenever the combined prompt's padded head lines up with
        them — see :meth:`register_prefix` for the alignment contract;
        greedy output is bit-identical either way.

        ``priority`` orders admission (higher first; FIFO within a
        level) and arms preemption: under pool exhaustion the scheduler
        evicts the lowest-priority running slot *strictly below* the
        blocked head's priority.  ``deadline_ms`` is a wall-clock budget
        from submission — at the first chunk boundary past it the
        request ends ``TIMED_OUT``, queued or running.  When
        ``scfg.max_queue`` bounds the admission queue, a submission
        beyond the bound returns an already-finished ``REJECTED`` handle
        instead of waiting forever.
        """
        scfg = self.scfg
        if prefix is not None:
            if prefix._engine is not self:
                raise ValueError("prefix handle belongs to a different "
                                 "engine")
            if prefix.released:
                raise ValueError("prefix handle was released")
            prompt = np.concatenate(
                [prefix.tokens, np.asarray(prompt, np.int32).ravel()])
        arr = self._coerce_prompt(prompt)
        if max_new is None:
            max_new = scfg.max_new_tokens
        if max_new <= 0:
            raise ValueError(f"max_new must be positive, got {max_new}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be positive, got {deadline_ms}")
        if temperature is not None and scfg.spec \
                and float(temperature) != scfg.temperature:
            raise ValueError(
                "per-request temperature is not supported with "
                "speculative decoding (residual acceptance needs draft "
                "and verify at one temperature) — set "
                "ServeConfig.temperature instead")
        if scfg.paged:
            need = scfg.request_pages(len(arr), max_new)
            if need > scfg.pool_pages:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{scfg.pool_pages} — raise num_pages")
        req = Request(uid=self._uid_next, prompt=arr, max_new=max_new,
                      temperature=temperature, stream=stream,
                      priority=int(priority), deadline_ms=deadline_ms)
        self._uid_next += 1
        if scfg.max_queue and len(self.queue) >= scfg.max_queue:
            self._stats["rejections"] += 1
            self._finish(req, None, RequestStatus.REJECTED,
                         time.perf_counter())
        else:
            self.queue.append(req)
        if self.journal is not None:    # durable before the handle is
            self.journal.log_submit(req)    # returned to the caller
        return RequestHandle(self, req)

    def cancel(self, handle: Union[RequestHandle, Request, int]) -> None:
        """Request cancellation; takes effect at the next chunk
        boundary (the slot is retired and its pages freed before the
        next decode chunk, so no further tokens are ever emitted).
        Idempotent: cancelling an already-terminal handle — finished,
        cancelled, timed out, rejected — is a no-op (in particular it
        can never double-release pages; retirement happens exactly once,
        when the request leaves its slot)."""
        if isinstance(handle, RequestHandle):
            req = handle._req
        elif isinstance(handle, Request):
            req = handle
        else:
            req = next((r for r in self.queue + self._slot_req
                        if r is not None and r.uid == handle), None)
            if req is None:
                return
        if req.status in TERMINAL_STATUSES:
            return
        req.cancel_requested = True

    # --- the scheduler tick -------------------------------------------

    def _pad_prompt(self, r: Request, rows: Optional[int] = None
                    ) -> np.ndarray:
        """Left-pad the request's *effective* prompt (original prompt
        plus any tokens emitted before a preemption) to ``rows``.  A
        resumed request's width is ``rows0 + emitted``, so its pad count
        equals the first admission's — the padded layout (and therefore
        any published prefix pages, and the greedy token stream) is
        preserved across preempt → requeue → re-prefill."""
        width = rows or self.scfg.prompt_pad
        eff = r.eff_prompt
        tokens = np.zeros((1, width), np.int32)
        L = min(len(eff), width)
        tokens[0, width - L:] = eff[-L:]                       # left-pad
        return tokens

    def _ensure_device_state(self) -> None:
        if self._cache is None:
            self._cache = self._init_cache()
            self._state = init_decode_state(self.scfg.slots)

    def _finish(self, req: Request, slot: Optional[int],
                status: RequestStatus, now: float) -> None:
        req.done = True
        req.set_status(status)
        req.finish_s = now
        self.finished.append(req)
        if slot is not None:
            self._slot_req[slot] = None
            self._backend.retire(slot)

    def _freeze_slot(self, i: int) -> None:
        """Stop slot ``i`` decoding without a fetch — two scalar updates
        ride host→device at the chunk boundary."""
        self._state = dict(
            self._state,
            done=self._state["done"].at[i].set(True),
            left=self._state["left"].at[i].set(0))

    def _apply_cancels(self) -> None:
        """Chunk-boundary cancellation: freeze the slot's device state,
        retire it in the backend (pages freed), and drop cancelled
        queue entries."""
        now = time.perf_counter()
        for i, r in enumerate(self._slot_req):
            if r is not None and r.cancel_requested:
                r.cancel_requested = False      # consumed exactly once
                self._freeze_slot(i)
                self._finish(r, i, RequestStatus.CANCELLED, now)
        for r in [r for r in self.queue if r.cancel_requested]:
            r.cancel_requested = False
            self.queue.remove(r)
            self._finish(r, None, RequestStatus.CANCELLED, now)

    def _admit(self) -> None:
        """Fill free slots from the queue — highest priority first, FIFO
        within a level (stable sort on submission uid; a preempted
        victim keeps its uid, so it re-admits ahead of later
        equal-priority arrivals).  When EVERY slot is free and the
        backend supports it, one batched wave prefill replaces ``slots``
        per-slot dispatches; otherwise per-slot refill.  Admission is
        gated by the backend (paged: worst-case reservation); when the
        head is blocked the scheduler preempts the lowest-priority
        running slot strictly below the head's priority, else records an
        admission wait (head-of-line blocking keeps FIFO fairness)."""
        scfg = self.scfg
        self.queue.sort(key=lambda r: (-r.priority, r.uid))
        head = self.queue[:scfg.slots]
        wave = self._backend.wave_step() if head and self.num_live == 0 \
            and all(r.rows0 is None for r in head) else None
        if wave is not None:
            take = head
            del self.queue[:len(take)]
            prompts = np.zeros((scfg.slots, scfg.prompt_pad), np.int32)
            budgets = np.zeros(scfg.slots, np.int32)
            valid = np.zeros(scfg.slots, bool)
            for i, r in enumerate(take):
                prompts[i] = self._pad_prompt(r)[0]
                budgets[i] = r.max_new
                valid[i] = True
                self._temps[i] = (scfg.temperature if r.temperature is None
                                  else r.temperature)
                r.rows0 = self._backend.admit(i, len(r.prompt), r.max_new)
                r.slot = i
                r.set_status(RequestStatus.RUNNING)
                self._slot_req[i] = r
            with TraceAnnotation("serve.prefill",
                                 uid=",".join(str(r.uid) for r in take),
                                 rows=scfg.prompt_pad):
                self._key, sk = jax.random.split(self._key)
                self._cache, self._state = wave(
                    self.params, {"tokens": jnp.asarray(prompts)},
                    self._cache, jnp.asarray(valid), jnp.asarray(budgets),
                    jnp.asarray(self._temps), sk)
            self._stats["prefills"] += len(take)
            return
        while self.queue:
            free = [i for i in range(scfg.slots)
                    if self._slot_req[i] is None]
            if not free:
                break
            r = self.queue[0]
            eff_len = len(r.prompt) + len(r.out)
            rows = r.resume_rows or self._backend.prompt_rows(eff_len)
            # the padded rows are what the prefix index keys on — hand
            # them to admission so matching and COW planning happen in
            # the backend (layouts without an index ignore them)
            padded = self._pad_prompt(r, rows)
            if not self._backend.can_admit(eff_len, r.remaining_new,
                                           tokens=padded[0], rows=rows):
                victim = self._victim_slot(r.priority)
                if victim is None:
                    self._stats["admission_waits"] += 1
                    break
                self._preempt(victim, time.perf_counter())
                continue
            self.queue.pop(0)
            i = free[0]
            rows = self._backend.admit(i, eff_len, r.remaining_new,
                                       tokens=padded[0], rows=rows)
            if r.rows0 is None:
                r.rows0 = rows
            start, cow = self._backend.prefill_plan(i)
            temp = (scfg.temperature if r.temperature is None
                    else r.temperature)
            with TraceAnnotation("serve.prefill", uid=r.uid, rows=rows):
                self._key, sk = jax.random.split(self._key)
                self._cache, self._state = self._backend.prefill_step(
                    rows, start, cow)(
                    self.params, {"tokens": jnp.asarray(padded[:, start:])},
                    self._cache, self._state, jnp.asarray(i, jnp.int32),
                    jnp.asarray(r.remaining_new, jnp.int32),
                    jnp.asarray(temp, jnp.float32), sk,
                    *self._backend.prefill_args(i))
            self._temps[i] = temp
            r.slot = i
            r.set_status(RequestStatus.RUNNING)
            self._slot_req[i] = r
            self._stats["prefills"] += 1

    def _collect(self, blk, emit, done, dt: float,
                 pages: int) -> List[TokenEvent]:
        """Distribute one fetched token block in emission order, stamp
        TTFTs, record the chunk stats (wall time ``dt``, ``pages`` in
        use), and retire finished slots."""
        scfg = self.scfg
        now = time.perf_counter()
        emitted: List[tuple] = []           # (request, index-in-output)
        for t in range(blk.shape[0]):       # chunk_tokens rows under spec
            for i in range(scfg.slots):
                r = self._slot_req[i]
                if emit[t, i] and r is not None:
                    r.out.append(int(blk[t, i]))
                    if r.first_token_s is None:
                        r.first_token_s = now
                    self._backend.note_commit(i)
                    emitted.append((r, len(r.out) - 1))
        self._stats["chunk_s"].append(dt)
        self._stats["chunk_pages"].append(pages)
        for i in range(scfg.slots):
            r = self._slot_req[i]
            if r is not None and done[i]:
                self._finish(r, i, RequestStatus.DONE, now)
        return [TokenEvent(uid=r.uid, token=r.out[idx], index=idx,
                           final=(r.done and idx == len(r.out) - 1))
                for r, idx in emitted]

    def step(self) -> List[TokenEvent]:
        """One scheduler tick: cancellations → deadlines → admission
        (+ prefill, preempting lower-priority slots under pool
        exhaustion) → one decode chunk → the single fetch → the numeric
        guard.  Returns every token the tick emitted, in emission order;
        an empty list means nothing is live (queue empty or admission
        fully blocked).  Never raises on an injected/transient fault —
        the affected requests end in a terminal status instead."""
        events: List[TokenEvent] = []
        with TraceAnnotation("serve.step", tick=self._tick):
            with jax.set_mesh(self.mesh):
                self._ensure_device_state()
                with TraceAnnotation("serve.admit"):
                    self._apply_cancels()
                    self._apply_deadlines()
                    self._admit()
                live = [i for i, r in enumerate(self._slot_req)
                        if r is not None]
                if live:
                    events = self._decode_chunk(live)
            self._tick += 1
            if self.journal is not None:    # the chunk-boundary fsync
                with TraceAnnotation("serve.journal"):  # BEFORE delivery
                    self.journal.record_tick(self, events)
        return events

    def _decode_chunk(self, live: List[int]) -> List[TokenEvent]:
        """Launch one decode chunk over the ``live`` slots, make the
        single fetch, and collect its tokens."""
        with TraceAnnotation("serve.launch"):
            loop, extra = self._backend.begin_chunk(live)
            pages = self._backend.pages_in_use()
            self._key, sk = jax.random.split(self._key)
            t0 = time.perf_counter()
            f0 = self._fault_count()
            out = self._launch_chunk(live, loop, sk, extra)
        fetched = self._fetch_chunk(out)
        dt = time.perf_counter() - t0
        events: List[TokenEvent] = []
        with TraceAnnotation("serve.collect", pages=pages,
                             pool=self.scfg.pool_pages):
            if fetched is None:         # unrecoverable fetch: the
                now = time.perf_counter()   # chunk's tokens are lost
                for i in live:
                    self._quarantine(i, now)
            else:
                blk, emit = self._guard_block(fetched[0], fetched[1])
                events = self._collect(blk, emit, fetched[2], dt, pages)
            self._note_chunk_health(self._fault_count() != f0)
            self._backend.end_chunk(
                [i for i in live if self._slot_req[i] is not None])
        return events

    # --- crash safety -------------------------------------------------

    def snapshot(self, directory: str) -> str:
        """One atomic, digest-verified checkpoint of the scheduler state
        (config, queue + slot occupancy, pins, stats, PRNG key) through
        :mod:`repro.checkpoint.store`; returns the step directory.  See
        :func:`repro.serving.journal.snapshot_engine`."""
        return snapshot_engine(self, directory)

    @classmethod
    def restore(cls, cfg: ModelConfig, mesh: Mesh, params: Any, *,
                scfg: Optional[ServeConfig] = None,
                draft_params: Any = None,
                journal_path: Optional[str] = None,
                snapshot_dir: Optional[str] = None):
        """Fresh engine + snapshot/journal replay; non-terminal requests
        are re-queued for bit-identical resume.  Returns the
        :class:`~repro.serving.journal.Recovered` bundle (``.engine``,
        ``.handles``, ``.prefixes``, ``.timings``)."""
        return recover_engine(cfg, mesh, params, scfg=scfg,
                              draft_params=draft_params,
                              journal_path=journal_path,
                              snapshot_dir=snapshot_dir)

    # --- convenience wrappers -----------------------------------------

    def run(self) -> List[Request]:
        """Serve until the queue drains; returns the finished-request
        records (cumulative across calls, like the v1 ``Server``).
        Tolerates a few fully-idle ticks before declaring the queue
        permanently blocked — transient pool pressure (chaos injection,
        a pin about to be released) clears within a tick or two."""
        idle = 0
        while self.queue or self.num_live:
            if self.step() or self.num_live:
                idle = 0
                continue
            idle += 1               # admission blocked with nothing live
            if idle > 8:
                break
        return self.finished

    def generate(self, prompts: Sequence[Any], *,
                 max_new: Optional[int] = None,
                 temperature: Optional[float] = None) -> List[List[int]]:
        """Submit a batch of prompts, serve to completion, and return
        each request's tokens in submission order."""
        handles = [self.submit(p, max_new=max_new, temperature=temperature)
                   for p in prompts]
        self.run()
        return [h.tokens for h in handles]
