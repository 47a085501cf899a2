"""Cache backends: the layout-specific half of the serving engine.

The scheduler in ``serving.api`` is layout-agnostic — every place the
old monolithic ``Server`` forked on ``scfg.paged`` is now a method on a
:class:`CacheBackend`:

  * :class:`MonoBackend` — the monolithic ``(slots, max_len, …)`` KV
    cache.  Admission always succeeds, retirement is free, and the
    whole-batch wave-prefill fast path is available.
  * :class:`PagedBackend` — the shared page pool + per-slot page tables.
    Owns the host-side allocator: worst-case page *reservation* at
    admission (requests wait instead of OOMing), lazy physical
    allocation at prefill/chunk boundaries, page recycling and table
    nulling at retirement, per-request prompt buckets, and the decode
    attention view narrowed to the live slots' page bucket.

Everything here is host arithmetic over already-fetched state plus
host→device argument passing (the page table): backends never add a
device→host sync, so the one-fetch-per-chunk contract is theirs to keep
by construction.  Both backends build and cache their jitted programs
(per prompt-bucket prefill steps, per view-bucket decode loops) through
``serving.loops``; the speculative loop is selected by ``scfg.spec``
inside the shared base — one spec builder serves both layouts.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Protocol, Tuple

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.models.config import ModelConfig
from repro.serving import loops
from repro.serving.config import ServeConfig
from repro.serving.prefix import PrefixIndex


class CacheBackend(Protocol):
    """What the scheduler needs from a cache layout.

    Lifecycle per request: ``can_admit`` → ``admit`` (reserve + return
    the prompt-row width; with prefix sharing also match the index and
    map shared pages) → ``prefill_plan``/``prefill_step``/
    ``prefill_args`` (where prefill starts, the jitted program and its
    layout-specific extra operands) → per chunk ``begin_chunk`` (returns
    the decode loop + extra traced args) / ``note_commit`` (a token
    landed) / ``end_chunk`` — then ``retire``.  ``tokens`` is the
    request's *padded* prompt rows (sharing keys on the padded layout);
    layouts without an index ignore it.
    """
    paged: bool

    def prompt_rows(self, prompt_len: int) -> int: ...
    def can_admit(self, prompt_len: int, max_new: int,
                  tokens: Optional[np.ndarray] = None,
                  rows: Optional[int] = None) -> bool: ...
    def admit(self, slot: int, prompt_len: int, max_new: int,
              tokens: Optional[np.ndarray] = None,
              rows: Optional[int] = None) -> int: ...
    def clear_programs(self) -> None: ...
    def prefill_plan(self, slot: int) -> Tuple[int, bool]: ...
    def prefill_step(self, rows: int, start: int = 0,
                     cow: bool = False) -> Callable: ...
    def prefill_args(self, slot: int) -> Tuple: ...
    def wave_step(self) -> Optional[Callable]: ...
    def begin_chunk(self, live_slots: List[int]) -> Tuple[Callable, Tuple]:
        ...
    def note_commit(self, slot: int) -> None: ...
    def end_chunk(self, live_slots: List[int]) -> None: ...
    def retire(self, slot: int) -> None: ...
    def pages_in_use(self) -> int: ...


class _BackendBase:
    """Shared jitted-program caches (decode loops keyed by view bucket,
    prefill steps keyed by prompt rows)."""

    paged = False

    def __init__(self, cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                 abstract_params: Any, abstract_draft: Any,
                 abstract_cache: Any, stats: Dict[str, Any]):
        self.cfg, self.mesh, self.scfg = cfg, mesh, scfg
        self._ap, self._ad, self._ac = (abstract_params, abstract_draft,
                                        abstract_cache)
        self.stats = stats
        self._prefill_steps: Dict[Tuple[int, int, bool], Callable] = {}
        self._decode_loops: Dict[Optional[int], Callable] = {}
        self._wave: Optional[Callable] = None

    def prefill_plan(self, slot: int) -> Tuple[int, bool]:
        """(start row, needs-COW-copy) for the slot's pending prefill —
        (0, False) unless prefix sharing mapped resident pages."""
        return 0, False

    def pages_in_use(self) -> int:
        """Pool pages holding live rows (0 for layouts without a pool)."""
        return 0

    def clear_programs(self) -> None:
        """Drop every cached jitted program so the next chunk/prefill
        re-traces — the engine's degraded mode re-resolves kernel
        dispatch (now forced to ``ref``) through this."""
        self._prefill_steps.clear()
        self._decode_loops.clear()
        self._wave = None

    def prefill_step(self, rows: int, start: int = 0,
                     cow: bool = False) -> Callable:
        key = (rows, start, cow)
        fn = self._prefill_steps.get(key)
        if fn is None:
            if start or cow:
                fn = loops.build_prefix_prefill_slot_step(
                    self.cfg, self.mesh, self.scfg, self._ap, self._ac,
                    prompt_rows=rows, start=start, cow=cow)
            else:
                fn = loops.build_prefill_slot_step(
                    self.cfg, self.mesh, self.scfg, self._ap, self._ac,
                    prompt_rows=rows, paged=self.paged)
            self._prefill_steps[key] = fn
        return fn

    def _decode_loop(self, view: Optional[int]) -> Callable:
        fn = self._decode_loops.get(view)
        if fn is None:
            if self.scfg.spec:
                fn = loops.build_spec_decode_loop(
                    self.cfg, self.mesh, self.scfg, self._ap, self._ad,
                    self._ac, paged=self.paged, view_pages=view)
            else:
                fn = loops.build_decode_loop(
                    self.cfg, self.mesh, self.scfg, self._ap, self._ac,
                    paged=self.paged, view_pages=view)
            self._decode_loops[view] = fn
        return fn


class MonoBackend(_BackendBase):
    """Monolithic ``slots × max_len`` cache: no allocator, no extra loop
    operands, and the wave-prefill fast path."""

    paged = False

    def prompt_rows(self, prompt_len: int) -> int:
        return self.scfg.prompt_pad

    def can_admit(self, prompt_len: int, max_new: int,
                  tokens: Optional[np.ndarray] = None,
                  rows: Optional[int] = None) -> bool:
        return True

    def admit(self, slot: int, prompt_len: int, max_new: int,
              tokens: Optional[np.ndarray] = None,
              rows: Optional[int] = None) -> int:
        # ``rows`` is a resumed request's exact prefill width (rows0 +
        # emitted); fresh admissions use the uniform prompt_pad
        return rows or self.scfg.prompt_pad

    def prefill_args(self, slot: int) -> Tuple:
        return ()

    def wave_step(self) -> Optional[Callable]:
        if self._wave is None:
            self._wave = loops.build_prefill_wave_step(
                self.cfg, self.mesh, self.scfg, self._ap, self._ac)
        return self._wave

    def begin_chunk(self, live_slots: List[int]) -> Tuple[Callable, Tuple]:
        return self._decode_loop(None), ()

    def note_commit(self, slot: int) -> None:
        pass

    def end_chunk(self, live_slots: List[int]) -> None:
        pass

    def retire(self, slot: int) -> None:
        pass


class PagedBackend(_BackendBase):
    """Shared page pool + per-slot page tables (see ``models.attention``
    for the device layout).  The admission *reservation* guarantees a
    request, once admitted, can always reach its budget: live slots can
    never starve mid-decode, waiting happens at admission instead.

    With ``scfg.prefix_cache`` a :class:`~repro.serving.prefix
    .PrefixIndex` keys resident full prompt pages by content: admission
    maps matched pages read-only at the head of the slot's table
    (refcount +1 each), reserves only the private remainder, and plans
    the prefill to start at the first non-shared row — with a
    copy-on-write page copy when the divergence falls mid-page.  Shared
    pages may then appear in several tables at once: decode only ever
    *gathers* them (each slot's writes land at its own position, past
    its prompt rows), so the attention view math is unchanged.  At
    retirement shared pages are decref'd, not freed — refcount zero
    moves them to the retained (warm, evictable) set, and they rejoin
    the free list only through eviction.  With the flag off every code
    path below reduces exactly to the v1 allocator (same free-list
    order, same stats).
    """

    paged = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        scfg = self.scfg
        self.free_pages: List[int] = list(range(scfg.pool_pages, 0, -1))
        self.reserved = 0
        self.slot_pages: List[List[int]] = [[] for _ in range(scfg.slots)]
        self.slot_need = [0] * scfg.slots
        self.slot_rows = [0] * scfg.slots
        self.ptab = np.zeros((scfg.slots, scfg.max_pages), np.int32)
        # --- prefix sharing ------------------------------------------
        self.prefix_on = scfg.prefix_cache
        self.index: Optional[PrefixIndex] = (
            PrefixIndex(scfg.page_size, scfg.prefix_cache_pages)
            if self.prefix_on else None)
        self.slot_shared: List[List[Any]] = [[] for _ in range(scfg.slots)]
        self.slot_resv = [0] * scfg.slots      # private pages reserved
        self.slot_plan: List[Tuple[int, int, int]] = \
            [(0, 0, 0)] * scfg.slots           # (start, cow_src, cow_dst)
        self._prefix_fills: Dict[int, Callable] = {}

    # --- admission / prefill ------------------------------------------

    def prompt_rows(self, prompt_len: int) -> int:
        return self.scfg.prompt_rows(prompt_len)

    def can_admit(self, prompt_len: int, max_new: int,
                  tokens: Optional[np.ndarray] = None,
                  rows: Optional[int] = None) -> bool:
        rows = rows or self.scfg.prompt_rows(prompt_len)
        need = self.scfg.rows_pages(rows, max_new)
        if not self.prefix_on:
            return self.reserved + need <= self.scfg.pool_pages
        # shared hits shrink the private need; retained (refcount-zero)
        # pages are reclaimable on demand so only live ones count
        if tokens is not None:
            nodes, _ = self.index.match(tokens, rows)
            need -= min(len(nodes), (rows - 1) // self.scfg.page_size)
        return (self.reserved + need + self.index.live_pages
                <= self.scfg.pool_pages)

    def admit(self, slot: int, prompt_len: int, max_new: int,
              tokens: Optional[np.ndarray] = None,
              rows: Optional[int] = None) -> int:
        scfg = self.scfg
        ps = scfg.page_size
        rows = rows or scfg.prompt_rows(prompt_len)
        need = scfg.rows_pages(rows, max_new)
        self.slot_need[slot] = need
        self.slot_rows[slot] = rows
        self.ptab[slot] = 0
        self.slot_plan[slot] = (0, 0, 0)
        if not (self.prefix_on and tokens is not None):
            self.slot_resv[slot] = need
            self.reserved += need
            self._alloc(slot, -(-rows // ps))
            return rows
        nodes, partial = self.index.match(tokens, rows)
        maxb = (rows - 1) // ps        # ≥ 1 row must be recomputed for
        if len(nodes) > maxb:          # the first-token logits: a full-
            partial = (nodes[maxb], ps)    # prompt match COWs its tail
            nodes = nodes[:maxb]           # page and redoes the last row
        m = len(nodes)
        start = m * ps
        r = 0
        if partial is not None:
            pnode, r = partial
            r = min(r, rows - 1 - start)
        for b, nd in enumerate(nodes):
            self.index.acquire(nd)
            self.ptab[slot, b] = nd.page
        self.slot_shared[slot] = list(nodes)
        self.slot_resv[slot] = need - m
        self.reserved += self.slot_resv[slot]
        self._alloc(slot, -(-rows // ps))
        cow_src = cow_dst = 0
        if r >= 1:
            cow_src, cow_dst = pnode.page, int(self.ptab[slot, m])
            start = m * ps + r
            self.stats["cow_copies"] += 1
        if start:
            self.stats["prefix_hits"] += 1
            self.stats["shared_pages"] += m
        self.slot_plan[slot] = (start, cow_src, cow_dst)
        self._index_prompt(slot, tokens, rows, m)
        return rows

    def _index_prompt(self, slot: int, tokens: np.ndarray, rows: int,
                      m: int) -> None:
        """Publish the slot's freshly computed full prompt blocks
        (``[m, rows // ps)``) into the index — ownership of those pages
        transfers from the slot's private list to the trie (refcount 1
        for this slot; decref'd at retire instead of freed).  Their
        content becomes valid when this admission's prefill executes,
        which precedes any matching reader in device program order."""
        ps = self.scfg.page_size
        shared = self.slot_shared[slot]
        parent = shared[-1] if shared else None
        created = []
        for b in range(m, rows // ps):
            node, ok = self.index.insert(
                parent, tokens[b * ps:(b + 1) * ps],
                int(self.ptab[slot, b]))
            if not ok:      # identical block already published (the
                break       # full-match COW tail) — keep page private
            self.index.acquire(node)
            created.append(node)
            parent = node
        if created:
            self.slot_pages[slot] = self.slot_pages[slot][len(created):]
            shared.extend(created)

    def prefill_plan(self, slot: int) -> Tuple[int, bool]:
        start, _, cow_dst = self.slot_plan[slot]
        return start, cow_dst != 0

    def prefill_args(self, slot: int) -> Tuple:
        _, cow_src, cow_dst = self.slot_plan[slot]
        args: Tuple = (jnp.asarray(self.ptab[slot]),)
        if cow_dst:
            args += (jnp.asarray(cow_src, jnp.int32),
                     jnp.asarray(cow_dst, jnp.int32))
        return args

    def wave_step(self) -> Optional[Callable]:
        return None                 # paged always refills per slot

    # --- registered (pinned) prefixes ---------------------------------

    def register_prefix(self, tokens: np.ndarray
                        ) -> Tuple[List[Any], Optional[np.ndarray]]:
        """Pin ``tokens`` (a whole number of pages) in the index: reuse
        resident blocks, allocate pages for the rest, refcount +1 on the
        full chain.  Returns ``(nodes, page_row)`` — ``page_row`` is the
        fill program's page table when any block needs computing,
        ``None`` when the head was fully resident."""
        scfg = self.scfg
        ps = scfg.page_size
        F = len(tokens) // ps
        nodes: List[Any] = []
        kids = self.index.children
        parent = None
        b = 0
        while b < F:
            child = kids.get(tokens[b * ps:(b + 1) * ps].tobytes())
            if child is None:
                break
            nodes.append(child)
            parent, kids = child, child.children
            b += 1
        n_new = F - b
        if self.reserved + self.index.live_pages + n_new > scfg.pool_pages:
            raise RuntimeError(
                f"cannot pin a {F}-page prefix: {n_new} new pages needed "
                f"but reservations + pinned/live shared pages leave no "
                f"room in the {scfg.pool_pages}-page pool — raise "
                f"num_pages or release other prefixes")
        for bb in range(b, F):
            node, _ = self.index.insert(
                parent, tokens[bb * ps:(bb + 1) * ps], self._take_page())
            nodes.append(node)
            parent = node
        for nd in nodes:
            self.index.acquire(nd)
        page_row = None
        if n_new:
            page_row = np.zeros(scfg.max_pages, np.int32)
            for bb, nd in enumerate(nodes):
                page_row[bb] = nd.page
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.pages_in_use())
        return nodes, page_row

    def release_prefix(self, nodes: List[Any]) -> None:
        for nd in nodes:
            self.free_pages.extend(self.index.release(nd))

    def clear_programs(self) -> None:
        super().clear_programs()
        self._prefix_fills.clear()

    def prefix_fill_step(self, rows: int) -> Callable:
        fn = self._prefix_fills.get(rows)
        if fn is None:
            fn = loops.build_prefix_fill_step(
                self.cfg, self.mesh, self.scfg, self._ap, self._ac,
                prompt_rows=rows)
            self._prefix_fills[rows] = fn
        return fn

    # --- page bookkeeping ---------------------------------------------

    def _take_page(self) -> int:
        """One free page — from the free list, else by evicting a
        retained (refcount-zero) prefix page.  The admission accounting
        (reservations + live shared pages ≤ pool) guarantees one of the
        two can serve every call."""
        if self.free_pages:
            return self.free_pages.pop()
        if self.prefix_on:
            page = self.index.evict_one()
            if page is not None:
                return page
        raise RuntimeError("page pool exhausted — admission reservation "
                           "accounting violated")

    def _alloc(self, i: int, target: int) -> None:
        """Grow slot ``i``'s total page count (shared head + private) to
        ``target``: pop from the free list (evicting retained prefix
        pages on pressure), write the host table row past the shared
        head, track the pool high-water mark.  The admission reservation
        guarantees every call can be served."""
        base = len(self.slot_shared[i])
        while base + len(self.slot_pages[i]) < target:
            page = self._take_page()
            self.ptab[i, base + len(self.slot_pages[i])] = page
            self.slot_pages[i].append(page)
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.pages_in_use())

    def pages_in_use(self) -> int:
        """Pool pages taken by slots and pinned prefixes — free pages and
        retained (refcount-zero, evictable) prefix pages excluded."""
        return self.scfg.pool_pages - len(self.free_pages) \
            - (self.index.retained_pages if self.prefix_on else 0)

    def _ensure(self, i: int) -> None:
        """Cover the next decode chunk (allocation happens at chunk
        boundaries, never mid-scan), capped at the slot's reservation.
        ``chunk_tokens`` is the chunk's commit upper bound — under
        speculation the drafted/verify rows *beyond* any commit need no
        real page (their writes land in the null page and their reads
        only cost acceptance, never correctness)."""
        scfg = self.scfg
        self._alloc(i, min(
            -(-min(self.slot_rows[i] + scfg.chunk_tokens,
                   scfg.max_len) // scfg.page_size),
            self.slot_need[i]))

    def _trim(self, i: int) -> None:
        """Return pages allocated past slot ``i``'s committed rows (the
        speculative chunk boundary: low acceptance leaves the lazy
        chunk-cover allocation ahead of the commit point — hand those
        pages back so waiting requests can admit; the next chunk's
        ``_ensure`` re-covers)."""
        target = max(-(-self.slot_rows[i] // self.scfg.page_size), 1)
        base = len(self.slot_shared[i])
        while base + len(self.slot_pages[i]) > target and self.slot_pages[i]:
            page = self.slot_pages[i].pop()
            self.ptab[i, base + len(self.slot_pages[i])] = 0
            self.free_pages.append(page)

    def _view_pages(self, live_rows: int) -> Optional[int]:
        """Decode view bucket covering ``live_rows`` cache rows."""
        scfg = self.scfg
        if not scfg.page_view_chunk:
            return None
        vc = scfg.page_view_chunk
        pages = -(-live_rows // scfg.page_size)
        vp = -(-pages // vc) * vc
        return min(vp, scfg.max_pages)

    # --- chunk lifecycle ----------------------------------------------

    def begin_chunk(self, live_slots: List[int]) -> Tuple[Callable, Tuple]:
        # the attention view must cover every row the chunk can WRITE:
        # commits (chunk_tokens) plus, under speculation, the verify
        # block's uncommitted tail (spec_k rows) — otherwise a live
        # slot's block write would clip into view-interior pages it
        # still attends to
        scfg = self.scfg
        span = scfg.chunk_tokens + scfg.spec_k
        live_rows = 0
        for i in live_slots:
            self._ensure(i)
            live_rows = max(live_rows,
                            min(self.slot_rows[i] + span, scfg.max_len))
        loop = self._decode_loop(self._view_pages(live_rows))
        return loop, (jnp.asarray(self.ptab),)

    def note_commit(self, slot: int) -> None:
        # pos advances at most once per emitted token
        self.slot_rows[slot] += 1

    def end_chunk(self, live_slots: List[int]) -> None:
        if self.scfg.spec:
            # chunk boundary: pages the chunk covered but the commits
            # never reached go back to the pool
            for i in live_slots:
                self._trim(i)

    def retire(self, slot: int) -> None:
        """Return slot's private pages to the pool, decref its shared
        pages (refcount zero retains them warm in the index — they
        rejoin the pool only through eviction) and null its table row —
        the next chunk's table refresh redirects the dead slot's
        residual writes to the garbage page, so recycled pages can't be
        corrupted."""
        for nd in self.slot_shared[slot]:
            self.free_pages.extend(self.index.release(nd))
        self.slot_shared[slot] = []
        self.slot_plan[slot] = (0, 0, 0)
        self.free_pages.extend(reversed(self.slot_pages[slot]))
        self.slot_pages[slot] = []
        self.reserved -= self.slot_resv[slot]
        self.slot_resv[slot] = 0
        self.slot_need[slot] = 0
        self.slot_rows[slot] = 0
        self.ptab[slot] = 0


def make_backend(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                 abstract_params: Any, abstract_draft: Any,
                 abstract_cache: Any, stats: Dict[str, Any]
                 ) -> CacheBackend:
    if int(dict(mesh.shape).get("model", 1)) > 1:
        # lazy import: serving.sharded imports this module for the base
        # classes, so the dependency must stay one-way at import time
        from repro.serving.sharded import make_sharded_backend
        return make_sharded_backend(cfg, mesh, scfg, abstract_params,
                                    abstract_draft, abstract_cache, stats)
    kind = PagedBackend if scfg.paged else MonoBackend
    return kind(cfg, mesh, scfg, abstract_params, abstract_draft,
                abstract_cache, stats)
