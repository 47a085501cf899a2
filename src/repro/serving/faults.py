"""Fault tolerance: the engine's failure-handling layer.

:class:`FaultTolerance` is the mixin :class:`~repro.serving.api.Engine`
inherits its robustness machinery from — deadline enforcement,
priority preemption, numeric-fault quarantine, kernel-failure retry on
the degraded (reference-dispatch) plans, and bounded fetch retry.  It
lives in its own module so the scheduler (``api.py``) stays about
scheduling; everything here is about what happens when a tick goes
wrong.

The failure policy, in one place:

  * **deadline** — at the first chunk boundary past ``deadline_ms`` the
    request ends ``TIMED_OUT``, queued or running (running slots are
    frozen + retired exactly like a cancel).
  * **pool exhaustion** — when the queue head cannot reserve pages, the
    lowest-priority running slot *strictly below* the head's priority
    is preempted: frozen, retired (its shared prompt pages drop to
    refcount zero in the prefix index — warm), and re-queued
    ``PREEMPTED``.  Re-admission prefills the effective prompt at the
    exact original width (``rows0 + emitted``), so the warm pages line
    up and only the suffix is recomputed.
  * **non-finite tokens** — the per-chunk fetched block is checked on
    the host; a poisoned slot's column is cleared (its chunk tokens are
    discarded, never surfaced), the slot quarantined, and the engine
    drops to ref dispatch.  One retry per request; a second fault ends
    it ``FAILED``.
  * **raising dispatch** — a decode-chunk invocation that raises flips
    the engine degraded, re-traces the backend's programs on the ref
    plans, and retries the chunk once.  The failure must surface
    *before* the jitted loop consumes its donated buffers (the chaos
    harness honors this; a genuine mid-execution fault on the retry
    propagates — that is not a transient).
  * **fetch errors** — the single device→host transfer is retried up to
    twice; if every attempt fails the chunk is lost and every live slot
    is quarantined.

Every path ends with the affected request in a terminal status or back
in the queue — ``step()`` never raises on an injected fault, and
``engine.audit()`` (delegating to :mod:`repro.serving.chaos`) can check
the structural invariants after every tick.
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels import dispatch
from repro.serving.state import Request, RequestStatus


class FaultTolerance:
    """Mixin carrying the engine's failure handling (see the module
    docstring for the policy).  Expects the host class to provide the
    scheduler state (``_slot_req``, ``queue``, ``_backend``, ``_stats``,
    ``scfg``, ``_finish``, ``_freeze_slot``, ...)."""

    # --- deadlines ----------------------------------------------------

    def _apply_deadlines(self) -> None:
        """Chunk-boundary deadline enforcement: queued or running, a
        request past its ``deadline_ms`` ends ``TIMED_OUT`` (running
        slots are frozen + retired exactly like a cancel)."""
        now = time.perf_counter()
        for i, r in enumerate(self._slot_req):
            if r is not None and r.past_deadline(now):
                self._freeze_slot(i)
                self._stats["timeouts"] += 1
                self._finish(r, i, RequestStatus.TIMED_OUT, now)
        for r in [r for r in self.queue if r.past_deadline(now)]:
            self.queue.remove(r)
            self._stats["timeouts"] += 1
            self._finish(r, None, RequestStatus.TIMED_OUT, now)

    # --- preemption / quarantine --------------------------------------

    def _evict_slot(self, i: int) -> Request:
        """Freeze + retire slot ``i`` and detach its request (shared
        prompt pages drop to refcount zero in the prefix index — warm
        for the re-admission's suffix-only prefill)."""
        r = self._slot_req[i]
        self._freeze_slot(i)
        self._slot_req[i] = None
        self._backend.retire(i)
        r.slot = None
        return r

    def _requeue(self, r: Request, now: float) -> None:
        """Send an evicted request back to the queue as ``PREEMPTED`` —
        or finish it if it has nothing left to decode."""
        if r.remaining_new <= 0 or (r.resume_rows or 0) >= self.scfg.max_len:
            self._finish(r, None, RequestStatus.DONE, now)
            return
        r.set_status(RequestStatus.PREEMPTED)
        self.queue.append(r)

    def _preempt(self, i: int, now: float) -> None:
        r = self._evict_slot(i)
        r.preempts += 1
        self._stats["preemptions"] += 1
        self._requeue(r, now)

    def _victim_slot(self, priority: int) -> Optional[int]:
        """Lowest-priority running slot strictly below ``priority`` —
        ties evict the youngest (least sunk work); ``None`` if every
        running request is at or above the requester's level."""
        best = None
        for i, r in enumerate(self._slot_req):
            if r is None or r.priority >= priority:
                continue
            if best is None or (r.priority, -r.uid) < (
                    self._slot_req[best].priority,
                    -self._slot_req[best].uid):
                best = i
        return best

    def _quarantine(self, i: int, now: float) -> None:
        """Pull slot ``i`` out of the batch after a numeric/device fault:
        the chunk's tokens for it are discarded, its pages retired, and
        the request re-queued to retry once on the degraded (ref) plans.
        A second fault ends it ``FAILED`` — never poisons the batch."""
        r = self._slot_req[i]
        if r is None:
            return
        r.faults += 1
        self._evict_slot(i)
        if r.faults > 1:
            self._finish(r, None, RequestStatus.FAILED, now)
            return
        self._requeue(r, now)

    # --- guarded chunk execution --------------------------------------

    def _invoke_loop(self, loop, args):
        """The compiled-dispatch seam: every decode-chunk invocation
        funnels through here so the chaos harness can inject kernel
        failures per engine (and ``_run_chunk`` can retry on the
        degraded plans)."""
        return loop(*args)

    def _fetch_block(self, tree) -> Optional[tuple]:
        """The single device→host transfer, with bounded retry: a
        transient fetch error (counted in ``fetch_errors``) is retried
        up to twice; if every attempt fails the chunk's tokens are lost
        and the caller quarantines the live slots."""
        with TraceAnnotation("serve.fetch"):
            for _ in range(3):
                try:
                    out = self._device_fetch(tree)
                except Exception:
                    self._stats["fetch_errors"] += 1
                    continue
                self.sync_count += 1
                return out
        return None

    def _loop_args(self, key, extra) -> tuple:
        if self.scfg.spec:
            return (self.params, self.draft_params, self._cache,
                    self._state, key) + tuple(extra)
        return (self.params, self._cache, self._state,
                jnp.asarray(self._temps), key) + tuple(extra)

    def _launch_chunk(self, live, loop, key, extra):
        """Invoke one decode chunk; returns the loop's outputs, still on
        the device.  A raising dispatch flips the engine into degraded
        (ref) mode and retries the chunk once on the re-traced loop; a
        retry failure propagates (the fault is not transient)."""
        try:
            return self._invoke_loop(loop, self._loop_args(key, extra))
        except Exception as e:
            self._stats["kernel_failures"] += 1
            self._enter_degraded(f"decode dispatch raised: {e!r}")
            loop, extra = self._backend.begin_chunk(live)
            return self._invoke_loop(loop, self._loop_args(key, extra))

    def _fetch_chunk(self, out):
        """Adopt a launched chunk's cache and state and make the single
        device→host fetch of its token block — the speculative loop's
        drafted/accepted counters ride in the same transfer.  Returns
        ``(tokens, emitted, done)``, or ``None`` when the fetch itself
        is unrecoverable."""
        if self.scfg.spec:
            cache, state, tokens, emitted, dr, ac = out
            fetched = self._fetch_block(
                (tokens, emitted, state["done"], dr, ac))
        else:
            cache, state, tokens, emitted = out
            fetched = self._fetch_block((tokens, emitted, state["done"]))
        self._cache, self._state = cache, state
        if fetched is None:
            return None
        if self.scfg.spec:
            blk, emit, done, dr, ac = fetched
            if np.all(np.isfinite([float(dr), float(ac)])):
                self._stats["drafted"] += int(dr)
                self._stats["accepted"] += int(ac)
            return blk, emit, done
        return fetched

    def _guard_block(self, blk, emit):
        """Numeric-fault guard on the fetched token block: a slot whose
        emitted tokens contain non-finite values is quarantined (its
        column cleared so ``_collect`` never sees the poisoned tokens)
        and the engine drops to the reference dispatch plans."""
        if not np.issubdtype(np.asarray(blk).dtype, np.floating):
            return blk, emit
        bad = np.any(~np.isfinite(np.asarray(blk)) & (emit != 0), axis=0)
        if not bad.any():
            return blk, emit
        emit = np.array(emit)
        now = time.perf_counter()
        for i in np.nonzero(bad)[0]:
            if self._slot_req[int(i)] is None:
                continue
            emit[:, i] = False
            self._stats["numeric_faults"] += 1
            self._quarantine(int(i), now)
        self._enter_degraded("non-finite tokens in the fetched block")
        return blk, emit

    def _enter_degraded(self, reason: str) -> None:
        """Drop every dispatch decision to the reference (``ref``) path
        and re-trace the backend's compiled programs.  Idempotent; the
        override outranks ``REPRO_DISPATCH_MODE`` — a runtime fault
        response beats static configuration."""
        if self.degraded:
            return
        self.degraded = True
        self._clean_chunks = 0
        warnings.warn(
            f"engine entering degraded (ref-dispatch) mode: {reason}",
            RuntimeWarning, stacklevel=2)
        dispatch.set_mode_override("ref")
        self._backend.clear_programs()

    def _fault_count(self) -> int:
        """Monotone tally of every fault class a chunk can hit — the
        before/after delta tells ``step()`` whether a chunk was clean."""
        return (self._stats["numeric_faults"]
                + self._stats["kernel_failures"]
                + self._stats["fetch_errors"])

    def _note_chunk_health(self, had_fault: bool) -> None:
        """Degraded-mode recovery: after ``degraded_recover_chunks``
        consecutive fault-free chunks, clear the ref-dispatch override
        and re-trace back onto the compiled plans (counted in
        ``degraded_recoveries``).  A fault during probation resets the
        streak; ``degraded_recover_chunks=0`` keeps PR 7's one-way
        behavior."""
        if not self.degraded or not self.scfg.degraded_recover_chunks:
            return
        self._clean_chunks = 0 if had_fault else self._clean_chunks + 1
        if self._clean_chunks < self.scfg.degraded_recover_chunks:
            return
        self.degraded = False
        self._clean_chunks = 0
        self._stats["degraded_recoveries"] += 1
        warnings.warn(
            "engine leaving degraded mode: "
            f"{self.scfg.degraded_recover_chunks} consecutive clean "
            "chunks — re-tracing on the compiled dispatch plans",
            RuntimeWarning, stacklevel=2)
        dispatch.set_mode_override(None)
        self._backend.clear_programs()

    # --- invariants ---------------------------------------------------

    def audit(self) -> Dict[str, Any]:
        """Check the engine's structural invariants (page refcount
        conservation, page-table/pool consistency, request state-machine
        legality).  Returns a report dict; raises
        :class:`~repro.serving.chaos.AuditError` on violation.  The
        chaos harness runs this after every step."""
        from repro.serving.chaos import audit_engine
        return audit_engine(self)
