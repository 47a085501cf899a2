"""Per-request and per-slot serving state, sampling, and the engine's
single device→host fetch point.

``Request`` is the host-side record of one submission (id, arrival
time, TTFT, output tokens, cancel flag); the *device*-side decode state
is the 4-array dict built by :func:`init_decode_state` that the jitted
loops carry between chunks.  Sampling helpers live here too because the
prefill steps and the decode loops share them.
"""

from __future__ import annotations

import dataclasses
import enum
import time
import warnings
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class RequestStatus(enum.Enum):
    QUEUED = "queued"               # submitted, not yet in a slot
    RUNNING = "running"             # prefilled into a slot, decoding
    DONE = "done"                   # finished (EOS / budget / capacity)
    CANCELLED = "cancelled"         # cancel() took effect
    TIMED_OUT = "timed_out"         # deadline_ms elapsed (queued or live)
    PREEMPTED = "preempted"         # evicted from its slot, re-queued
    REJECTED = "rejected"           # bounded admission queue was full
    FAILED = "failed"               # second numeric/device fault


#: States a request can never leave.  PREEMPTED is *not* terminal — a
#: preempted request sits back in the queue and re-admits.
TERMINAL_STATUSES = frozenset({
    RequestStatus.DONE, RequestStatus.CANCELLED, RequestStatus.TIMED_OUT,
    RequestStatus.REJECTED, RequestStatus.FAILED,
})

#: The request state machine — ``engine.audit()`` checks every recorded
#: history against this map.
LEGAL_TRANSITIONS = {
    RequestStatus.QUEUED: {RequestStatus.RUNNING, RequestStatus.CANCELLED,
                           RequestStatus.TIMED_OUT, RequestStatus.REJECTED},
    RequestStatus.PREEMPTED: {RequestStatus.RUNNING,
                              RequestStatus.CANCELLED,
                              RequestStatus.TIMED_OUT},
    RequestStatus.RUNNING: {RequestStatus.DONE, RequestStatus.CANCELLED,
                            RequestStatus.TIMED_OUT,
                            RequestStatus.PREEMPTED, RequestStatus.FAILED},
    RequestStatus.DONE: set(), RequestStatus.CANCELLED: set(),
    RequestStatus.TIMED_OUT: set(), RequestStatus.REJECTED: set(),
    RequestStatus.FAILED: set(),
}


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (L,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # --- request-level API v2 fields -----------------------------------
    status: RequestStatus = RequestStatus.QUEUED
    temperature: Optional[float] = None   # None → ServeConfig.temperature
    stream: bool = False
    cancel_requested: bool = False
    slot: Optional[int] = None            # slot while RUNNING
    arrival_s: float = dataclasses.field(default_factory=time.perf_counter)
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None
    # --- fault tolerance (PR 7) ----------------------------------------
    priority: int = 0               # higher admits first / preempts lower
    deadline_ms: Optional[float] = None   # wall budget from arrival
    rows0: Optional[int] = None     # prompt rows at FIRST admission
    faults: int = 0                 # numeric/device faults charged to us
    preempts: int = 0               # times evicted from a slot
    history: List[RequestStatus] = dataclasses.field(default_factory=list)

    def __post_init__(self) -> None:
        self.history.append(self.status)

    def set_status(self, status: RequestStatus) -> None:
        """Record a state transition (legality is *audited*, not
        enforced — the engine must never raise mid-tick)."""
        self.status = status
        self.history.append(status)

    @property
    def ttft_s(self) -> Optional[float]:
        """Time-to-first-token in seconds (queue wait + prefill + the
        first chunk), or ``None`` before any token arrived."""
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    # --- resumption (preempt / quarantine → requeue) -------------------

    @property
    def eff_prompt(self) -> np.ndarray:
        """The prompt a re-admission prefills: original prompt plus every
        token already emitted (the continuation context)."""
        if not self.out:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out, np.int32)])

    @property
    def remaining_new(self) -> int:
        """Token budget left after what was already emitted."""
        return self.max_new - len(self.out)

    @property
    def resume_rows(self) -> Optional[int]:
        """Exact prefill width for a re-admission: the rows of the first
        admission plus one per emitted token — no re-bucketing, so the
        padded layout (and any published prefix pages) line up and the
        greedy continuation stays on the original token stream.  ``None``
        until first admitted."""
        if self.rows0 is None:
            return None
        return self.rows0 + len(self.out)

    def past_deadline(self, now: float) -> bool:
        return (self.deadline_ms is not None
                and (now - self.arrival_s) * 1e3 >= self.deadline_ms)


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One emitted token, as returned by ``Engine.step()``."""
    uid: int
    token: int
    index: int                      # position in the request's output
    final: bool                     # last token of this request


def _fresh_stats() -> Dict[str, Any]:
    return {"chunk_s": [], "chunk_pages": [], "prefills": 0,
            "peak_pages": 0, "admission_waits": 0,
            "drafted": 0, "accepted": 0,
            "prefix_hits": 0, "shared_pages": 0, "cow_copies": 0,
            "timeouts": 0, "rejections": 0, "preemptions": 0,
            "numeric_faults": 0, "kernel_failures": 0, "fetch_errors": 0,
            "degraded_recoveries": 0, "restarts": 0}


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Typed snapshot of the engine's serving counters — what
    ``Engine.stats()`` returns.

    Replaces the ad-hoc dict/attribute surface (``stats["peak_pages"]``,
    ``cache_bytes()``, ``acceptance_rate()``): one frozen record with
    every counter the benchmarks and the launcher read, plus the
    prefix-sharing tallies.  ``chunk_s`` / ``chunk_pages`` hold one
    entry per decode chunk: its wall time (launch to fetched block) and
    the pool pages in use while it ran (paged; 0 for the monolithic
    cache) — against ``ServeConfig.pool_pages`` the share of the pool
    that holds live rows.
    """
    chunk_s: List[float]            # wall seconds per decode chunk
    chunk_pages: List[int]          # paged: pool pages in use per chunk
    prefills: int                   # prompt prefills dispatched
    peak_pages: int                 # paged: pool high-water mark
    admission_waits: int            # paged: admissions deferred for pages
    drafted: int                    # spec: tokens drafted
    accepted: int                   # spec: drafted tokens accepted
    prefix_hits: int                # admissions that mapped shared pages
    shared_pages: int               # pages mapped read-only at admission
    cow_copies: int                 # copy-on-write page copies
    sync_count: int                 # device→host transfers
    cache_bytes: int                # allocated KV/state cache footprint
    acceptance_rate: float          # accepted / drafted (0 if no spec)
    # --- fault tolerance (PR 7) ----------------------------------------
    timeouts: int = 0               # requests past deadline_ms
    rejections: int = 0             # bounced off the bounded queue
    preemptions: int = 0            # slots evicted for a higher priority
    numeric_faults: int = 0         # non-finite fetched blocks (per slot)
    kernel_failures: int = 0        # decode dispatch raised, ref retry
    fetch_errors: int = 0           # device→host fetch attempts that raised
    degraded: bool = False          # engine re-planned on ref dispatch
    # --- crash safety (PR 8) -------------------------------------------
    degraded_recoveries: int = 0    # degraded → compiled re-trace events
    restarts: int = 0               # supervised crash/hang restorations


def init_decode_state(slots: int) -> Dict[str, Array]:
    """All-free decode state: every slot done, no budget, pos 0."""
    return {
        "tok": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "done": jnp.ones((slots,), bool),
        "left": jnp.zeros((slots,), jnp.int32),
    }


def sample_token(logits: Array, key: Array, temperature: float) -> Array:
    """(B, V) → (B,) int32 at one static temperature (0 → greedy)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def _slot_keys(key: Array, n: int) -> Array:
    """(n,) independent keys via per-slot ``fold_in``."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))


def sample_token_folded(logits: Array, key: Array,
                        temperature: float) -> Array:
    """(B, V) → (B,) with a per-slot ``fold_in`` key discipline.

    The speculative path samples at many (step, slot, draft-position)
    sites whose *consumption* depends on data (how many drafts a slot
    accepts).  A split-per-call stream would let one slot's acceptance
    shift every later draw; folding the key per slot (callers fold per
    step and draft position first) pins each draw to its coordinates, so
    the same seed yields the same tokens with and without speculation at
    temperature 0 — and a reproducible stream at temperature > 0.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = _slot_keys(key, logits.shape[0])
    return jax.vmap(
        lambda k, l: jax.random.categorical(k, l / temperature)
    )(keys, logits).astype(jnp.int32)


def sample_token_slots(logits: Array, key: Array, temps: Array) -> Array:
    """(B, V) → (B,) with a *per-slot* temperature vector ``temps``.

    Slots with ``temps[i] <= 0`` take the argmax (greedy — bit-identical
    to :func:`sample_token` at temperature 0), the rest draw from their
    own tempered distribution under the per-slot ``fold_in`` discipline,
    so a batch can mix greedy and sampled requests without either
    perturbing the other's stream.
    """
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = _slot_keys(key, logits.shape[0])
        safe = jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.vmap(
            lambda k, l: jax.random.categorical(k, l)
        )(keys, logits / safe).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)


def _slot_uniform(key: Array, n: int) -> Array:
    """(n,) uniforms, one per slot, via the same fold discipline."""
    keys = _slot_keys(key, n)
    return jax.vmap(lambda k: jax.random.uniform(k))(keys)


def _device_fetch(tree: Any) -> Any:
    """The engine's single device→host transfer point.

    Every token/state readback goes through here (resolved through the
    deprecated ``repro.serving.engine`` module so existing tests that
    monkeypatch ``engine._device_fetch`` still intercept every sync).
    """
    return jax.device_get(tree)


class _StatsAccessor:
    """``engine.stats`` — callable (v2) and, for one release, still
    subscriptable like the old raw dict.

    ``engine.stats()`` returns the typed :class:`EngineStats` snapshot;
    ``engine.stats["peak_pages"]`` keeps working with a
    ``DeprecationWarning`` (the v1 surface).  The engine and backends
    mutate the underlying dict directly (``engine._stats``)."""

    def __init__(self, engine: Any):
        self._engine = engine

    def __call__(self) -> EngineStats:
        e = self._engine
        d = e._stats
        return EngineStats(
            chunk_s=list(d["chunk_s"]),
            chunk_pages=list(d["chunk_pages"]),
            prefills=d["prefills"], peak_pages=d["peak_pages"],
            admission_waits=d["admission_waits"], drafted=d["drafted"],
            accepted=d["accepted"], prefix_hits=d["prefix_hits"],
            shared_pages=d["shared_pages"], cow_copies=d["cow_copies"],
            sync_count=e.sync_count, cache_bytes=e._cache_nbytes(),
            acceptance_rate=d["accepted"] / max(d["drafted"], 1),
            timeouts=d["timeouts"], rejections=d["rejections"],
            preemptions=d["preemptions"],
            numeric_faults=d["numeric_faults"],
            kernel_failures=d["kernel_failures"],
            fetch_errors=d["fetch_errors"],
            degraded=bool(getattr(e, "degraded", False)),
            degraded_recoveries=d["degraded_recoveries"],
            restarts=d["restarts"])

    def __getitem__(self, key: str) -> Any:
        warnings.warn(
            "dict-style engine.stats[...] access is deprecated; call "
            "engine.stats() for a typed EngineStats snapshot",
            DeprecationWarning, stacklevel=2)
        return self._engine._stats[key]

    def __contains__(self, key: str) -> bool:
        return key in self._engine._stats

    def __repr__(self) -> str:
        return f"_StatsAccessor({self._engine._stats!r})"


class RequestHandle:
    """Caller-side view of one submitted request.

    Iterating the handle yields its tokens in emission order, calling
    ``engine.step()`` whenever the buffered stream runs dry — so
    ``for tok in handle:`` streams tokens as the scheduler produces
    them, interleaved with any other live requests.
    """

    def __init__(self, engine: Any, req: Request):
        self._engine = engine
        self._req = req

    @property
    def uid(self) -> int:
        return self._req.uid

    @property
    def status(self) -> RequestStatus:
        return self._req.status

    @property
    def done(self) -> bool:
        return self._req.status in TERMINAL_STATUSES

    @property
    def slot(self) -> Optional[int]:
        return self._req.slot

    @property
    def tokens(self) -> List[int]:
        """Tokens emitted so far (a copy; safe to mutate)."""
        return list(self._req.out)

    @property
    def ttft_s(self) -> Optional[float]:
        return self._req.ttft_s

    def cancel(self) -> None:
        self._engine.cancel(self)

    def result(self) -> List[int]:
        """Drive the engine until this request finishes; returns its
        full output."""
        for _ in self:
            pass
        return self.tokens

    def __iter__(self) -> Iterator[int]:
        i = 0
        stalls = 0
        while True:
            out = self._req.out
            while i < len(out):
                yield out[i]
                i += 1
            if self.done:
                return
            events = self._engine.step()
            if (not events and not self.done
                    and self._req.status in (RequestStatus.QUEUED,
                                             RequestStatus.PREEMPTED)
                    and not self._engine.num_live):
                # tolerate transient stalls (chaos pool pressure, a pin
                # about to drop) before declaring the engine wedged
                stalls += 1
                if stalls > 8:
                    raise RuntimeError(
                        f"engine made no progress on request {self.uid} "
                        "(queued, no live slots, empty tick)")
            else:
                stalls = 0

    def __repr__(self) -> str:
        return (f"RequestHandle(uid={self.uid}, "
                f"status={self._req.status.value}, "
                f"tokens={len(self._req.out)})")
