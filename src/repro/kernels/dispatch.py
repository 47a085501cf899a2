"""Unified sparse-kernel dispatch: registry + backend fallback + autotune.

This is the software half of the paper's co-design move: the FPGA picks a
functional unit (USSA / SSSA / CSA) to match the sparsity pattern of each
layer; here a registry picks a Pallas kernel (``nm_spmm`` / ``bsr_matmul``
/ ``csa_matmul`` / ``lookahead_decode``) — or the pure-jnp reference — from
a :class:`SparsityDescriptor` derived from the packed weight.  Callers
(``core.sparse_linear``, the model layers, ``serving.engine``, every
``benchmarks/bench_*``) go through :func:`sparse_matmul` and never name a
kernel directly.

Three execution modes, resolved per call:

  * ``compiled``  — real Pallas lowering; only when a TPU backend is
                    present.  Block sizes come from the autotune cache.
  * ``interpret`` — ``pallas_call(interpret=True)``; exercises the exact
                    kernel logic on CPU (slow: tests/debugging only).
  * ``ref``       — the jnp oracle in ``kernels/ref.py``; the CPU
                    production path (same FLOP/byte structure as the
                    kernel, compiles under XLA anywhere).

``impl`` accepted by every entry point:
  ``auto``   → compiled on TPU, ref elsewhere (suite runs green on CPU);
  ``kernel`` → compiled on TPU, interpret elsewhere;
  ``ref`` / ``interpret`` / ``compiled`` → forced.
``REPRO_DISPATCH_MODE`` overrides the resolution globally (CI uses it).

Autotune: for the compiled path, a small sweep over ``bm``/``bkc``
candidates is timed once per ``(kernel, M, K, N, dtype, pattern)`` key and
persisted to a JSON cache (``REPRO_AUTOTUNE_CACHE`` or
``<repo>/.autotune.json``, see ``core.caches``), so steady-state dispatch
is a dict lookup.  ``ref`` mode never sweeps; ``interpret`` sweeps only
when asked (tests use it to exercise the machinery on tiny shapes).  A
compiled sweep in which no candidate runs raises: there is no silent
default on the chip.

Every per-call degradation to the jnp oracle is counted
(:func:`fallback_count`), so a run can prove it never left the kernels.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.caches import autotune_cache_path
from repro.core.sparsity import (BlockSparsePack, CombinedPack, LookaheadPack,
                                 NMPack)
from repro.kernels import ref as _ref

Array = jax.Array

PACK_TYPES = (BlockSparsePack, NMPack, CombinedPack, LookaheadPack)

MODES = ("compiled", "interpret", "ref")
IMPLS = ("auto", "kernel") + MODES


# ---------------------------------------------------------------------------
# Sparsity descriptor — what the registry selects on
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparsityDescriptor:
    """Structural summary of a weight: the dispatch key.

    ``pattern`` is the human-readable sparsity signature used in cache
    keys and logs: ``"2:4g128"``, ``"bsr128x128d0.50"``, ``"dense"``, …
    For the paged-attention family the descriptor summarizes the *cache*
    geometry instead of a weight: ``K`` is the logical KV view
    (``max_pages * page_size``), ``N`` the head dim, ``g`` the page size
    and ``bk`` the page count — so plan/autotune keys are page-shaped.
    """
    kind: str          # dense | block | nm | combined | lookahead | paged
    K: int
    N: int
    dtype: str
    n: Optional[int] = None        # N:M pattern (nm / combined)
    m: Optional[int] = None
    g: Optional[int] = None        # column-group width (nm)
    bk: Optional[int] = None       # skip-tile geometry (block / combined)
    bn: Optional[int] = None
    density: Optional[float] = None  # non-zero tile fraction (block/combined)

    @property
    def pattern(self) -> str:
        if self.kind == "nm":
            return f"{self.n}:{self.m}g{self.g}"
        if self.kind == "block":
            return f"bsr{self.bk}x{self.bn}d{self.density:.2f}"
        if self.kind == "combined":
            return (f"csa{self.bk}x{self.bn}d{self.density:.2f}"
                    f"+{self.n}:{self.m}")
        if self.kind == "paged":
            # ``n`` carries the shard-local KV head count when the pool is
            # head-parallel (absent on single-device keys, so the cache
            # stays backward compatible)
            heads = f"h{self.n}" if self.n else ""
            return f"paged{self.g}x{self.bk}{heads}"
        return self.kind

    @classmethod
    def of(cls, weight: Any) -> "SparsityDescriptor":
        """Build the descriptor for a dense array or any pack."""
        if isinstance(weight, NMPack):
            return cls(kind="nm", K=weight.K, N=weight.N,
                       dtype=str(weight.values.dtype),
                       n=weight.n, m=weight.m, g=weight.g)
        if isinstance(weight, BlockSparsePack):
            return cls(kind="block", K=weight.K, N=weight.N,
                       dtype=str(weight.values.dtype),
                       bk=weight.bk, bn=weight.bn,
                       density=_tile_density(weight))
        if isinstance(weight, CombinedPack):
            return cls(kind="combined", K=weight.K, N=weight.N,
                       dtype=str(weight.values.dtype),
                       n=weight.n, m=weight.m, bk=weight.bk, bn=weight.bn,
                       density=_tile_density(weight))
        if isinstance(weight, LookaheadPack):
            return cls(kind="lookahead", K=weight.K, N=weight.N,
                       dtype=str(weight.enc.dtype))
        if hasattr(weight, "ptab") and hasattr(weight, "lens"):
            # kernels.paged_attention.PagedKV (duck-typed so this module
            # stays pallas-import-free): descriptor of the cache geometry
            ps, mp = weight.page_size, weight.max_pages
            return cls(kind="paged", K=mp * ps, N=weight.head_dim,
                       dtype=str(weight.k.dtype), g=ps, bk=mp)
        if hasattr(weight, "shape") and len(weight.shape) >= 2:
            return cls(kind="dense", K=weight.shape[-2], N=weight.shape[-1],
                       dtype=str(weight.dtype))
        raise TypeError(f"cannot describe weight of type {type(weight)}")


def _tile_density(pack) -> float:
    """Non-zero-tile fraction without forcing device sync on traced packs."""
    try:
        import numpy as np
        total = (pack.K // pack.bk) * (pack.N // pack.bn)
        return float(np.asarray(pack.counts).sum()) / max(total, 1)
    except Exception:            # abstract/traced counts: geometry bound
        return min(1.0, pack.max_nnz / max(pack.K // pack.bk, 1))


# ---------------------------------------------------------------------------
# Mode resolution — the CPU-fallback policy in one place
# ---------------------------------------------------------------------------

def has_tpu() -> bool:
    return jax.default_backend() == "tpu"


_MODE_OVERRIDE: Optional[str] = None


def set_mode_override(mode: Optional[str]) -> Optional[str]:
    """Force every subsequent mode resolution to ``mode`` (the serving
    engine's degraded path pins ``"ref"`` after a kernel/numeric fault);
    ``None`` restores normal resolution.  Wins over both ``impl`` and
    the ``REPRO_DISPATCH_MODE`` env override — a runtime fault response
    must beat static configuration.  Returns the previous override so
    callers (tests, chaos detach) can restore it."""
    global _MODE_OVERRIDE
    if mode is not None and mode not in MODES:
        raise ValueError(f"mode override {mode!r} not in {MODES}")
    old, _MODE_OVERRIDE = _MODE_OVERRIDE, mode
    return old


def mode_override() -> Optional[str]:
    return _MODE_OVERRIDE


def resolve_mode(impl: str = "auto") -> str:
    """impl → concrete execution mode, honoring REPRO_DISPATCH_MODE."""
    if _MODE_OVERRIDE is not None:
        return _MODE_OVERRIDE
    forced = os.environ.get("REPRO_DISPATCH_MODE", "")
    if forced:
        if forced not in MODES:
            raise ValueError(f"REPRO_DISPATCH_MODE={forced!r} not in {MODES}")
        return forced
    if impl not in IMPLS:
        raise ValueError(f"impl {impl!r} not in {IMPLS}")
    if impl == "auto":
        return "compiled" if has_tpu() else "ref"
    if impl == "kernel":
        return "compiled" if has_tpu() else "interpret"
    return impl


# ---------------------------------------------------------------------------
# Autotune cache — JSON-persisted (kernel, shape, dtype, pattern) → blocks
# ---------------------------------------------------------------------------

class AutotuneCache:
    """Tiny persistent map: dispatch key → {"bm": .., "bkc": .., "us": ..}.

    Load-on-first-use; every ``put`` rewrites the file (entries are rare —
    one per distinct layer geometry).  A corrupt or partially-written
    file (truncated JSON from a killed process, or a valid-JSON payload
    that isn't an object of block dicts) must never take dispatch down:
    it is ignored with one warning and rebuilt by the next ``put``.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or autotune_cache_path()
        self._data: Optional[Dict[str, dict]] = None
        self._lock = threading.Lock()
        self._warned = False

    def _warn_corrupt(self, why: str) -> None:
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"ignoring corrupt autotune cache {self.path!r} ({why}); "
                "it will be rebuilt on the next sweep",
                RuntimeWarning, stacklevel=3)

    def _load(self) -> Dict[str, dict]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    data = json.load(f)
            except OSError:
                data = {}
            except ValueError as e:           # truncated / invalid JSON
                self._warn_corrupt(str(e))
                data = {}
            if not isinstance(data, dict):
                self._warn_corrupt(
                    f"top level is {type(data).__name__}, expected object")
                data = {}
            elif any(not isinstance(v, dict) for v in data.values()):
                self._warn_corrupt("non-object entries dropped")
                data = {k: v for k, v in data.items()
                        if isinstance(v, dict)}
            self._data = data
        return self._data

    def get(self, key: str) -> Optional[dict]:
        return self._load().get(key)

    def put(self, key: str, value: dict) -> None:
        with self._lock:
            data = self._load()
            data[key] = value
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)

    def clear(self) -> None:
        with self._lock:
            self._data = {}
            try:
                os.remove(self.path)
            except OSError:
                pass

    def __len__(self) -> int:
        return len(self._load())


_CACHE = AutotuneCache()


def autotune_cache() -> AutotuneCache:
    """The process-global cache (tests swap it via ``set_autotune_cache``)."""
    return _CACHE


def set_autotune_cache(cache: AutotuneCache) -> AutotuneCache:
    global _CACHE
    old, _CACHE = _CACHE, cache
    return old


def cache_key(kernel: str, M: int, desc: SparsityDescriptor,
              mode: str) -> str:
    return (f"{kernel}|M{M}|K{desc.K}|N{desc.N}|{desc.dtype}"
            f"|{desc.pattern}|{mode}")


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One dispatchable kernel.

    ``supports(desc, M)`` — structural eligibility (format + divisibility).
    ``run(x, weight, mode, blocks)`` — execute; ``blocks`` holds tuned
    tile sizes (subset of ``tunable``).  ``nm_spmm``, the one kernel a
    row-parallel split serves, also takes ``out_dtype=`` (its partial
    sums stay f32 until the cross-shard ``psum``).
    ``candidates(desc, M)`` — autotune sweep points, list of block dicts.
    """
    name: str
    kind: str                                       # descriptor kind served
    supports: Callable[[SparsityDescriptor, int], bool]
    run: Callable[[Array, Any, str, dict], Array]
    candidates: Callable[[SparsityDescriptor, int], List[dict]]
    priority: int = 0                               # higher wins within kind


_REGISTRY: Dict[str, KernelEntry] = {}


def register(entry: KernelEntry) -> KernelEntry:
    if entry.name in _REGISTRY:
        raise ValueError(f"kernel {entry.name!r} already registered")
    _REGISTRY[entry.name] = entry
    return entry


def registry() -> Dict[str, KernelEntry]:
    return dict(_REGISTRY)


def _bm_candidates(M: int) -> List[int]:
    """Row-tile sweep points covering both serving phases.

    Prefill runs MXU-shaped M (≥ 64: the 64/128/256 ladder); decode runs
    M = slots (1–32 rows), where a 64-row tile pads 8–64× dead rows — so
    small-M geometries add matching small tiles to the grid and the
    autotune cache ends up holding rows for both phases.
    """
    if M < 64:
        out = [bm for bm in (8, 16, 32) if bm <= max(M, 8)]
        return out + [64]
    return [bm for bm in (64, 128, 256) if bm <= M]


def _bkc_for(desc: SparsityDescriptor, cap: int = 128) -> int:
    """Largest lane-aligned bkc ≤ cap (a multiple of 128 and of n that
    divides Kc), else the whole Kc — the tiles the TPU's (8, 128) block
    rule admits for the metadata and activation blocks."""
    Kc = desc.K * desc.n // desc.m
    for bkc in range(min(cap, Kc) // 128 * 128, 0, -128):
        if Kc % bkc == 0 and bkc % desc.n == 0:
            return bkc
    return Kc


def _pad_m(x: Array, bm: int) -> Tuple[Array, int]:
    M = x.shape[0]
    pad = (-M) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, M


# --- entries ---------------------------------------------------------------

def _nm_run(x, pack, mode, blocks, out_dtype=None):
    if mode == "ref":
        return _ref.nm_spmm_ref(x, pack)
    from repro.kernels.nm_spmm import nm_spmm
    bm = blocks.get("bm", 128)
    bkc = blocks.get("bkc") or _bkc_for(SparsityDescriptor.of(pack))
    xp, M = _pad_m(x, bm)
    out = nm_spmm(xp, pack, bm=bm, bkc=bkc, out_dtype=out_dtype,
                  interpret=(mode == "interpret"))
    return out[:M]


def _nm_candidates(desc, M):
    cands = []
    for bm in _bm_candidates(M):
        for cap in (128, 256, 512):
            bkc = _bkc_for(desc, cap)
            if {"bm": bm, "bkc": bkc} not in cands:
                cands.append({"bm": bm, "bkc": bkc})
    return cands


register(KernelEntry(
    name="nm_spmm", kind="nm",
    supports=lambda d, M: (d.K % d.m == 0 and d.N % d.g == 0
                           and (d.K * d.n // d.m) % d.n == 0),
    run=_nm_run, candidates=_nm_candidates))


def _bsr_run(x, pack, mode, blocks):
    if mode == "ref":
        return _ref.bsr_matmul_ref(x, pack)
    from repro.kernels.bsr_matmul import bsr_matmul
    bm = blocks.get("bm", 128)
    xp, M = _pad_m(x, bm)
    out = bsr_matmul(xp, pack, bm=bm, interpret=(mode == "interpret"))
    return out[:M]


register(KernelEntry(
    name="bsr_matmul", kind="block",
    supports=lambda d, M: d.K % d.bk == 0 and d.N % d.bn == 0,
    run=_bsr_run,
    candidates=lambda d, M: [{"bm": bm} for bm in _bm_candidates(M)]))


def _csa_run(x, pack, mode, blocks):
    if mode == "ref":
        return _ref.csa_matmul_ref(x, pack)
    from repro.kernels.csa_matmul import csa_matmul
    bm = blocks.get("bm", 128)
    xp, M = _pad_m(x, bm)
    out = csa_matmul(xp, pack, bm=bm, interpret=(mode == "interpret"))
    return out[:M]


register(KernelEntry(
    name="csa_matmul", kind="combined",
    supports=lambda d, M: d.K % d.bk == 0 and d.N % d.bn == 0,
    run=_csa_run,
    candidates=lambda d, M: [{"bm": bm} for bm in _bm_candidates(M)]))


def _lookahead_run(x, pack, mode, blocks):
    if mode == "ref":
        return _ref.lookahead_matmul_ref(x, pack)
    from repro.kernels.lookahead_decode import lookahead_matmul
    bm = blocks.get("bm", 128)
    bk = min(blocks.get("bk", 128), pack.K)
    bn = min(blocks.get("bn", 128), pack.N)
    xp, M = _pad_m(x, bm)
    out = lookahead_matmul(xp, pack, bm=bm, bk=bk, bn=bn,
                           interpret=(mode == "interpret"))
    return out[:M]


def _lookahead_candidates(desc, M):
    # full bm × bk × bn sweep (ROADMAP: widen beyond the bm-only grid);
    # 128 leads each axis so the pre-sweep default stays the MXU tile
    cands = []
    for bm in _bm_candidates(M):
        for bk in (128, 64, 256):
            if bk > desc.K:
                continue
            for bn in (128, 64, 256):
                if bn > desc.N:
                    continue
                cands.append({"bm": bm, "bk": bk, "bn": bn})
    return cands or [{"bm": _bm_candidates(M)[0],
                      "bk": min(128, desc.K), "bn": min(128, desc.N)}]


register(KernelEntry(
    name="lookahead_decode", kind="lookahead",
    supports=lambda d, M: True,
    run=_lookahead_run,
    candidates=_lookahead_candidates))


def _paged_attn_run(x, kv, mode, blocks):
    """``x`` is the decode query block (B, H, D); ``kv`` a PagedKV."""
    if mode == "ref":
        return _ref.paged_attention_ref(x, kv.k, kv.v, kv.ptab, kv.lens)
    from repro.kernels.paged_attention import paged_attention as _pa
    return _pa(x, kv.k, kv.v, kv.ptab, kv.lens,
               interpret=(mode == "interpret"))


register(KernelEntry(
    name="paged_attention", kind="paged",
    supports=lambda d, M: True,
    run=_paged_attn_run,
    # the grid is fixed by the cache geometry — candidates record the
    # page shape so plans and autotune keys stay page-addressed
    candidates=lambda d, M: [{"ps": d.g, "pages": d.bk}]))


def _dense_run(x, w, mode, blocks):
    return jnp.dot(x, w)


register(KernelEntry(
    name="dense", kind="dense",
    supports=lambda d, M: True,
    run=_dense_run,
    candidates=lambda d, M: [{}]))


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Decision:
    """What dispatch resolved for one (x, weight) call."""
    kernel: str
    mode: str
    blocks: Dict[str, int]
    descriptor: SparsityDescriptor
    reason: str = ""


def _ref_decision(desc: SparsityDescriptor, entry_name: str,
                  reason: str) -> Decision:
    return Decision(kernel=entry_name, mode="ref", blocks={},
                    descriptor=desc, reason=reason)


def select(weight: Any, M: int = 128, impl: str = "auto",
           shard: Optional[Tuple[int, int]] = None) -> Decision:
    """Pick (kernel, mode, block sizes) for ``x (M, K) @ weight``.

    Pure function of structure — no execution: cached blocks on a hit,
    defaults on a miss.

    ``shard=(kf, nf)`` keys the decision at the SHARD-LOCAL problem
    (``K/kf``, ``N/nf``) — what each mesh slice actually computes under
    tensor parallelism.  A factor that does not divide is ignored
    (mirrors ``sharding.best_effort``: that axis stayed replicated).
    """
    desc = SparsityDescriptor.of(weight)
    if shard is not None:
        kf, nf = shard
        kf = kf if kf > 1 and desc.K % kf == 0 else 1
        nf = nf if nf > 1 and desc.N % nf == 0 else 1
        if kf > 1 or nf > 1:
            desc = dataclasses.replace(desc, K=desc.K // kf, N=desc.N // nf)
    mode = resolve_mode(impl)
    entry = _entry_for(desc, M)
    if entry is None:
        # registered kernels can't serve this geometry — ref always can
        fallback = _REGISTRY["dense"] if desc.kind == "dense" else None
        name = fallback.name if fallback else f"{desc.kind}-ref"
        return _ref_decision(desc, name, "no kernel supports geometry")
    if desc.kind == "dense":
        return Decision(kernel="dense", mode="compiled", blocks={},
                        descriptor=desc, reason="dense weight")
    if mode == "ref":
        return _ref_decision(desc, entry.name, "cpu fallback")
    blocks = _blocks_for(entry, desc, M, mode)
    return Decision(kernel=entry.name, mode=mode, blocks=blocks,
                    descriptor=desc,
                    reason="tpu" if mode == "compiled" else "forced kernel")


def _entry_for(desc: SparsityDescriptor, M: int) -> Optional[KernelEntry]:
    best = None
    for e in _REGISTRY.values():
        if e.kind != desc.kind:
            continue
        if not e.supports(desc, M):
            continue
        if best is None or e.priority > best.priority:
            best = e
    return best


def _blocks_for(entry: KernelEntry, desc: SparsityDescriptor, M: int,
                mode: str) -> Dict[str, int]:
    key = cache_key(entry.name, M, desc, mode)
    hit = _CACHE.get(key)
    if hit is not None:
        return {k: v for k, v in hit.items() if k != "us"}
    # on a miss: defaults (a compiled eager call sweeps at call time,
    # since the sweep needs operands)
    return _default_blocks(entry.candidates(desc, M), M)


def _default_blocks(cands: List[dict], M: int) -> Dict[str, int]:
    # prefer the 128-row tile (MXU-shaped) when present; otherwise the
    # largest tile that doesn't pad past M (decode-shaped geometries),
    # else first listed
    for c in cands:
        if c.get("bm", 128) == 128:
            return dict(c)
    fitting = [c for c in cands if c.get("bm", 1) <= max(M, 8)]
    if fitting:
        return dict(max(fitting, key=lambda c: c.get("bm", 1)))
    return dict(cands[0]) if cands else {}


# ---------------------------------------------------------------------------
# Autotune sweep
# ---------------------------------------------------------------------------

def _time_call(fn: Callable[[], Array], reps: int = 3) -> float:
    jax.block_until_ready(fn())                     # compile / warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def tune(x: Array, weight: Any, mode: str = "compiled",
         candidates: Optional[Sequence[dict]] = None,
         cache: Optional[AutotuneCache] = None,
         reps: int = 3) -> Dict[str, int]:
    """Sweep block-size candidates for (x, weight); persist + return best.

    Used by the compiled path on cache miss and directly by tests /
    benchmarks (which pass ``mode="interpret"`` or ``"ref"`` off-TPU).
    """
    desc = SparsityDescriptor.of(weight)
    entry = _entry_for(desc, x.shape[0])
    if entry is None or desc.kind == "dense":
        return {}
    cache = cache or _CACHE
    key = cache_key(entry.name, x.shape[0], desc, mode)
    hit = cache.get(key)
    if hit is not None:
        return {k: v for k, v in hit.items() if k != "us"}
    cands = list(candidates) if candidates is not None \
        else entry.candidates(desc, x.shape[0])
    best, best_us, last_err = None, float("inf"), None
    for blocks in cands:
        try:
            us = _time_call(lambda b=blocks: entry.run(x, weight, mode, b),
                            reps=reps)
        except Exception as e:                      # illegal tiling: skip
            last_err = e
            continue
        if us < best_us:
            best, best_us = dict(blocks), us
    if best is None:
        if mode == "compiled":
            # on the chip a kernel that compiles for no candidate is a
            # fault, not a tiling choice: surface it instead of defaults
            raise RuntimeError(
                f"{entry.name}: no block candidate compiled for "
                f"M={x.shape[0]} {desc.pattern} ({len(cands)} tried)"
            ) from last_err
        return _default_blocks(cands, x.shape[0])
    cache.put(key, {**best, "us": round(best_us, 1)})
    return best


# ---------------------------------------------------------------------------
# Execution — the single entry point call sites use
# ---------------------------------------------------------------------------

_FALLBACKS = 0


def fallback_count() -> int:
    """Calls of :func:`sparse_matmul` that degraded to the jnp oracle
    because the kernel raised (process lifetime)."""
    return _FALLBACKS


def sparse_matmul(x: Array, weight: Any, *, impl: str = "auto",
                  name: Optional[str] = None) -> Array:
    """``x (M, K) @ weight (K, N) -> (M, N)`` for dense or any pack.

    Selects the kernel from the weight's sparsity descriptor, resolves the
    execution mode for this backend, applies cached/tuned block sizes, and
    runs.  This is the only matmul entry point call sites should import.
    ``name`` (the weight's param name) places a kernel call on a
    tensor-parallel mesh, see :func:`_run_sharded`.
    """
    global _FALLBACKS
    decision = select(weight, M=x.shape[0], impl=impl)
    entry = _REGISTRY.get(decision.kernel)
    if entry is None:                               # "<kind>-ref" fallback
        return _ref_matmul(x, weight)
    if decision.mode == "ref" or decision.kernel == "dense":
        return entry.run(x, weight, decision.mode, decision.blocks)
    split = _model_split(name, weight)
    if split is not None:
        run = lambda: _run_sharded(entry, x, weight, decision.mode,  # noqa
                                   split, impl)
    elif decision.mode == "compiled" and not isinstance(x, jax.core.Tracer) \
            and _CACHE.get(cache_key(
                entry.name, x.shape[0], decision.descriptor,
                decision.mode)) is None:
        # eager compiled call with no cached tiling: sweep once, persist.
        # Under jit tracing the sweep can't time anything — cached blocks
        # (via `select`) or defaults apply instead.
        return entry.run(x, weight, decision.mode,
                         tune(x, weight, mode=decision.mode))
    else:
        run = lambda: entry.run(x, weight, decision.mode,  # noqa: E731
                                decision.blocks)
    try:
        return run()
    except Exception as e:
        # the Daghero-style posture: a sparse fast path may fail (bad
        # tiling, lowering bug, backend quirk) but the jnp oracle always
        # runs — degrade this call rather than take the workload down,
        # and count it so a chip run can refuse a degraded result
        _FALLBACKS += 1
        warnings.warn(
            f"{decision.kernel} raised in {decision.mode} mode "
            f"({type(e).__name__}: {e}); falling back to the ref path",
            RuntimeWarning, stacklevel=2)
        return _ref_matmul(x, weight)


# ---------------------------------------------------------------------------
# Tensor parallelism: kernels under shard_map
# ---------------------------------------------------------------------------

def _model_split(name: Optional[str], weight: Any
                 ) -> Optional[Tuple[int, int]]:
    """``(kf, nf)`` split of ``weight`` over the ambient mesh's ``model``
    axis (``jax.set_mesh``), or ``None`` off-mesh / unsplit.  A Mosaic
    kernel cannot be partitioned by XLA, so every kernel call on a
    multi-device model axis goes through :func:`_run_sharded`."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or dict(mesh.shape).get("model", 1) == 1:
        return None
    from repro.distributed.sharding import shard_factors
    kf, nf = shard_factors((name,), mesh) if name else (1, 1)
    kf = kf if weight.K % kf == 0 else 1
    nf = nf if weight.N % nf == 0 else 1
    return kf, nf


def _pack_specs(weight: Any, kf: int, nf: int) -> Optional[Any]:
    """shard_map in_specs of a pack split ``(kf, nf)`` over ``model`` —
    the layout ``sharding.param_specs`` places (output-feature strips
    for column-parallel weights; the compressed K axis for row-parallel
    N:M), or ``None`` where the kernel cannot compute a slice."""
    m = "model"
    if (kf, nf) == (1, 1):
        return None
    if isinstance(weight, NMPack):
        if weight.idx.shape[-2] % nf or weight.Kc % kf \
                or (weight.Kc // kf) % weight.n:
            return None
        if nf > 1:
            return dataclasses.replace(weight, values=jax.P(None, m),
                                       idx=jax.P(m, None))
        return dataclasses.replace(weight, values=jax.P(m, None),
                                   idx=jax.P(None, m))
    if isinstance(weight, (BlockSparsePack, CombinedPack)) and kf == 1 \
            and weight.counts.shape[-1] % nf == 0:
        return jax.tree.map(lambda _: jax.P(m), weight)
    return None


def _run_sharded(entry: KernelEntry, x: Array, weight: Any, mode: str,
                 split: Tuple[int, int], impl: str) -> Array:
    """Run ``entry`` per shard: column-parallel weights compute their
    output features from a replicated ``x``; row-parallel ones contract
    their K slice and ``psum`` the partial products.  A layout the
    kernel cannot slice runs replicated on every device.  Block sizes
    are keyed at the shard-local problem, as ``build_plans`` keys them."""
    kf, nf = split
    specs = _pack_specs(weight, kf, nf)
    if specs is None:
        kf = nf = 1
        specs = jax.tree.map(lambda _: jax.P(), weight)
    blocks = select(weight, M=x.shape[0], impl=impl,
                    shard=(kf, nf)).blocks
    local = {"K": weight.K // kf, "N": weight.N // nf}

    def body(xs, ws):
        ws = dataclasses.replace(ws, **local)
        if kf == 1:
            return entry.run(xs, ws, mode, blocks)
        part = entry.run(xs, ws, mode, blocks, out_dtype=jnp.float32)
        return jax.lax.psum(part, "model").astype(xs.dtype)

    return jax.shard_map(
        body, mesh=jax.sharding.get_abstract_mesh(),
        in_specs=(jax.P(None, "model") if kf > 1 else jax.P(), specs),
        out_specs=jax.P(None, "model") if nf > 1 else jax.P(),
        check_vma=False)(x, weight)


def _ref_matmul(x: Array, weight: Any) -> Array:
    if isinstance(weight, BlockSparsePack):
        return _ref.bsr_matmul_ref(x, weight)
    if isinstance(weight, NMPack):
        return _ref.nm_spmm_ref(x, weight)
    if isinstance(weight, CombinedPack):
        return _ref.csa_matmul_ref(x, weight)
    if isinstance(weight, LookaheadPack):
        return _ref.lookahead_matmul_ref(x, weight)
    return jnp.dot(x, weight)


def paged_attention(q: Array, kv: Any, *, impl: str = "auto") -> Array:
    """Decode attention against a paged KV cache, behind the same mode
    policy as the matmuls.

    ``q (B, H, D)`` (one query per sequence), ``kv`` a
    :class:`kernels.paged_attention.PagedKV`.  ``ref`` mode runs the
    gather oracle (the CPU production path); kernel modes run the Pallas
    scalar-prefetch kernel, whose grid walks pages through the page
    table and never materializes the gathered view.
    """
    mode = resolve_mode(impl)
    return _paged_attn_run(q, kv, mode, {})


def plan_paged_attention(cfg: Any, batch: int, page_size: int,
                         max_pages: int, impl: str = "auto",
                         dtype: str = "bfloat16",
                         kv_heads: Optional[int] = None) -> dict:
    """The paged-attention row of a serving plan — same shape as
    :func:`plan_params` entries, keyed by the page-shaped descriptor so
    the autotune cache and plan introspection see the cache geometry
    (``paged{ps}x{pages}``) rather than a weight pattern.

    Like the flash kernel (``dispatch.attention``), the Pallas kernel is
    the *standalone* twin of the model-internal path: the serving decode
    loop runs the inline jnp scatter/gather in ``models.attention`` (the
    SPMD-partitionable form, semantically the ``ref`` oracle), while
    :func:`paged_attention` exposes the kernel for page-shaped decode
    calls and benchmarks; this row records the geometry both share.

    ``kv_heads`` keys the row at a SHARD-LOCAL head count (head-parallel
    paged pools under TP serve ``Hk/model_ext`` heads per shard); omitted
    on single-device plans so existing cache keys are untouched."""
    desc = SparsityDescriptor(kind="paged", K=max_pages * page_size,
                              N=cfg.head_dim, dtype=dtype,
                              g=page_size, bk=max_pages, n=kv_heads)
    mode = resolve_mode(impl)
    entry = _REGISTRY["paged_attention"]
    blocks = dict(entry.candidates(desc, batch)[0])
    hit = _CACHE.get(cache_key(entry.name, batch, desc, mode))
    if hit is not None:
        blocks = {k: v for k, v in hit.items() if k != "us"}
    return {"param": "attention/kv_cache", "M": batch,
            "kernel": entry.name, "mode": mode, "blocks": blocks,
            "pattern": desc.pattern}


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: Optional[int] = None, softcap: Optional[float] = None,
              scale: Optional[float] = None, impl: str = "auto",
              bq: int = 128, bk: int = 128) -> Array:
    """Fused attention behind the same mode policy as the matmuls."""
    mode = resolve_mode(impl)
    if mode == "ref":
        return _ref.mha_ref(q, k, v, causal=causal, window=window,
                            softcap=softcap, scale=scale)
    from repro.kernels.flash_attention import flash_attention
    Lq, Lk = q.shape[-2], k.shape[-2]
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale,
                           bq=min(bq, Lq), bk=min(bk, Lk),
                           interpret=(mode == "interpret"))


# ---------------------------------------------------------------------------
# Whole-model planning (serving warm-up / introspection)
# ---------------------------------------------------------------------------

def plan_params(params: Any, M: int = 128, impl: str = "auto",
                shard_of: Optional[Callable[[Tuple[str, ...]],
                                            Tuple[int, int]]] = None
                ) -> List[dict]:
    """Walk a param pytree and record the dispatch decision for every
    packed weight — the serving engine calls this at build time, once per
    phase geometry (``M = prompt_pad`` rows for prefill, ``M = slots``
    for decode), so the kernel/mode/block selection (and any autotune
    misses) is visible before the first request, not during it.

    ``shard_of(path_names) -> (kf, nf)`` maps a weight's pytree path to
    its tensor-parallel split (``sharding.shard_factors``), so sharded
    engines key plans at the per-device problem size."""
    plan: List[dict] = []

    def visit(path, leaf):
        if isinstance(leaf, PACK_TYPES):
            parts = tuple(str(getattr(p, "key", getattr(p, "idx", "?")))
                          for p in path)
            name = "/".join(parts)
            shard = shard_of(parts) if shard_of is not None else None
            d = select(leaf, M=M, impl=impl, shard=shard)
            row = {"param": name, "M": M, "kernel": d.kernel,
                   "mode": d.mode, "blocks": dict(d.blocks),
                   "pattern": d.descriptor.pattern}
            if shard is not None and shard != (1, 1):
                row["shard"] = list(shard)
            plan.append(row)
        return leaf

    jax.tree_util.tree_map_with_path(
        visit, params, is_leaf=lambda x: isinstance(x, PACK_TYPES))
    return plan
