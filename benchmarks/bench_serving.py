"""Serving-engine benchmark: end-to-end tokens/sec and per-token latency
for the chunked on-device decode loop vs the seed-style per-token loop,
plus the paged-vs-monolithic KV-cache scenario.

Grid: {dense, nm, combined} × slots ∈ {1, 8}.  The sparse configs pack
the MLP weights through ``core.sparse_linear.pack_params`` so decode
actually runs the paper's kernels through the dispatch layer (same code
path the models use — nothing hand-wired).  For every cell we report:

  * ``tok_per_s``        — chunked engine (one host sync per
    ``decode_chunk`` steps, per-slot continuous refill);
  * ``p50_ms`` / ``p95_ms`` — per-token latency percentiles derived from
    the engine's per-chunk wall times;
  * ``ttft_p50_ms`` / ``ttft_p95_ms`` — time-to-first-token percentiles
    over the scenario's requests (queue wait + prefill + first chunk,
    stamped per request by the engine);
  * ``ref_tok_per_s``    — the seed reference: whole-wave prefill + one
    jitted decode step and one host sync **per token**;
  * ``speedup``          — chunked / reference throughput (the number
    PR 2's acceptance gate reads at slots=8);
  * ``syncs``            — device→host transfers the chunked engine made
    (the ceil(tokens/decode_chunk) contract, observable).

The **heterogeneous-length scenario** (``het-mono`` / ``het-paged``
rows) serves a short-heavy prompt mix spanning 16–512 tokens on 8 slots
at the same logical capacity: the monolithic engine reserves the full
``slots × max_len`` cache and pads every prompt to 512, the paged engine
(``page_size=16``, per-request prompt buckets, demand-sized page pool)
allocates pages for actual lengths.  Reported per engine: ``tok_per_s``,
``kv_mb`` (allocated cache), plus for paged ``peak_used_mb`` (pages in
flight), ``kv_ratio`` (mono/paged allocated bytes) and
``speedup_vs_mono`` — PR 3's acceptance gate reads kv_ratio ≥ 2 or
speedup ≥ 1.3.

The **speculative rows** (``spec-k{2,4,8}`` self-draft, ``spec-k4-pack``
nm-sparse draft) serve the same heterogeneous mix through the paged
speculative loop: each decode step drafts k tokens per slot and verifies
the whole ``(slots, k+1)`` block in ONE dense forward.  Reported per
row: ``tok_per_s``, ``acceptance_rate`` (accepted/drafted — 1.0 for the
self-draft by construction, the honest measured rate for the sparse
draft), ``p50_ms``/``p95_ms`` and ``speedup_vs_paged`` (vs the het-paged
baseline).  tok/s scales with acceptance: the self-draft rows isolate
the amortized-dense-cost ceiling of this host (every draft still costs
a forward on the CPU ref path — the sparse draft only wins where a
drafted token is cheaper than a dense one, i.e. on bandwidth-bound
accelerators running the packed kernels), the pack row shows what a
real sparse draft's acceptance does to it.

The **shared-system-prompt scenario** (``shared-sys-{64,256}`` rows)
serves 8 slots of equal-length prompts that share a pinned head —
``engine.register_prefix(head)`` then ``submit(suffix, prefix=handle)``
— against an unshared paged engine serving the identical full prompts.
Sharing maps the head's resident pages into every slot's page table and
prefill computes only the suffix rows, so the row reports
``ttft_speedup`` (unshared/shared TTFT p50) and ``kv_ratio``
(unshared/shared peak allocated page bytes) — PR 6's acceptance gate
reads both ≥ 1.5 — plus the engine's own ``prefix_hits`` /
``shared_pages`` counters.  Retention is capped (``prefix_cache_pages=1``)
and warm-up suffixes are disjoint from the timed ones, so the timed run
measures pinned-head sharing only.

The **crash-restore row** (``crash-restore``) re-times the het-paged
mix with the write-ahead request journal attached (``journal_tok_per_s``
and ``journal_overhead_pct`` — the fsync-per-chunk-boundary price of
crash safety, acceptance wants < 5%), then serves the same mix under the
:class:`~repro.serving.supervisor.Supervisor` with an injected mid-run
crash: ``tok_per_s`` is end-to-end throughput *through* the kill +
restore, ``recovery_ms`` / ``load_ms`` / ``replay_ms`` the recovery
latency breakdown (snapshot load, journal replay) recover_engine stamps.

Shapes shrink under ``REPRO_BENCH_SMOKE=1`` (the CI smoke step) so one
pass stays in seconds.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import models as MZ
from repro.core.sparse_linear import SparsityConfig, pack_params
from repro.models.config import ModelConfig
from repro.serving import (Engine, ServeConfig, build_decode_step,
                           build_prefill_step, sample_token)
from repro.launch.mesh import make_mesh

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") == "1"

D_MODEL, D_FF, N_LAYERS = (64, 128, 2)
MAX_NEW = 16 if SMOKE else 64
DECODE_CHUNK = 8 if SMOKE else 16
PROMPT_PAD = 16
MAX_LEN = PROMPT_PAD + MAX_NEW + DECODE_CHUNK + 2
SLOTS = (1, 8)
VOCAB = 256

SPARSITY = {
    "dense": None,
    "nm": SparsityConfig(format="nm", n=2, m=4, block_n=64),
    "combined": SparsityConfig(format="combined", sparsity=0.5, n=2, m=4,
                               block_k=64, block_n=64),
}

# --- heterogeneous-length scenario (paged vs monolithic KV cache) ----------
# a short-heavy production-style prompt mix spanning 16–512 tokens; both
# engines get the same logical capacity (max_len per slot) — monolithic
# physically reserves slots×max_len and pads every prompt to HET_PAD,
# paged allocates pages for actual lengths out of a demand-sized pool.
HET_SLOTS = 8
HET_PAGE = 16                       # KV rows per page
HET_PAD = 512                       # monolithic uniform prompt pad
HET_BUCKET = 64                     # paged per-request prompt bucket
HET_MAX_NEW = 8 if SMOKE else 32
HET_CHUNK = 8 if SMOKE else 16
HET_MAX_LEN = HET_PAD + 2 * HET_MAX_NEW
HET_LENS = ([16, 32, 64, 96, 128, 256, 384, 512] if SMOKE else
            [16, 24, 32, 48, 64, 64, 96, 128, 160, 256, 384, 512])


def _het_scfg() -> ServeConfig:
    """The paged heterogeneous config sans pool size (set below)."""
    return ServeConfig(
        slots=HET_SLOTS, max_len=HET_MAX_LEN, prompt_pad=HET_PAD,
        max_new_tokens=HET_MAX_NEW, decode_chunk=HET_CHUNK,
        temperature=0.0, eos_token=-1, page_size=HET_PAGE,
        prompt_buckets=HET_BUCKET, page_view_chunk=8)


def _het_pool_pages() -> int:
    """Demand-sized pool: the worst-case pages of any HET_SLOTS requests
    live at once (so admission never throttles this workload) — computed
    through the engine's own admission math so they can't drift."""
    scfg = _het_scfg()
    need = sorted((scfg.request_pages(L, HET_MAX_NEW) for L in HET_LENS),
                  reverse=True)
    return sum(need[:HET_SLOTS])


def _model(fmt: str):
    scfg = SPARSITY[fmt]
    cfg = ModelConfig(name=f"bench-{fmt}", n_layers=N_LAYERS,
                      d_model=D_MODEL, vocab_size=VOCAB, n_heads=4,
                      n_kv_heads=2, d_ff=D_FF, remat=False,
                      mlp_sparsity=scfg or SparsityConfig())
    params = MZ.init_model(jax.random.key(0), cfg)
    if scfg is not None:
        params = pack_params(params, cfg)
    return cfg, params


def _requests(rng, n):
    return [rng.integers(1, VOCAB, size=int(rng.integers(4, PROMPT_PAD + 1))
                         ).astype(np.int32) for _ in range(n)]


SPEC_KS = (2, 4) if SMOKE else (2, 4, 8)


def _serve_chunked(cfg, mesh, params, slots, requests, scfg=None,
                   warm_all=False, max_new=None, prefix_tokens=None,
                   warm_requests=None, rounds=1):
    scfg = scfg or ServeConfig(
        slots=slots, max_len=MAX_LEN, prompt_pad=PROMPT_PAD,
        max_new_tokens=MAX_NEW, decode_chunk=DECODE_CHUNK,
        temperature=0.0, eos_token=-1)
    server = Engine(cfg, mesh, scfg, params)
    handle = (server.register_prefix(prefix_tokens)
              if prefix_tokens is not None else None)
    if warm_all:
        # heterogeneous mix: visit every prompt bucket / view bucket so
        # the timed run pays zero compiles
        for p in (warm_requests if warm_requests is not None else requests):
            server.submit(p, max_new=max_new, prefix=handle)
    else:
        server.submit(requests[0][: scfg.prompt_pad],
                      max_new=scfg.decode_chunk + 1)
    server.run()                                    # compile warm-up
    server.finished.clear()
    server.reset_stats()
    # rounds > 1 drains between equal-sized submit batches: every batch
    # is a fresh single wave, so TTFT percentiles average over rounds
    # instead of mixing queue-wait into the tail
    per_round = -(-len(requests) // rounds)
    t0 = time.perf_counter()
    done = []
    for i in range(rounds):
        for p in requests[i * per_round:(i + 1) * per_round]:
            server.submit(p, max_new=max_new, prefix=handle)
        done.extend(server.run())
    wall = time.perf_counter() - t0
    stats = server.stats()                          # typed EngineStats
    toks = sum(len(r.out) for r in done)
    per_tok_ms = np.concatenate([
        np.full(n, s / n * 1e3)
        for s, n in zip(stats.chunk_s, stats.chunk_tokens) if n]) \
        if stats.chunk_tokens else np.zeros(1)
    page_bytes_used = 0
    if scfg.paged:
        # per-page bytes across layers ≈ pool bytes / (pool+null pages)
        page_bytes_used = int(
            stats.cache_bytes * stats.peak_pages / (scfg.pool_pages + 1))
    ttft_ms = np.asarray([r.ttft_s for r in done
                          if r.ttft_s is not None]) * 1e3
    if ttft_ms.size == 0:
        ttft_ms = np.zeros(1)
    return {"tokens": toks, "tok_per_s": toks / wall,
            "p50_ms": float(np.percentile(per_tok_ms, 50)),
            "p95_ms": float(np.percentile(per_tok_ms, 95)),
            "ttft_p50_ms": float(np.percentile(ttft_ms, 50)),
            "ttft_p95_ms": float(np.percentile(ttft_ms, 95)),
            "syncs": stats.sync_count, "wall_s": wall,
            "kv_bytes": stats.cache_bytes,
            "peak_used_bytes": page_bytes_used,
            "admission_waits": stats.admission_waits,
            "acceptance_rate": stats.acceptance_rate,
            "prefix_hits": stats.prefix_hits,
            "shared_pages": stats.shared_pages,
            "cow_copies": stats.cow_copies}


def _serve_per_token(cfg, mesh, params, slots, requests):
    """The seed engine's hot path: whole-wave prefill, shared position
    counter, ``np.asarray(tok)`` once per generated token."""
    scfg = ServeConfig(slots=slots, max_len=MAX_LEN, prompt_pad=PROMPT_PAD,
                       max_new_tokens=MAX_NEW, temperature=0.0, eos_token=-1)
    abstract_params = jax.eval_shape(lambda: params)
    abstract_cache = jax.eval_shape(
        lambda: MZ.init_cache(cfg, slots, MAX_LEN))
    batch_shapes = {"tokens": np.zeros((slots, PROMPT_PAD), np.int32)}
    prefill = build_prefill_step(cfg, mesh, scfg, abstract_params,
                                 abstract_cache, batch_shapes)
    decode = build_decode_step(cfg, mesh, scfg, abstract_params,
                               abstract_cache)
    init_cache = jax.jit(lambda: MZ.init_cache(cfg, slots, MAX_LEN))
    key = jax.random.key(0)

    def run_waves(reqs):
        toks = 0
        queue = list(reqs)
        with jax.set_mesh(mesh):
            while queue:
                active, queue = queue[:slots], queue[slots:]
                prompts = np.zeros((slots, PROMPT_PAD), np.int32)
                for i, p in enumerate(active):
                    L = min(len(p), PROMPT_PAD)
                    prompts[i, PROMPT_PAD - L:] = p[-L:]
                cache = init_cache()
                logits, cache = prefill(params, {"tokens":
                                                 jnp.asarray(prompts)}, cache)
                tok = sample_token(logits[:, :cfg.vocab_size], key, 0.0)
                pos = PROMPT_PAD
                for t in range(MAX_NEW):
                    np.asarray(tok)                # the per-token sync
                    toks += len(active)
                    if t == MAX_NEW - 1 or pos + 1 >= MAX_LEN:
                        break
                    logits, cache = decode(params, tok, cache,
                                           jnp.asarray(pos))
                    tok = sample_token(logits[:, :cfg.vocab_size], key, 0.0)
                    pos += 1
        return toks

    run_waves(requests[:1])                         # compile warm-up
    t0 = time.perf_counter()
    toks = run_waves(requests)
    wall = time.perf_counter() - t0
    return {"tokens": toks, "tok_per_s": toks / wall, "wall_s": wall}


def _het_scenario(mesh) -> list:
    """Paged vs monolithic serving of the heterogeneous prompt mix."""
    import dataclasses
    cfg, params = _model("dense")
    rng = np.random.default_rng(1)
    requests = [rng.integers(1, VOCAB, size=L).astype(np.int32)
                for L in HET_LENS]
    paged_scfg = dataclasses.replace(_het_scfg(),
                                     num_pages=_het_pool_pages())
    mono_scfg = dataclasses.replace(paged_scfg, page_size=0, num_pages=0,
                                    prompt_buckets=0)
    mono = _serve_chunked(cfg, mesh, params, HET_SLOTS, requests,
                          scfg=mono_scfg, warm_all=True)
    paged = _serve_chunked(cfg, mesh, params, HET_SLOTS, requests,
                           scfg=paged_scfg, warm_all=True)
    mb = 1.0 / (1024 * 1024)
    return [
        {"config": "het-mono", "slots": HET_SLOTS,
         "tokens": mono["tokens"],
         "tok_per_s": round(mono["tok_per_s"], 1),
         "p50_ms": round(mono["p50_ms"], 3),
         "p95_ms": round(mono["p95_ms"], 3),
         "ttft_p50_ms": round(mono["ttft_p50_ms"], 3),
         "ttft_p95_ms": round(mono["ttft_p95_ms"], 3),
         "syncs": mono["syncs"],
         "kv_mb": round(mono["kv_bytes"] * mb, 3)},
        {"config": "het-paged", "slots": HET_SLOTS,
         "tokens": paged["tokens"],
         "tok_per_s": round(paged["tok_per_s"], 1),
         "p50_ms": round(paged["p50_ms"], 3),
         "p95_ms": round(paged["p95_ms"], 3),
         "ttft_p50_ms": round(paged["ttft_p50_ms"], 3),
         "ttft_p95_ms": round(paged["ttft_p95_ms"], 3),
         "syncs": paged["syncs"],
         "kv_mb": round(paged["kv_bytes"] * mb, 3),
         "peak_used_mb": round(paged["peak_used_bytes"] * mb, 3),
         "kv_ratio": round(mono["kv_bytes"] / paged["kv_bytes"], 2),
         "speedup_vs_mono": round(paged["tok_per_s"]
                                  / max(mono["tok_per_s"], 1e-9), 2),
         "admission_waits": paged["admission_waits"]},
    ]


# --- shared-system-prompt scenario (prefix cache over paged) ---------------
# 8 slots of equal-total-length prompts led by a pinned shared head
# (``register_prefix``) vs the unshared paged engine serving the same
# full prompts.  Every suffix opens with a token unique across the whole
# bench so the only sharing is the pinned head — no accidental partial
# matches, and compile keys are identical between warm-up and timed run.
SH_SLOTS = 8
SH_HEADS = (64, 256)                # shared head lengths (pages: 4 / 16)
SH_SUFFIX = 16                      # per-request distinct tail
SH_MAX_NEW = 8 if SMOKE else 32
SH_CHUNK = 2                        # short chunks: TTFT ≈ prefill cost
SH_ROUNDS = 2 if SMOKE else 4       # single-wave rounds averaged into
SH_REQS = SH_SLOTS * SH_ROUNDS      # the TTFT percentiles (no queue
                                    # wait — each round drains first)


def _shared_scenario(mesh) -> list:
    """Prefix-shared vs unshared paged serving of a shared-system-prompt
    workload: same physical page pool, same prompts, same budgets.  Runs
    a larger model than the grid so prefill compute (what sharing
    eliminates) dominates per-call dispatch overhead."""
    import dataclasses
    cfg = ModelConfig(name="bench-shared", n_layers=4, d_model=256,
                      vocab_size=VOCAB, n_heads=4, n_kv_heads=2,
                      d_ff=512, remat=False)
    params = MZ.init_model(jax.random.key(0), cfg)
    rng = np.random.default_rng(2)
    rows = []
    for head_len in SH_HEADS:
        head = rng.integers(1, VOCAB, size=head_len).astype(np.int32)

        def suffixes(tag0, n):
            out = []
            for i in range(n):
                s = rng.integers(1, VOCAB, size=SH_SUFFIX).astype(np.int32)
                s[0] = tag0 + i             # unique first token → no
                out.append(s)               # cross-request tail sharing
            return out
        warm = suffixes(1, SH_SLOTS)
        timed = suffixes(1 + SH_SLOTS, SH_REQS)
        total = head_len + SH_SUFFIX
        base = ServeConfig(
            slots=SH_SLOTS, max_len=total + 2 * SH_MAX_NEW,
            prompt_pad=total, max_new_tokens=SH_MAX_NEW,
            decode_chunk=SH_CHUNK, temperature=0.0, eos_token=-1,
            page_size=HET_PAGE, page_view_chunk=8)
        pool = SH_SLOTS * base.request_pages(total, SH_MAX_NEW)
        un_scfg = dataclasses.replace(base, num_pages=pool)
        sh_scfg = dataclasses.replace(un_scfg, prefix_cache=True,
                                      prefix_cache_pages=1)
        un = _serve_chunked(
            cfg, mesh, params, SH_SLOTS,
            [np.concatenate([head, s]) for s in timed], scfg=un_scfg,
            warm_all=True, max_new=SH_MAX_NEW, rounds=SH_ROUNDS,
            warm_requests=[np.concatenate([head, s]) for s in warm])
        sh = _serve_chunked(
            cfg, mesh, params, SH_SLOTS, timed, scfg=sh_scfg,
            warm_all=True, max_new=SH_MAX_NEW, prefix_tokens=head,
            warm_requests=warm, rounds=SH_ROUNDS)
        mb = 1.0 / (1024 * 1024)
        rows.append({
            "config": f"shared-sys-{head_len}", "slots": SH_SLOTS,
            "tokens": sh["tokens"],
            "tok_per_s": round(sh["tok_per_s"], 1),
            "p50_ms": round(sh["p50_ms"], 3),
            "p95_ms": round(sh["p95_ms"], 3),
            "ttft_p50_ms": round(sh["ttft_p50_ms"], 3),
            "ttft_p95_ms": round(sh["ttft_p95_ms"], 3),
            "syncs": sh["syncs"],
            "prefix_hits": sh["prefix_hits"],
            "shared_pages": sh["shared_pages"],
            "cow_copies": sh["cow_copies"],
            "kv_alloc_mb": round(sh["peak_used_bytes"] * mb, 3),
            "base_tok_per_s": round(un["tok_per_s"], 1),
            "base_ttft_p50_ms": round(un["ttft_p50_ms"], 3),
            "base_kv_alloc_mb": round(un["peak_used_bytes"] * mb, 3),
            "ttft_speedup": round(un["ttft_p50_ms"]
                                  / max(sh["ttft_p50_ms"], 1e-9), 2),
            "kv_ratio": round(un["peak_used_bytes"]
                              / max(sh["peak_used_bytes"], 1), 2),
            "admission_waits": sh["admission_waits"]})
    return rows


# --- mixed-priority scenario (preemption vs admission wait) ----------------
# a pool saturated by low-priority batch requests while short interactive
# requests arrive mid-serve: with priority the scheduler preempts a batch
# slot (cancel + retire; warm re-admission resumes the suffix), without
# it the interactive request waits for a batch slot to drain.  The row
# reports interactive TTFT both ways — the preemption payoff.
PR_SLOTS = 6                        # slot headroom: arrivals block on
PR_BATCH = 4                        # *pages*, not slots, so preemption
PR_BATCH_LEN = 256                  # (not slot-wait) is what's measured
PR_INTER_LEN = 32                   # interactive prompt tokens
PR_BATCH_NEW = 16 if SMOKE else 48
PR_INTER_NEW = 4
PR_INTER_N = 3                      # interactive arrivals, spaced out
PR_GAP = 2                          # scheduler ticks between arrivals


def _preempt_serve(cfg, mesh, params, scfg, batch, inter, priority):
    eng = Engine(cfg, mesh, scfg, params)

    def one_pass():
        t0 = time.perf_counter()
        for p in batch:
            eng.submit(p, max_new=PR_BATCH_NEW)
        ih, tick = [], 0
        while eng.queue or eng.num_live or len(ih) < len(inter):
            if tick and tick % PR_GAP == 0 and len(ih) < len(inter):
                ih.append(eng.submit(inter[len(ih)], max_new=PR_INTER_NEW,
                                     priority=priority))
            eng.step()
            tick += 1
        return ih, time.perf_counter() - t0

    # greedy + fixed arrival ticks → the warm pass replays the exact
    # timed schedule, compiling every geometry it will touch (including
    # the preempt-resume prefill at rows0+len(out))
    one_pass()
    eng.finished.clear()
    eng.reset_stats()
    ih, wall = one_pass()
    stats = eng.stats()
    toks = sum(len(r.out) for r in eng.finished)
    ttft = np.asarray([h.ttft_s for h in ih
                       if h.ttft_s is not None]) * 1e3
    if ttft.size == 0:
        ttft = np.zeros(1)
    return {"tokens": toks, "tok_per_s": toks / wall,
            "inter_ttft_p50_ms": float(np.percentile(ttft, 50)),
            "inter_ttft_p95_ms": float(np.percentile(ttft, 95)),
            "preemptions": stats.preemptions,
            "admission_waits": stats.admission_waits}


def _preempt_scenario(mesh) -> list:
    cfg, params = _model("dense")
    rng = np.random.default_rng(3)
    batch = [rng.integers(1, VOCAB, size=PR_BATCH_LEN).astype(np.int32)
             for _ in range(PR_BATCH)]
    inter = [rng.integers(1, VOCAB, size=PR_INTER_LEN).astype(np.int32)
             for _ in range(PR_INTER_N)]
    base = ServeConfig(
        slots=PR_SLOTS, max_len=PR_BATCH_LEN + 2 * PR_BATCH_NEW,
        prompt_pad=PR_BATCH_LEN, max_new_tokens=PR_BATCH_NEW,
        decode_chunk=4, temperature=0.0, eos_token=-1,
        page_size=HET_PAGE, prompt_buckets=HET_BUCKET, page_view_chunk=8)
    # pool fits exactly the batch saturation: an interactive arrival
    # finds a free slot but no pages until a batch request retires
    # (admission wait) or is preempted (priority)
    pool = PR_BATCH * base.request_pages(PR_BATCH_LEN, PR_BATCH_NEW)
    import dataclasses
    scfg = dataclasses.replace(base, num_pages=pool)
    pre = _preempt_serve(cfg, mesh, params, scfg, batch, inter, priority=1)
    wait = _preempt_serve(cfg, mesh, params, scfg, batch, inter, priority=0)
    return [{
        "config": "mixed-priority-preempt", "slots": PR_SLOTS,
        "tokens": pre["tokens"],
        "tok_per_s": round(pre["tok_per_s"], 1),
        "inter_ttft_p50_ms": round(pre["inter_ttft_p50_ms"], 3),
        "inter_ttft_p95_ms": round(pre["inter_ttft_p95_ms"], 3),
        "preemptions": pre["preemptions"],
        "base_tok_per_s": round(wait["tok_per_s"], 1),
        "base_inter_ttft_p50_ms": round(wait["inter_ttft_p50_ms"], 3),
        "base_inter_ttft_p95_ms": round(wait["inter_ttft_p95_ms"], 3),
        "base_admission_waits": wait["admission_waits"],
        "ttft_p95_speedup": round(
            wait["inter_ttft_p95_ms"]
            / max(pre["inter_ttft_p95_ms"], 1e-9), 2)}]


def _spec_scenario(mesh, paged_tok_per_s: float) -> list:
    """Speculative serving of the heterogeneous mix vs the paged
    baseline: ``spec-k{K}`` rows self-draft (acceptance ≈ 1 — the
    amortized-dense-cost ceiling), ``spec-k4-pack`` drafts with the
    nm-packed weights against the dense verifier (the paper's
    sparse/dense split; acceptance is whatever the pack earns)."""
    import dataclasses
    rng = np.random.default_rng(1)
    requests = [rng.integers(1, VOCAB, size=L).astype(np.int32)
                for L in HET_LENS]
    pool = dataclasses.replace(_het_scfg(), num_pages=_het_pool_pages())

    def spec_serve(cfg, params, k, draft):
        # decode_chunk counts verify steps: scale it down so tokens per
        # host sync stay ≈ the baseline's (otherwise most of a chunk
        # runs masked once every slot's budget is spent)
        chunk = max(1, -(-HET_CHUNK // (k + 1)))
        scfg = dataclasses.replace(pool, spec_k=k, spec_draft=draft,
                                   decode_chunk=chunk)
        out = _serve_chunked(cfg, mesh, params, HET_SLOTS, requests,
                             scfg=scfg, warm_all=True)
        return {"slots": HET_SLOTS, "tokens": out["tokens"],
                "tok_per_s": round(out["tok_per_s"], 1),
                "acceptance_rate": round(out["acceptance_rate"], 3),
                "p50_ms": round(out["p50_ms"], 3),
                "p95_ms": round(out["p95_ms"], 3),
                "ttft_p50_ms": round(out["ttft_p50_ms"], 3),
                "ttft_p95_ms": round(out["ttft_p95_ms"], 3),
                "syncs": out["syncs"],
                "speedup_vs_paged": round(
                    out["tok_per_s"] / max(paged_tok_per_s, 1e-9), 2)}

    cfg, params = _model("dense")
    rows = [{"config": f"spec-k{k}", **spec_serve(cfg, params, k, "self")}
            for k in SPEC_KS]
    # real sparse draft: dense verify weights, nm-packed draft of the
    # same weights (spec_draft="pack" packs per the model config)
    dense_cfg = ModelConfig(
        name="bench-spec-nm", n_layers=N_LAYERS, d_model=D_MODEL,
        vocab_size=VOCAB, n_heads=4, n_kv_heads=2, d_ff=D_FF, remat=False,
        mlp_sparsity=SPARSITY["nm"])
    dense_params = MZ.init_model(jax.random.key(0), dense_cfg)
    rows.append({"config": "spec-k4-pack",
                 **spec_serve(dense_cfg, dense_params, 4, "pack")})
    return rows


# --- crash-restore scenario (WAL overhead + recovery latency) --------------
# the heterogeneous mix served three ways: plain paged (re-timed for a
# fair same-process A/B), paged + write-ahead journal (the fsync'd WAL
# every chunk boundary pays for crash safety — acceptance wants < 5%
# tok/s overhead), and supervised with an injected mid-run crash (the
# row's headline: end-to-end tok/s *through* a kill + restore, plus the
# recovery latency breakdown recover_engine stamps).
CRASH_TICK = 2                      # monkey ticks before the injected kill


def _crash_restore_scenario(mesh) -> list:
    import dataclasses
    import shutil
    import tempfile
    import warnings as _warnings

    from repro.serving import ChaosConfig, ChaosMonkey, Supervisor

    cfg, params = _model("dense")
    rng = np.random.default_rng(1)
    requests = [rng.integers(1, VOCAB, size=L).astype(np.int32)
                for L in HET_LENS]
    paged_scfg = dataclasses.replace(_het_scfg(),
                                     num_pages=_het_pool_pages())
    tmp = tempfile.mkdtemp(prefix="bench_crash_")
    try:
        # A/B: identical workload, only the WAL differs.  3 rounds per
        # run average the per-tick fsync over enough chunks, and the
        # pair is measured 3 times interleaved (median overhead) — a
        # single pair is at the mercy of CPU frequency/cache drift on
        # a run this short
        reqs3 = requests * 3
        pairs = []
        for i in range(3):
            base = _serve_chunked(cfg, mesh, params, HET_SLOTS, reqs3,
                                  scfg=paged_scfg, warm_all=True,
                                  warm_requests=requests, rounds=3)
            jr_scfg = dataclasses.replace(
                paged_scfg,
                journal_path=os.path.join(tmp, f"wal{i}.jsonl"))
            jr = _serve_chunked(cfg, mesh, params, HET_SLOTS, reqs3,
                                scfg=jr_scfg, warm_all=True,
                                warm_requests=requests, rounds=3)
            pairs.append((base["tok_per_s"], jr["tok_per_s"]))
        overhead = float(np.median(
            [(b - j) / max(b, 1e-9) * 100.0 for b, j in pairs]))
        jr_tps = float(np.median([j for _, j in pairs]))

        # supervised kill-and-recover: same mix at a quarter of the
        # decode chunk (so the run spans enough scheduler ticks that the
        # kill lands mid-stream, with delivered prefixes to preserve),
        # crash at CRASH_TICK, snapshots bounding the replay
        sup_scfg = dataclasses.replace(paged_scfg,
                                       decode_chunk=max(2, HET_CHUNK // 4))
        sup = Supervisor(
            cfg, mesh, sup_scfg, params,
            journal_path=os.path.join(tmp, "sup_wal.jsonl"),
            snapshot_dir=os.path.join(tmp, "snap"), snapshot_every=4)
        ChaosMonkey(sup.engine, ChaosConfig(
            seed=0, rate=0.0, crash_tick=CRASH_TICK)).attach()
        for p in requests:
            sup.submit(p, max_new=HET_MAX_NEW)
        t0 = time.perf_counter()
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore", RuntimeWarning)
            done = sup.run()
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        rec = sup.last_recovery
        return [{
            "config": "crash-restore", "slots": HET_SLOTS,
            "tokens": toks,
            "tok_per_s": round(toks / wall, 1),
            "restarts": sup.restarts,
            "recovery_ms": round(rec.get("total_ms", 0.0), 1),
            "load_ms": round(rec.get("load_ms", 0.0), 1),
            "replay_ms": round(rec.get("replay_ms", 0.0), 1),
            "journal_tok_per_s": round(jr_tps, 1),
            "journal_overhead_pct": round(overhead, 2)}]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# --- tensor-parallel scaling scenario (sharded-d{1,2,4,8}) -----------------
# tok/s and TTFT vs model-parallel extent on the simulated host mesh
# (XLA_FLAGS=--xla_force_host_platform_device_count=8).  The model widens
# to 8 KV heads / d_ff=512 so the column/row-parallel weight placements
# and the head-parallel paged pool all engage at every extent; extents
# beyond jax.device_count() are skipped (the row is omitted, not faked),
# so a single-device run emits only sharded-d1.
SD_SLOTS = 4
SD_MAX_NEW = 8 if SMOKE else 32
SD_DEVICES = (1, 2, 4, 8)
SD_PAGE = 8


def _sharded_scenario(rng) -> list:
    scfg_sp = SPARSITY["combined"]
    cfg = ModelConfig(name="bench-sharded", n_layers=N_LAYERS,
                      d_model=64, vocab_size=VOCAB, n_heads=8,
                      n_kv_heads=8, d_ff=512, remat=False,
                      mlp_sparsity=scfg_sp)
    params = pack_params(MZ.init_model(jax.random.key(0), cfg), cfg)
    requests = _requests(rng, 2 * SD_SLOTS)
    rows = []
    for d in SD_DEVICES:
        if d > jax.device_count():
            continue
        mesh = make_mesh((1, d))
        scfg = ServeConfig(slots=SD_SLOTS, max_len=MAX_LEN,
                           prompt_pad=PROMPT_PAD,
                           max_new_tokens=SD_MAX_NEW,
                           decode_chunk=DECODE_CHUNK, temperature=0.0,
                           eos_token=-1, page_size=SD_PAGE)
        r = _serve_chunked(cfg, mesh, params, SD_SLOTS, requests,
                           scfg=scfg, max_new=SD_MAX_NEW)
        rows.append({
            "config": f"sharded-d{d}", "devices": d, "slots": SD_SLOTS,
            "tokens": r["tokens"],
            "tok_per_s": round(r["tok_per_s"], 1),
            "ttft_p50_ms": round(r["ttft_p50_ms"], 3),
            "ttft_p95_ms": round(r["ttft_p95_ms"], 3),
            "p50_ms": round(r["p50_ms"], 3),
            "p95_ms": round(r["p95_ms"], 3),
            "syncs": r["syncs"],
            "kv_heads_per_shard": (cfg.n_kv_heads // d
                                   if cfg.n_kv_heads % d == 0 else
                                   cfg.n_kv_heads)})
    return rows


def run() -> dict:
    rng = np.random.default_rng(0)
    mesh = make_mesh()
    rows = []
    for fmt in SPARSITY:
        cfg, params = _model(fmt)
        for slots in SLOTS:
            requests = _requests(rng, 2 * slots)
            chunked = _serve_chunked(cfg, mesh, params, slots, requests)
            ref = _serve_per_token(cfg, mesh, params, slots, requests)
            rows.append({
                "config": fmt, "slots": slots,
                "tokens": chunked["tokens"],
                "tok_per_s": round(chunked["tok_per_s"], 1),
                "p50_ms": round(chunked["p50_ms"], 3),
                "p95_ms": round(chunked["p95_ms"], 3),
                "ttft_p50_ms": round(chunked["ttft_p50_ms"], 3),
                "ttft_p95_ms": round(chunked["ttft_p95_ms"], 3),
                "syncs": chunked["syncs"],
                "ref_tok_per_s": round(ref["tok_per_s"], 1),
                "speedup": round(chunked["tok_per_s"]
                                 / max(ref["tok_per_s"], 1e-9), 2),
            })
    het_rows = _het_scenario(mesh)
    rows.extend(het_rows)
    paged_tps = next(r["tok_per_s"] for r in het_rows
                     if r["config"] == "het-paged")
    rows.extend(_spec_scenario(mesh, paged_tps))
    rows.extend(_shared_scenario(mesh))
    rows.extend(_preempt_scenario(mesh))
    rows.extend(_crash_restore_scenario(mesh))
    rows.extend(_sharded_scenario(rng))
    return {"rows": rows, "decode_chunk": DECODE_CHUNK, "max_new": MAX_NEW,
            "sharded": {"devices": [d for d in SD_DEVICES
                                    if d <= jax.device_count()],
                        "slots": SD_SLOTS, "max_new": SD_MAX_NEW,
                        "page_size": SD_PAGE},
            "het": {"lens": HET_LENS, "page_size": HET_PAGE,
                    "max_len": HET_MAX_LEN, "pool_pages": _het_pool_pages(),
                    "max_new": HET_MAX_NEW},
            "spec_ks": list(SPEC_KS),
            "shared": {"heads": list(SH_HEADS), "suffix": SH_SUFFIX,
                       "requests": SH_REQS, "max_new": SH_MAX_NEW,
                       "page_size": HET_PAGE},
            "crash": {"crash_tick": CRASH_TICK, "snapshot_every": 4,
                      "max_new": HET_MAX_NEW},
            "preempt": {"slots": PR_SLOTS, "batch_len": PR_BATCH_LEN,
                        "batch_new": PR_BATCH_NEW,
                        "inter_len": PR_INTER_LEN,
                        "inter_new": PR_INTER_NEW,
                        "interactive": PR_INTER_N},
            "backend": jax.default_backend()}


def main(out=None) -> None:
    if out is None:
        out = run()
    print(f"# serving bench — chunked loop (decode_chunk="
          f"{out['decode_chunk']}) vs per-token loop, "
          f"{out['backend']} backend")
    print("config,slots,tokens,tok_per_s,p50_ms,p95_ms,ttft_p50_ms,"
          "ttft_p95_ms,syncs,ref_tok_per_s,speedup")
    for r in out["rows"]:
        if r["config"].startswith(("het-", "spec-", "shared-", "mixed-",
                           "crash-", "sharded-")):
            continue
        print(f"{r['config']},{r['slots']},{r['tokens']},"
              f"{r['tok_per_s']},{r['p50_ms']},{r['p95_ms']},"
              f"{r['ttft_p50_ms']},{r['ttft_p95_ms']},{r['syncs']},"
              f"{r['ref_tok_per_s']},{r['speedup']}")
    het = [r for r in out["rows"] if r["config"].startswith("het-")]
    if het:
        h = out.get("het", {})
        print(f"# heterogeneous prompts {min(h.get('lens', [0]))}–"
              f"{max(h.get('lens', [0]))} on {HET_SLOTS} slots — paged "
              f"(page_size={h.get('page_size')}, pool="
              f"{h.get('pool_pages')} pages) vs monolithic "
              f"(max_len={h.get('max_len')})")
        print("config,slots,tokens,tok_per_s,p50_ms,p95_ms,ttft_p50_ms,"
              "ttft_p95_ms,syncs,kv_mb,peak_used_mb,kv_ratio,"
              "speedup_vs_mono,admission_waits")
        for r in het:
            print(f"{r['config']},{r['slots']},{r['tokens']},"
                  f"{r['tok_per_s']},{r['p50_ms']},{r['p95_ms']},"
                  f"{r['ttft_p50_ms']},{r['ttft_p95_ms']},"
                  f"{r['syncs']},{r['kv_mb']},{r.get('peak_used_mb', '')},"
                  f"{r.get('kv_ratio', '')},{r.get('speedup_vs_mono', '')},"
                  f"{r.get('admission_waits', '')}")
    shared = [r for r in out["rows"] if r["config"].startswith("shared-")]
    if shared:
        sh = out.get("shared", {})
        print(f"# shared-system-prompt serving on {SH_SLOTS} slots — "
              f"pinned head (register_prefix) + {sh.get('suffix')}-token "
              f"distinct tails, vs the unshared paged engine "
              f"(page_size={sh.get('page_size')})")
        print("config,slots,tokens,tok_per_s,ttft_p50_ms,ttft_p95_ms,"
              "syncs,prefix_hits,shared_pages,cow_copies,kv_alloc_mb,"
              "base_tok_per_s,base_ttft_p50_ms,base_kv_alloc_mb,"
              "ttft_speedup,kv_ratio,admission_waits")
        for r in shared:
            print(f"{r['config']},{r['slots']},{r['tokens']},"
                  f"{r['tok_per_s']},{r['ttft_p50_ms']},"
                  f"{r['ttft_p95_ms']},{r['syncs']},{r['prefix_hits']},"
                  f"{r['shared_pages']},{r['cow_copies']},"
                  f"{r['kv_alloc_mb']},{r['base_tok_per_s']},"
                  f"{r['base_ttft_p50_ms']},{r['base_kv_alloc_mb']},"
                  f"{r['ttft_speedup']},{r['kv_ratio']},"
                  f"{r['admission_waits']}")
    mixed = [r for r in out["rows"] if r["config"].startswith("mixed-")]
    if mixed:
        pr = out.get("preempt", {})
        print(f"# mixed-priority serving on {pr.get('slots')} slots — "
              f"{pr.get('batch_len')}-token batch requests saturate the "
              f"pool, {pr.get('interactive')} interactive arrivals "
              f"mid-run: priority preemption vs admission wait")
        print("config,slots,tokens,tok_per_s,inter_ttft_p50_ms,"
              "inter_ttft_p95_ms,preemptions,base_tok_per_s,"
              "base_inter_ttft_p50_ms,base_inter_ttft_p95_ms,"
              "base_admission_waits,ttft_p95_speedup")
        for r in mixed:
            print(f"{r['config']},{r['slots']},{r['tokens']},"
                  f"{r['tok_per_s']},{r['inter_ttft_p50_ms']},"
                  f"{r['inter_ttft_p95_ms']},{r['preemptions']},"
                  f"{r['base_tok_per_s']},{r['base_inter_ttft_p50_ms']},"
                  f"{r['base_inter_ttft_p95_ms']},"
                  f"{r['base_admission_waits']},{r['ttft_p95_speedup']}")
    crash = [r for r in out["rows"] if r["config"].startswith("crash-")]
    if crash:
        cr = out.get("crash", {})
        print(f"# crash-restore on the heterogeneous mix — WAL journaling "
              f"overhead vs het-paged, plus a supervised kill at tick "
              f"{cr.get('crash_tick')} restored from snapshot+journal")
        print("config,slots,tokens,tok_per_s,restarts,recovery_ms,"
              "load_ms,replay_ms,journal_tok_per_s,journal_overhead_pct")
        for r in crash:
            print(f"{r['config']},{r['slots']},{r['tokens']},"
                  f"{r['tok_per_s']},{r['restarts']},{r['recovery_ms']},"
                  f"{r['load_ms']},{r['replay_ms']},"
                  f"{r['journal_tok_per_s']},{r['journal_overhead_pct']}")
    spec = [r for r in out["rows"] if r["config"].startswith("spec-")]
    if spec:
        print(f"# speculative serving on the heterogeneous mix — "
              f"k drafts (self or nm-packed) + one dense block verify "
              f"per step, vs het-paged")
        print("config,slots,tokens,tok_per_s,acceptance_rate,p50_ms,"
              "p95_ms,ttft_p50_ms,ttft_p95_ms,syncs,speedup_vs_paged")
        for r in spec:
            print(f"{r['config']},{r['slots']},{r['tokens']},"
                  f"{r['tok_per_s']},{r['acceptance_rate']},"
                  f"{r['p50_ms']},{r['p95_ms']},{r['ttft_p50_ms']},"
                  f"{r['ttft_p95_ms']},{r['syncs']},"
                  f"{r['speedup_vs_paged']}")
    shd = [r for r in out["rows"] if r["config"].startswith("sharded-")]
    if shd:
        sd = out.get("sharded", {})
        print(f"# tensor-parallel serving on {sd.get('slots')} slots — "
              f"combined-sparse weights + head-parallel paged pool "
              f"(page_size={sd.get('page_size')}) across "
              f"{sd.get('devices')} simulated device(s)")
        print("config,devices,slots,tokens,tok_per_s,ttft_p50_ms,"
              "ttft_p95_ms,p50_ms,p95_ms,syncs,kv_heads_per_shard")
        for r in shd:
            print(f"{r['config']},{r['devices']},{r['slots']},"
                  f"{r['tokens']},{r['tok_per_s']},{r['ttft_p50_ms']},"
                  f"{r['ttft_p95_ms']},{r['p50_ms']},{r['p95_ms']},"
                  f"{r['syncs']},{r['kv_heads_per_shard']}")


if __name__ == "__main__":
    main()
