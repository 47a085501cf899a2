"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots a model (fresh init or checkpoint), starts the slot-based
continuous-batching server, feeds it a synthetic request stream and
reports throughput.  The decode step it runs is the same jitted function
the dry-run's decode cells lower.

Example:
  python -m repro.launch.serve --arch qwen3-0.6b --reduced \\
      --requests 16 --max-new 32

Tensor-parallel serving: ``--model-parallel N`` builds the elastic
``("data","model")`` mesh and the Engine places the (packed) weights and
the head-parallel paged KV pool per ``distributed.sharding``.  On a
CPU-only host, ``--devices N`` simulates N devices
(``--xla_force_host_platform_device_count``) — set BEFORE jax imports,
which is why this module defers ``import jax`` into ``main()``.

``--dry-run`` lowers + compiles the actual serving programs (per-slot
paged prefill, the chunked decode loop) for the FULL config on abstract
weights — no parameters materialize, so the 132B-class cells run on a
laptop.  Reports per-device memory analysis and the sharded dispatch
plan as JSON; this is how CI proves ``dbrx_132b``/``qwen2_vl_72b``
serve on the simulated 8-way mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-pad", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=16,
                    help="on-device decode steps per host sync")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV rows per cache page; >0 switches the KV "
                         "cache to the paged layout (pool + per-slot "
                         "page tables)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="allocatable pages in the shared pool; 0 sizes "
                         "it at full capacity (slots x max_len) — set "
                         "lower to overcommit, requests then wait for "
                         "pages at admission")
    ap.add_argument("--prompt-buckets", type=int, default=0,
                    help="paged only: pad each prompt to a multiple of "
                         "this instead of the uniform --prompt-pad")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged only: share resident prompt pages "
                         "across requests (radix index + copy-on-write)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="with --prefix-cache: pin a random shared head "
                         "of this many tokens (a --page-size multiple) "
                         "via register_prefix and lead every request "
                         "with it")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: tokens drafted per "
                         "verify step (0 disables; the decode loop then "
                         "drafts with --spec-draft params and verifies "
                         "the whole block in one dense forward)")
    ap.add_argument("--spec-draft", default="pack",
                    choices=("pack", "self"),
                    help="drafter weights: 'pack' = the model packed "
                         "into its configured sparse formats (the "
                         "sparse-draft/dense-verify split), 'self' = "
                         "the verify weights themselves (acceptance "
                         "~1, measures the amortized dense cost)")
    ap.add_argument("--no-spec", action="store_true",
                    help="force speculation off (overrides --spec-k)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: submissions beyond "
                         "this many waiting requests are REJECTED "
                         "outright (0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="per-request deadline; requests still queued "
                         "or decoding past it finish TIMED_OUT at the "
                         "next chunk boundary (0 = none)")
    ap.add_argument("--supervise", action="store_true",
                    help="run the engine under the crash-safe "
                         "supervisor: step() failures (including "
                         "injected crashes) and watchdog-detected hangs "
                         "tear the engine down and restore it from the "
                         "journal + latest snapshot with bit-identical "
                         "resume (requires --journal)")
    ap.add_argument("--journal", default="",
                    help="write-ahead request journal path (append-only "
                         "JSONL, fsync'd at chunk boundaries); with "
                         "--supervise it is what recovery replays")
    ap.add_argument("--snapshot-dir", default=None,
                    help="engine snapshot directory; with --supervise, "
                         "snapshots bound how much journal replay a "
                         "recovery pays")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot every N scheduler ticks (0 = never)")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="supervised watchdog: a step slower than this "
                         "(past the post-start compile grace) counts as "
                         "a hung engine and triggers restore (0 = off)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host devices (CPU SPMD via "
                         "--xla_force_host_platform_device_count; must "
                         "be set before jax initializes, so only this "
                         "launcher can apply it)")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower + compile the serving programs on "
                         "abstract weights (no params materialize) and "
                         "report per-device memory + the dispatch plan "
                         "as JSON — the 132B-class configs' CI path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    from repro.core.caches import configure_caches
    configure_caches()
    if args.dry_run:
        return _dry_run(args)

    import jax

    from repro import configs as C
    from repro import models as MZ
    from repro.checkpoint import restore_latest
    from repro.launch.mesh import make_elastic_mesh
    from repro.serving import Engine, ServeConfig, Supervisor

    mod = C._module(args.arch)
    cfg = mod.reduced() if args.reduced else mod.config()
    mesh = make_elastic_mesh(model_parallel=args.model_parallel)

    rng = jax.random.key(args.seed)
    with jax.set_mesh(mesh):
        params = MZ.init_model(rng, cfg)
    if args.checkpoint_dir:
        restored = restore_latest(args.checkpoint_dir,
                                  {"params": jax.eval_shape(lambda: params)})
        if restored is not None:
            params = restored[0]["params"]
            print(f"restored checkpoint step {restored[1]}")

    spec_k = 0 if args.no_spec else args.spec_k
    scfg = ServeConfig(slots=args.slots, max_len=args.max_len,
                       prompt_pad=args.prompt_pad,
                       max_new_tokens=args.max_new,
                       decode_chunk=args.decode_chunk,
                       temperature=args.temperature, seed=args.seed,
                       page_size=args.page_size, num_pages=args.num_pages,
                       prompt_buckets=args.prompt_buckets,
                       prefix_cache=args.prefix_cache,
                       max_queue=args.max_queue,
                       spec_k=spec_k, spec_draft=args.spec_draft,
                       journal_path=args.journal)
    if args.supervise:
        if not args.journal:
            ap.error("--supervise needs --journal (recovery replays it)")
        server = Supervisor(cfg, mesh, scfg, params,
                            journal_path=args.journal,
                            snapshot_dir=args.snapshot_dir,
                            snapshot_every=args.snapshot_every,
                            watchdog_ms=args.watchdog_ms)
    else:
        server = Engine(cfg, mesh, scfg, params)

    rng_np = np.random.default_rng(args.seed)
    handle = None
    if args.shared_prefix:
        handle = server.register_prefix(rng_np.integers(
            0, min(cfg.vocab_size, 1024),
            size=args.shared_prefix).astype(np.int32))
    for _ in range(args.requests):
        # pinned-head sharing needs equal padded heads (left-padding),
        # so the demo fixes the suffix length when a prefix is pinned
        L = (args.prompt_len if handle is not None
             else int(rng_np.integers(4, args.prompt_len + 1)))
        server.submit(rng_np.integers(
            0, min(cfg.vocab_size, 1024), size=L).astype(np.int32),
            prefix=handle, deadline_ms=args.deadline_ms or None)

    t0 = time.time()
    done = server.run()
    dt = time.time() - t0
    stats = server.stats()                  # typed EngineStats snapshot
    toks = sum(len(r.out) for r in done)
    ttfts = sorted(server.ttfts_s())
    report = {
        "arch": cfg.name, "requests": len(done),
        "generated_tokens": toks, "wall_s": round(dt, 2),
        "tok_per_s": round(toks / dt, 1),
        "ttft_p50_ms": round(1e3 * ttfts[len(ttfts) // 2], 2)
        if ttfts else None,
        "decode_chunk": scfg.decode_chunk,
        "host_syncs": stats.sync_count,
        "prefills": stats.prefills,
        "kv_cache_mb": round(stats.cache_bytes / 2**20, 2),
        # robustness counters: every contained fault shows up here
        "timeouts": stats.timeouts,
        "rejections": stats.rejections,
        "preemptions": stats.preemptions,
        "numeric_faults": stats.numeric_faults,
        "kernel_failures": stats.kernel_failures,
        "fetch_errors": stats.fetch_errors,
        "degraded": stats.degraded,
        "degraded_recoveries": stats.degraded_recoveries,
    }
    if args.supervise:
        report.update({
            "restarts": server.restarts,
            "recovery_ms": round(
                server.last_recovery.get("total_ms", 0.0), 1),
        })
    if scfg.paged:
        report.update({
            "page_size": scfg.page_size,
            "pool_pages": scfg.pool_pages,
            "peak_pages": stats.peak_pages,
            "admission_waits": stats.admission_waits,
        })
    if scfg.prefix_cache:
        report.update({
            "prefix_hits": stats.prefix_hits,
            "shared_pages": stats.shared_pages,
            "cow_copies": stats.cow_copies,
        })
    if scfg.spec:
        report.update({
            "spec_k": scfg.spec_k,
            "spec_draft": scfg.spec_draft,
            "drafted_tokens": stats.drafted,
            "accepted_tokens": stats.accepted,
            "acceptance_rate": round(stats.acceptance_rate, 4),
        })
    print(json.dumps(report))
    return 0


def _dry_run(args) -> int:
    """AOT-compile the serving programs on abstract weights.

    Mirrors ``launch.dryrun``: ``eval_shape`` the param/cache pytrees,
    preflight the sharding specs, then ``jit(...).lower(...).compile()``
    the per-slot prefill step and the chunked decode loop under the
    elastic mesh.  ``memory_analysis()`` of the compiled executables is
    the fits-per-device proof; nothing ever materializes.
    """
    import jax
    import jax.numpy as jnp

    from repro import configs as C
    from repro import models as MZ
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_elastic_mesh, mesh_chips
    from repro.serving import ServeConfig, loops
    from repro.serving.sharded import build_plans, kv_heads_per_shard

    t0 = time.time()
    mod = C._module(args.arch)
    cfg = mod.reduced() if args.reduced else mod.config()
    mesh = make_elastic_mesh(model_parallel=args.model_parallel)

    rng = jax.random.key(args.seed)
    abstract_params = jax.eval_shape(lambda: MZ.init_model(rng, cfg))
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    problems = SH.validate_specs(abstract_params, pspecs, mesh)
    if problems:
        raise ValueError(f"param spec problems: {problems[:5]}")

    page_size = args.page_size or 16
    scfg = ServeConfig(slots=args.slots, max_len=args.max_len,
                       prompt_pad=args.prompt_pad,
                       max_new_tokens=args.max_new,
                       decode_chunk=args.decode_chunk,
                       page_size=page_size, num_pages=args.num_pages,
                       seed=args.seed)
    abstract_cache = jax.eval_shape(
        lambda: MZ.init_cache(cfg, scfg.slots, scfg.max_len,
                              page_size=scfg.page_size,
                              num_pages=scfg.pool_pages))
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    problems = SH.validate_specs(abstract_cache, cspecs, mesh)
    if problems:
        raise ValueError(f"cache spec problems: {problems[:5]}")

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    state = {"tok": sds((scfg.slots,)), "pos": sds((scfg.slots,)),
             "done": sds((scfg.slots,), bool), "left": sds((scfg.slots,))}
    key = jax.eval_shape(lambda: jax.random.key(0))
    temps = sds((scfg.slots,), jnp.float32)
    ptab = sds((scfg.slots, scfg.max_pages))

    def _mem(compiled):
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")}
        mem["total_per_device"] = (mem["argument_size_in_bytes"]
                                   + mem["output_size_in_bytes"]
                                   + mem["temp_size_in_bytes"])
        return mem

    programs = {}
    with jax.set_mesh(mesh):
        prefill = loops.build_prefill_slot_step(
            cfg, mesh, scfg, abstract_params, abstract_cache,
            prompt_rows=scfg.prompt_pad, paged=True)
        batch = {"tokens": sds((1, scfg.prompt_pad))}
        t = time.time()
        cp = prefill.lower(abstract_params, batch, abstract_cache, state,
                           sds(()), sds(()), sds((), jnp.float32), key,
                           sds((scfg.max_pages,))).compile()
        programs["prefill_slot"] = {"compile_s": round(time.time() - t, 2),
                                    "memory": _mem(cp)}
        decode = loops.build_decode_loop(
            cfg, mesh, scfg, abstract_params, abstract_cache, paged=True)
        t = time.time()
        cd = decode.lower(abstract_params, abstract_cache, state, temps,
                          key, ptab).compile()
        programs["decode_loop"] = {"compile_s": round(time.time() - t, 2),
                                   "memory": _mem(cd)}

    plans = build_plans(abstract_params, None, cfg, scfg, mesh=mesh)

    def _bytes(tree):
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree.leaves(tree))

    report = {
        "arch": cfg.name, "dry_run": True,
        "devices": mesh_chips(mesh), "mesh": dict(mesh.shape),
        "params": cfg.param_count(),
        "param_bytes_global": _bytes(abstract_params),
        "cache_bytes_global": _bytes(abstract_cache),
        "kv_heads_per_shard": kv_heads_per_shard(cfg, mesh),
        "slots": scfg.slots, "max_len": scfg.max_len,
        "page_size": scfg.page_size, "pool_pages": scfg.pool_pages,
        "decode_chunk": scfg.decode_chunk,
        "programs": programs,
        "plan_rows": {k: len(v) for k, v in plans.items()},
        "decode_plan_sample": plans["decode"][:3],
        "wall_s": round(time.time() - t0, 2),
    }
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
