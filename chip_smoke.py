"""Smoke run of the served sparse path on a TPU: the quickest proof that
the system still starts on the chip.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # tensor parallel on a 1x4 mesh

One chip: Qwen3-0.6B at full width (28 layers, d_model 1024, vocab
151936) with 2:4 N:M attention and MLP weights, initialised from
``--seed`` and packed by ``core.sparse_linear.pack_params``, served by the
streaming ``Engine`` over the paged KV cache.  Phases:

  1. each sparse kernel at the model's shapes (decode M = slots and
     prefill M = prompt rows) against its ``kernels/ref.py`` oracle —
     ``nm_spmm`` on the packed weights, ``csa_matmul`` on block x 2:4
     packs of the MLP widths — plus one compiled autotune sweep (which
     raises when no candidate runs);
  2. the Engine serves ``--requests`` prompts of 64-512 tokens,
     ``--max-new`` tokens each: every request must finish DONE, every
     packed weight's plan row must be ``compiled`` on its sparse kernel,
     and every fault counter must stay zero;
  3. the last-position prefill logits of two prompts against the same
     model on the ``ref`` dispatch path (jnp oracles), same process.

Four chips (``--chips 4``): the same model and requests through the
sharded Engine on a ``data=1 x model=4`` mesh, compared with a
one-device Engine and one-device prefill logits in the same process.
No other phase runs.

Any failed check exits non-zero.  The script refuses to run off the TPU
(there is no CPU branch) and under a ``REPRO_DISPATCH_MODE`` other than
``compiled``.  Its last stdout line is one JSON object naming the device.
Times printed here are from one smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Tolerances, as relative L2 error ||a - b|| / ||b||.
#
# Kernel vs oracle: both accumulate exact bf16 products in f32 and round
# the result to bf16 once; only the summation order differs, so an
# element differs by at most one bf16 ulp (2^-7 relative) and only where
# the f32 sums straddle a rounding boundary.  1e-2 bounds the case in
# which every element flips.
KERNEL_RTOL = 1e-2
# Prefill logits: the kernel and oracle paths share every non-sparse op,
# so they differ only by those per-matmul one-ulp flips, propagated
# through 196 sparse projections (28 layers x 7) and re-normalised by
# RMSNorm at every block.  Flips of a small share of elements at 2^-7
# each, summed over the depth like a random walk, stay near 1e-2; 2e-2
# leaves room for that and still refuses a wrong kernel, whose error is
# O(1).
LOGIT_RTOL = 2e-2

FAULT_COUNTERS = ("kernel_failures", "numeric_faults", "fetch_errors",
                  "degraded_recoveries")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# Checks (imported by tests/test_chip_smoke.py)
# ---------------------------------------------------------------------------

def check_dispatch_mode() -> None:
    mode = os.environ.get("REPRO_DISPATCH_MODE", "")
    require(mode in ("", "compiled"),
            f"REPRO_DISPATCH_MODE={mode!r}: the smoke run measures the "
            "compiled kernels only")


def expected_kernels(params) -> dict:
    """Packed-weight param name (as ``dispatch.plan_params`` names it)
    -> the sparse kernel that must serve it."""
    import jax
    from repro.core import sparsity
    from repro.kernels import dispatch
    kernel_of = {sparsity.NMPack: "nm_spmm",
                 sparsity.BlockSparsePack: "bsr_matmul",
                 sparsity.CombinedPack: "csa_matmul",
                 sparsity.LookaheadPack: "lookahead_decode"}
    out = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, dispatch.PACK_TYPES))[0]
    for path, leaf in leaves:
        if isinstance(leaf, dispatch.PACK_TYPES):
            name = "/".join(str(getattr(p, "key", getattr(p, "idx", "?")))
                            for p in path)
            out[name] = kernel_of[type(leaf)]
    return out


def check_plans(engine, params) -> list:
    """Every packed weight's prefill and decode plan row must be
    ``compiled`` on its sparse kernel.  Returns the printable summary."""
    want = expected_kernels(params)
    require(bool(want), "the model holds no packed weights")
    lines, seen = [], set()
    for phase, plan in (("prefill", engine.prefill_plan),
                        ("decode", engine.decode_plan)):
        for row in plan:
            if row["param"] == "attention/kv_cache":
                # the served decode runs the inline jnp page gather; this
                # row only records the geometry the Pallas kernel would use
                lines.append(f"  {phase:7s} M={row['M']:<5d} "
                             f"attention/kv_cache: {row['kernel']} "
                             f"{row['mode']} {row['pattern']} (planned, "
                             "not run)")
                continue
            seen.add(row["param"])
            require(row["param"] in want,
                    f"plan row for an unpacked weight: {row}")
            require(row["mode"] == "compiled"
                    and row["kernel"] == want[row["param"]],
                    f"{phase} plan row {row['param']} M={row['M']} is "
                    f"{row['kernel']}/{row['mode']}, expected "
                    f"{want[row['param']]}/compiled")
            lines.append(f"  {phase:7s} M={row['M']:<5d} {row['param']}: "
                         f"{row['kernel']} {row['mode']} {row['blocks']} "
                         f"{row['pattern']}")
    require(seen == set(want),
            f"packed weights missing from the plans: {set(want) - seen}")
    return lines


def check_counters(engine) -> dict:
    """Every fault counter zero, the engine never degraded, and the
    dispatch-level ref fallback never fired."""
    from repro.kernels import dispatch
    st = engine.stats()
    counters = {c: getattr(st, c) for c in FAULT_COUNTERS}
    counters["degraded"] = bool(st.degraded or engine.degraded)
    counters["dispatch_fallbacks"] = dispatch.fallback_count()
    bad = {k: v for k, v in counters.items() if v}
    require(not bad, f"fault counters non-zero: {bad}")
    return counters


def check_done(handles) -> None:
    from repro.serving.state import RequestStatus
    bad = [(h.uid, h.status.value) for h in handles
           if h.status is not RequestStatus.DONE]
    require(not bad, f"requests not DONE: {bad}")


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compile seconds per program, tagged with the phase."""

    def __init__(self):
        self.phase = "setup"
        self.rows = []

    def __call__(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.rows.append((self.phase, kw.get("fun_name", "?"), secs))

    def report(self, phase):
        rows = [r for r in self.rows if r[0] == phase]
        total = sum(s for _, _, s in rows)
        print(f"[{phase}] compiled {len(rows)} programs in {total:.2f} s")
        for _, name, s in sorted(rows, key=lambda r: -r[2])[:8]:
            print(f"  compile {name}: {s:.2f} s")


def build_model(seed: int):
    import jax
    from repro import models as MZ
    from repro.configs import qwen3_0_6b
    from repro.core.sparse_linear import pack_params
    cfg = qwen3_0_6b.sparse()
    t = time.perf_counter()
    dense = jax.jit(lambda k: MZ.init_model(k, cfg))(jax.random.key(seed))
    params = pack_params(dense, cfg)
    jax.block_until_ready(params)
    del dense
    print(f"[setup] {cfg.name} 2:4 sparse: {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, vocab {cfg.vocab_size}; init + pack "
          f"{time.perf_counter() - t:.2f} s")
    return cfg, params


def make_prompts(cfg, n: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(64, 513, size=n)
    return [rng.integers(0, cfg.vocab_size, size=int(L)).astype(np.int32)
            for L in lens]


def serve_config(args):
    from repro.serving import ServeConfig
    return ServeConfig(slots=args.slots, max_len=args.max_len,
                       prompt_pad=args.prompt_pad,
                       max_new_tokens=args.max_new, decode_chunk=16,
                       page_size=args.page_size, eos_token=-1,
                       seed=args.seed)


def serve(cfg, mesh, scfg, params, prompts, tag):
    """Serve ``prompts`` to completion; returns (engine, handles)."""
    from repro.serving import Engine
    engine = Engine(cfg, mesh, scfg, params)
    handles = [engine.submit(p) for p in prompts]
    t = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t
    toks = sum(len(h.tokens) for h in handles)
    st = engine.stats()
    print(f"[{tag}] smoke run, not a benchmark: {len(handles)} requests, "
          f"{toks} tokens in {dt:.2f} s wall (compiles included), "
          f"{st.prefills} prefills, {st.sync_count} host syncs, "
          f"peak {st.peak_pages} pages")
    return engine, handles


def padded_prompts(prompts, rows: int):
    import numpy as np
    out = np.zeros((len(prompts), rows), np.int32)
    for i, p in enumerate(prompts):
        p = p[-rows:]
        out[i, rows - len(p):] = p                 # left-pad, as served
    return out


def prefill_logits(cfg, params, tokens, mesh):
    """Last-position prefill logits (B, vocab) through ``models.prefill``
    — a fresh jit, so the dispatch mode in force now is what is traced."""
    import jax
    import numpy as np
    from repro import models as MZ

    def f(p, t):
        cache = MZ.init_cache(cfg, t.shape[0], t.shape[1])
        return MZ.prefill(p, cfg, {"tokens": t}, cache)[0]

    with jax.set_mesh(mesh):
        out = jax.jit(f)(params, tokens)
    return np.asarray(out, np.float32)[:, :cfg.vocab_size]


def check_kernel(w, M: int, rng) -> None:
    """``w`` through its compiled kernel (the plan's blocks) against its
    ref oracle at ``M`` rows."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch
    x = jnp.asarray(rng.normal(size=(M, w.K)), jnp.bfloat16)
    d = dispatch.select(w, M=M, impl="compiled")
    entry = dispatch.registry()[d.kernel]
    got = jax.jit(lambda x, w: entry.run(x, w, "compiled", d.blocks))(x, w)
    err = rel_l2(got, dispatch._ref_matmul(x, w))
    print(f"  {d.kernel} M={M} K={w.K} N={w.N} blocks={d.blocks}: "
          f"rel-L2 {err:.3e} vs ref (limit {KERNEL_RTOL:g})")
    require(err <= KERNEL_RTOL,
            f"{d.kernel} M={M} K={w.K} N={w.N} disagrees with its oracle: "
            f"rel-L2 {err:.3e}")


def check_kernels(cfg, params, args) -> None:
    """Each distinct packed weight of layer 0, then the combined (CSA)
    kernel on block x 2:4 packs of the MLP widths (the served model packs
    N:M only), at decode and prefill M; then one compiled autotune sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import pruning, sparsity
    from repro.kernels import dispatch

    shapes = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        params["layers"], is_leaf=lambda x: isinstance(x, dispatch.PACK_TYPES))[0]
    for path, leaf in leaves:
        if isinstance(leaf, dispatch.PACK_TYPES):
            w = jax.tree.map(lambda a: a[0], leaf)          # layer 0
            shapes.setdefault((type(w).__name__, w.K, w.N), w)
    rng = np.random.default_rng(args.seed)
    for _, w in sorted(shapes.items()):
        for M in (args.slots, args.prompt_pad):
            check_kernel(w, M, rng)
    for K, N in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        dense = jnp.asarray(rng.normal(size=(K, N)), jnp.bfloat16)
        wp, _ = pruning.combined_nm(dense, 0.5, 2, 4, group=128, block=128)
        w = sparsity.pack_combined(wp, 2, 4, 128, 128)
        for M in (args.slots, args.prompt_pad):
            check_kernel(w, M, rng)
    (kind, K, N), w = max(shapes.items(), key=lambda kv: kv[0][2])
    x = jnp.asarray(rng.normal(size=(args.slots, K)), jnp.bfloat16)
    best = dispatch.tune(x, w, mode="compiled", reps=3)
    print(f"  tune {kind} M={args.slots} K={K} N={N}: best {best}")


def run_one_chip(args, log: CompileLog) -> None:
    import numpy as np
    from repro.kernels import dispatch
    from repro.launch.mesh import make_host_mesh

    cfg, params = build_model(args.seed)
    mesh = make_host_mesh()

    log.phase = "kernels"
    print("[kernels] sparse kernels vs their oracles at the model's shapes")
    check_kernels(cfg, params, args)
    log.report("kernels")

    log.phase = "engine"
    prompts = make_prompts(cfg, args.requests, args.seed)
    engine, handles = serve(cfg, mesh, serve_config(args), params, prompts,
                            "engine")
    log.report("engine")
    check_done(handles)
    print("[engine] dispatch plan (packed weights):")
    for line in check_plans(engine, params):
        print(line)
    print(f"[engine] counters: {check_counters(engine)}")

    log.phase = "reference"
    tokens = padded_prompts(prompts[:2], args.prompt_pad)
    got = prefill_logits(cfg, engine.params, tokens, mesh)
    prev = dispatch.set_mode_override("ref")
    try:
        want = prefill_logits(cfg, engine.params, tokens, mesh)
    finally:
        dispatch.set_mode_override(prev)
    log.report("reference")
    err = rel_l2(got, want)
    print(f"[reference] prefill logits, compiled kernels vs ref path: "
          f"rel-L2 {err:.3e} (limit {LOGIT_RTOL:g}); greedy first token "
          f"agrees {int((got.argmax(-1) == want.argmax(-1)).sum())}/"
          f"{len(got)} (printed, not gated: random-init logits are "
          f"nearly tied); engine's first token = compiled argmax "
          f"{sum(h.tokens[0] == int(t) for h, t in zip(handles, got.argmax(-1)))}"
          f"/{len(got)}")
    require(bool(np.isfinite(got).all()), "non-finite logits")
    require(err <= LOGIT_RTOL,
            f"prefill logits disagree with the ref path: rel-L2 {err:.3e}")
    print(f"[final] counters: {check_counters(engine)}")


def run_four_chips(args, log: CompileLog) -> None:
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh, make_mesh

    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
            f"{len(devices)}")
    cfg, params = build_model(args.seed)
    prompts = make_prompts(cfg, args.requests, args.seed)
    tokens = padded_prompts(prompts[:2], args.prompt_pad)
    scfg = serve_config(args)

    log.phase = "one-device"
    mesh1 = make_host_mesh()
    e1, h1 = serve(cfg, mesh1, scfg, params, prompts, "one-device")
    want = prefill_logits(cfg, e1.params, tokens, mesh1)
    log.report("one-device")

    log.phase = "model=4"
    mesh4 = make_mesh((1, 4), devices=devices[:4])
    e4, h4 = serve(cfg, mesh4, scfg, params, prompts, "model=4")
    got = prefill_logits(cfg, e4.params, tokens, mesh4)
    log.report("model=4")
    print(f"[model=4] mesh {dict(mesh4.shape)}, "
          f"{e4._backend.shard_info()}")

    for e, hs in ((e1, h1), (e4, h4)):
        check_done(hs)
        check_plans(e, params)
        check_counters(e)
    err = rel_l2(got, want)
    same = [np.array_equal(a.tokens, b.tokens) for a, b in zip(h1, h4)]
    agree = sum(int(x == y) for a, b in zip(h1, h4)
                for x, y in zip(a.tokens, b.tokens))
    total = sum(len(a.tokens) for a in h1)
    print(f"[compare] prefill logits, model=4 vs one device: rel-L2 "
          f"{err:.3e} (limit {LOGIT_RTOL:g}); greedy tokens agree "
          f"{agree}/{total}, identical streams {sum(same)}/{len(same)} "
          "(printed, not gated)")
    require(bool(np.isfinite(got).all()), "non-finite logits")
    require(err <= LOGIT_RTOL,
            f"model=4 prefill logits disagree with one device: rel-L2 "
            f"{err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prompt-pad", type=int, default=512)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args(argv)

    check_dispatch_mode()
    import jax
    backend = jax.default_backend()
    require(backend == "tpu",
            f"JAX backend is {backend!r}: this smoke run needs a TPU")
    from repro.core.caches import configure_caches
    cache_dir = configure_caches()
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)

    devs = jax.devices()
    print(f"platform {devs[0].platform}, device_kind {devs[0].device_kind}, "
          f"{len(devs)} devices, jax {jax.__version__}, compile cache "
          f"{cache_dir}")
    t = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, log)
    else:
        run_one_chip(args, log)
    print(f"[final] total {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"SMOKE FAILED: {e}", file=sys.stderr)
        sys.exit(1)
