"""Benchmark aggregator: one section per paper table/figure + the
roofline report.

  PYTHONPATH=src python -m benchmarks.run                      # all, stdout
  PYTHONPATH=src python -m benchmarks.run --sections kernels \
      --json BENCH_kernels.json                                # CI smoke

``--json PATH`` additionally writes a machine-readable record: per-section
wall time + ok flag, and whatever structured payload a section's ``run()``
returns (for ``kernels`` that includes per-kernel µs and GFLOP/s), so the
perf trajectory accumulates across PRs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

SECTIONS = [
    ("fig8_ussa", "benchmarks.bench_ussa"),
    ("fig9_sssa", "benchmarks.bench_sssa"),
    ("fig10_csa_models", "benchmarks.bench_csa_models"),
    ("table2_int7", "benchmarks.bench_int7"),
    ("table3_resources", "benchmarks.bench_resources"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.roofline"),
]


def _jsonable(obj):
    """Coerce section payloads (numpy scalars, tuples) to plain JSON."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item"):          # numpy scalar
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write per-section wall time + structured results")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of section names")
    ap.add_argument("--warm-autotune", action="store_true",
                    help="offline sweep populating the JSON autotune "
                         "cache for the serving-relevant dispatch keys "
                         "(kernel, M, K, N), then exit")
    ap.add_argument("--slots", type=int, default=8,
                    help="--warm-autotune: serving slots (decode M)")
    ap.add_argument("--prompt-pad", type=int, default=128,
                    help="--warm-autotune: prompt pad (per-slot refill "
                         "M; slots*prompt_pad is the wave-prefill M, "
                         "swept on TPU only)")
    args = ap.parse_args(argv)

    from repro.core.caches import configure_caches
    configure_caches()
    if args.warm_autotune:
        from benchmarks import warm_autotune
        out = warm_autotune.run(slots=args.slots,
                                prompt_pad=args.prompt_pad)
        warm_autotune.main(out)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(_jsonable(out), f, indent=1, sort_keys=True)
            print(f"wrote {args.json}")
        return 0

    wanted = set(args.sections.split(",")) if args.sections else None
    unknown = (wanted or set()) - {n for n, _ in SECTIONS}
    if unknown:
        print(f"unknown sections: {sorted(unknown)}", file=sys.stderr)
        return 2
    if args.json:
        # fail fast on an unwritable path — not after minutes of sections
        try:
            import os
            d = os.path.dirname(args.json)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(args.json, "a"):
                pass
        except OSError as e:
            print(f"cannot write --json {args.json}: {e}", file=sys.stderr)
            return 2

    import importlib
    import inspect
    record = {"sections": {},
              "argv": list(argv) if argv is not None else sys.argv[1:]}
    failures = 0
    t_all = time.time()
    for name, module in SECTIONS:
        if wanted is not None and name not in wanted:
            continue
        print(f"\n{'='*72}\n== {name}\n{'='*72}", flush=True)
        t0 = time.time()
        entry = {"ok": False, "wall_s": 0.0}
        try:
            mod = importlib.import_module(module)
            if args.json and hasattr(mod, "run"):
                data = mod.run()
                entry["data"] = _jsonable(data)
                # reuse results for the human table when main() accepts them
                if inspect.signature(mod.main).parameters:
                    mod.main(data)
                else:
                    mod.main()
            else:
                mod.main()
            entry["ok"] = True
            print(f"[{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
        entry["wall_s"] = round(time.time() - t0, 3)
        record["sections"][name] = entry
    record["total_s"] = round(time.time() - t_all, 3)

    n_run = len(record["sections"])
    print(f"\n{n_run - failures}/{n_run} benchmark sections succeeded")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        print(f"wrote {args.json}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
