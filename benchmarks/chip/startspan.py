"""A reading of a window's start, not part of a benchmark run: one window
of a cell traced from t = 0, so the first tick, which admits every
closed-loop client at once, is in the trace.  Needs the chip, like
``run.py``.

  python3 benchmarks/chip/startspan.py --workload nm-chat --seed 11 \
      --seconds 51

Prints one JSON line: the TTFT p95 over every request of the window,
over those submitted at t = 0 and over the rest; the first ticks'
``serve.step`` / ``serve.admit`` / ``serve.prefill`` spans; the pool's
live share from the engine's ``chunk_pages`` counter, over the whole
window and over its second half; and the per-layer metrics of the
traced span (its first ``harness.TRACE_S`` seconds).  Served tokens are
not compared with the reference.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import programtrace  # noqa: E402
import readers  # noqa: E402
import run as runner  # noqa: E402


def p95_ms(values):
    return 1e3 * readers.p95(values) if values else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(ROOT, args.workload)
    dev = runner.device_record(cell.chips)
    import jax
    import tracereduce
    from workcount import load_peaks
    peaks = load_peaks(dev["kind"])
    runner.configure_jax(ROOT)
    engine = harness.build_engine(cell, args.seed)
    harness.warm_up(engine, cell)
    engine.reset_stats()
    trace_dir = os.path.join(ROOT, ".bench_trace")
    tracer = harness._Tracer((0.0, harness.TRACE_S), trace_dir)
    win = harness.run_window(engine, cell, args.seed, args.seconds, tracer)
    rec = harness.summarize(win, engine)
    st = engine.stats()
    pool = engine.scfg.pool_pages
    half = [p for (a, _), p in zip(rec["ticks"], st.chunk_pages)
            if a >= args.seconds / 2]
    t = time.monotonic()
    red = tracereduce.load(tracereduce.find_xplane(trace_dir),
                           harness.device_ids(engine.mesh))
    rec["trace_read_s"] = time.monotonic() - t
    ctx = harness.context(cell, rec, red, peaks)
    ctx.trace_dir = trace_dir
    metrics = harness.read_metrics(cell, cell.per_layer, ctx)
    reqs = readers.window_requests(ctx)
    ttft = readers.ttft_s(ctx)
    at0 = [v for r, v in zip(reqs, ttft) if r["start"] == 0.0]
    later = [v for r, v in zip(reqs, ttft) if r["start"] > 0.0]
    pt = programtrace.of(ctx)
    spans = pt.spans if pt else []
    first = {}
    for name in ("serve.step", "serve.admit", "serve.prefill"):
        first[name] = [round(1e3 * s.dur, 3) for s in spans
                       if s.name == name][:20]
    print(json.dumps({
        "seed": args.seed, "requests": len(reqs), "at_t0": len(at0),
        "ttft_p95_ms": p95_ms(ttft), "ttft_p95_ms_t0": p95_ms(at0),
        "ttft_p95_ms_later": p95_ms(later),
        "ttft_ms_t0": sorted(round(1e3 * v, 3) for v in at0),
        "live_share_window": 100 * sum(st.chunk_pages)
        / (pool * len(st.chunk_pages)),
        "live_share_second_half": 100 * sum(half) / (pool * len(half))
        if half else None,
        "first_spans_ms": first,
        "metrics": {k: v["value"] for k, v in metrics.items()},
        "device": dev, "jax": jax.__version__}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
