"""Readings that set a cell's limits and its serving size; not part of a
benchmark run.  Needs the chip, like ``run.py``.

  python3 benchmarks/chip/calibrate.py --workload nm-chat \
      --seeds 11,12,13 --seconds 35
      Per seed, in one process: a whole run (set-up, window, comparison)
      with the control in the program's place.  One JSON line per seed:
      the program's gap numbers and whether they pass the cell's limits,
      the control's and whether they fail them, the end-to-end metrics.

  python3 benchmarks/chip/calibrate.py --workload nm-chat \
      --sweep serve.slots+clients=8,16,32 --seconds 20
      One window per value of one or more mix keys (joined by ``+``), each
      on an engine built anew from the seed: requests sent and finished,
      the end-to-end metrics, decode chunk time, each chip's peak memory
      so far, and the program's gap numbers on the cell's sample.

``--set key=value`` (repeatable) overrides a mix key first, for example
``--set prompt_tokens.multiple_of=1`` for prompts of any length.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import loadgen  # noqa: E402
import run as runner  # noqa: E402


def set_key(cell, dotted: str, value) -> None:
    """Set ``a.b=value`` in the cell's mix and re-derive its settings."""
    mix = json.loads(json.dumps(cell.mix))
    node = mix
    *path, last = dotted.split(".")
    for k in path:
        node = node[k]
    node[last] = value
    cell.mix = mix
    cell.settings = loadgen.serve_settings(mix)


def _num(text: str):
    return json.loads(text)


def seeds_mode(cell, seeds, seconds, log, peaks):
    for seed in seeds:
        t = time.monotonic()
        res = harness.run(cell, seed, seconds, False, t, log, peaks=peaks,
                          control=True)
        rec = res["record"]
        print(json.dumps({
            "seed": seed, "program_correct": res["program_correct"],
            "control_correct": res["correct"], **res["gaps"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "window_compiles": rec["window_compiles"],
            "reference_s": rec["reference_s"],
            "memory_peak_bytes": res["memory_peak_bytes"],
            "memory_peaks": res["memory_peaks"]}), flush=True)


def sweep_mode(cell, keys, values, seconds, seed, log):
    key = "+".join(keys)
    for value in values:
        for k in keys:
            set_key(cell, k, value)
        t = time.monotonic()
        eng = harness.build_engine(cell, seed)
        harness.warm_up(eng, cell)
        eng.reset_stats()
        warm_s = time.monotonic() - t
        log.phase = f"{key}={value}"
        win = harness.run_window(eng, cell, seed, seconds)
        window_compiles = log.count(log.phase)
        log.phase = "after"
        rec = harness.summarize(win, eng)
        rec["setup_s"] = warm_s
        ctx = harness.context(cell, rec)
        metrics = harness.read_metrics(cell, cell.end_to_end, ctx)
        chunk = rec["chunk_s"]
        mesh = eng.mesh
        mem = harness.memory_peaks(mesh)
        sample = harness.check_sample(win, seed,
                                      cell.limits["sample_tokens"])
        for r in win["recs"].values():
            r.handle = None
        del eng
        gc.collect()
        gaps = harness.logit_gaps(cell, mesh, seed, sample)["program"]
        reqs = rec["requests"]
        print(json.dumps({
            key: value, "warm_s": warm_s, "sent": len(reqs),
            "finished": sum(r["done"] for r in reqs),
            "tokens": sum(r["n"] for r in reqs), "ticks": len(rec["ticks"]),
            "chunk_ms_mean": 1e3 * sum(chunk) / max(1, len(chunk)),
            "metrics": {k: v["value"] for k, v in metrics.items()},
            "stats": rec["stats"],
            "memory_peak_bytes_so_far": harness.fullest(mem),
            "memory_peaks_so_far": mem,
            "window_compiles": window_compiles, **gaps}), flush=True)
        del win
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sweep", default="", help="key=v1,v2,...")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--set", action="append", default=[],
                    help="key=value, a mix override")
    args = ap.parse_args(argv)
    cell = harness.resolve(ROOT, args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        set_key(cell, k, _num(v))
    dev = runner.device_record(cell.chips)
    import jax
    from workcount import load_peaks
    peaks = load_peaks(dev["kind"])
    runner.configure_jax(ROOT)
    log = harness.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    if args.sweep:
        key, vals = args.sweep.split("=", 1)
        sweep_mode(cell, key.split("+"), [_num(v) for v in vals.split(",")],
                   args.seconds, args.seed, log)
    else:
        seeds_mode(cell, [int(s) for s in args.seeds.split(",")],
                   args.seconds, log, peaks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
