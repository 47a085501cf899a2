"""On-chip serving benchmark: one run of one cell.

  python3 benchmarks/chip/run.py --workload nm-chat --seed 7 \
      --seconds 10 --trace 0

Runs from the root of a checkout on a machine holding the chips the cell
asks for, in one process: checks the device (a TPU, as many chips as the
cell needs; there is no CPU fallback), builds the cell's model from
``--seed``, warms every program the window can reach (set-up), offers the
cell's traffic for ``--seconds``, compares a sample of what was served
with the plain float32 reference, and prints the result as the last line
of standard output.  ``--trace 1`` runs the window under the profiler
and reports the per-layer metrics instead of the end-to-end ones.

Earlier lines report the compiles counted inside the window, requests
sent / succeeded / failed, generator lateness and each chip's peak
device memory;
the last lines of standard error give each number compared beside its
limit.  JAX's persistent compilation cache is kept at ``.jax_cache``
in the checkout, and the trace at ``.bench_trace``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
# the TPU runtime writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def device_record(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; refuses anything but
    enough TPU chips."""
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"platform {rec['platform']}, device_kind {rec['kind']}, "
          f"{rec['count']} devices, jax {jax.__version__}")
    if rec["platform"] != "tpu":
        raise harness.BenchError(f"no TPU: JAX runs on {rec['platform']!r}")
    if rec["count"] < chips:
        raise harness.BenchError(f"the cell needs {chips} chips, JAX sees "
                                 f"{rec['count']}")
    return rec


def configure_jax(root: str) -> str:
    import jax
    cache = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def report(cell, res: dict, dev: dict) -> None:
    """Earlier lines, the stderr check lines, then the result line."""
    rec = res["record"]
    reqs = [r for r in rec["requests"] if r["due_in_window"]]
    late = rec["lateness"]
    print(f"[window] compiles inside the window: {rec['window_compiles'] or 0}"
          f" (warm-up served {rec['warmup_requests']} requests)")
    print(f"[window] requests sent {len(reqs)}, succeeded "
          f"{sum(r['done'] for r in reqs)}, failed {res['failed']}, "
          f"unfinished {sum(not r['done'] for r in reqs)}; "
          f"{sum(r['n'] for r in rec['requests'])} tokens in "
          f"{rec['window_s']:.3f} s; {len(rec['ticks'])} ticks")
    if late:
        print(f"[window] generator lateness: max {max(late) * 1e3:.3f} ms, "
              f"mean {sum(late) / len(late) * 1e3:.3f} ms over {len(late)} "
              f"arrivals")
    else:
        print("[window] generator lateness: none (closed loop, each client "
              "sends when its reply is complete)")
    print(f"[window] engine counters: {rec['stats']}, dispatch fallbacks "
          f"{rec['dispatch_fallbacks']}")
    print(f"[setup] {rec['setup_s']:.2f} s: " + ", ".join(
        f"{k} {v:.2f} s" for k, v in rec["setup_parts"].items()))
    if "trace_read_s" in rec:
        print(f"[trace] traced {rec['traced']} s of the window, read in "
              f"{rec['trace_read_s']:.2f} s from {rec['trace_chips']} chip "
              f"planes")
        for name, secs in res.get("top_ops", []):
            print(f"[trace] device op {name}: {secs:.6f} s")
    print("[device] peak memory per chip: " + ", ".join(
        f"chip {i} {b} bytes" for i, b in res["memory_peaks"].items())
        + f"; fullest {res['memory_peak_bytes']} bytes")
    print(f"[check] reference took {rec['reference_s']:.2f} s over "
          f"{res['gaps']['program']['compared_tokens']} served tokens: "
          f"{res['gaps']}")
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"],
           "device": dict(dev, memory_peak_bytes=res["memory_peak_bytes"])}
    if "busy_s" in res:
        out["device"].update(busy_s=res["busy_s"], window_s=res["window_s"])
        out["breakdown"] = res["breakdown"]
    out["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve(ROOT, args.workload)
        dev = device_record(cell.chips)
        import jax
        from workcount import load_peaks
        peaks = load_peaks(dev["kind"])
        configure_jax(ROOT)
        log = harness.CompileLog()
        jax.monitoring.register_event_duration_secs_listener(log)
        res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, log,
                          trace_dir=os.path.join(ROOT, ".bench_trace"),
                          peaks=peaks)
    except (harness.BenchError, KeyError, ImportError, OSError) as e:
        print(f"benchmark cannot run: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    report(cell, res, dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
