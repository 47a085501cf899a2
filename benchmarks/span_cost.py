"""Host cost of the engine's spans when no profiler is running.

  JAX_PLATFORMS=cpu python -m benchmarks.span_cost

Times ``with jax.profiler.TraceAnnotation(name[, args])`` around an
empty body, the form ``Engine.step`` uses for its ``serve.*`` spans,
against the same loop with no span, and prints nanoseconds per span for
each form (median of several repeats).  A tick enters six spans, plus
one per prefill dispatch.
"""

from __future__ import annotations

import statistics
import time

from jax.profiler import TraceAnnotation


def _loop(n: int, form: str) -> float:
    t = time.perf_counter_ns()
    if form == "none":
        for i in range(n):
            pass
    elif form == "plain":
        for i in range(n):
            with TraceAnnotation("serve.admit"):
                pass
    else:
        for i in range(n):
            with TraceAnnotation("serve.prefill", uid=i, rows=128):
                pass
    return (time.perf_counter_ns() - t) / n


def main(n: int = 200_000, repeats: int = 7) -> dict:
    out = {}
    for form in ("none", "plain", "args"):
        out[form] = statistics.median(_loop(n, form) for _ in range(repeats))
    for form in ("plain", "args"):
        print(f"span {form}: {out[form] - out['none']:.1f} ns a span "
              f"(loop {out[form]:.1f} ns, empty loop {out['none']:.1f} ns)")
    return out


if __name__ == "__main__":
    main()
