"""Shared arithmetic of the metric readers (``metrics/<name>.py``).

The decode loop is found in the trace by the name of the program's
jitted function (``serving/loops.py`` ``build_decode_loop``, jitted
``loop``).
"""

from __future__ import annotations

import numpy as np

DECODE_PROGRAM = r"jit_loop\b"


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def window_requests(ctx):
    return [r for r in ctx.record["requests"] if r["due_in_window"]]


def ttft_s(ctx):
    end = ctx.record["window_s"]
    return [(r["first"] if r["first"] is not None else end) - r["start"]
            for r in window_requests(ctx)]


def tpot_s(ctx):
    return [(r["last"] - r["first"]) / (r["n"] - 1)
            for r in ctx.record["requests"] if r["done"] and r["n"] >= 2]


def span(ctx):
    """The host-clock interval the metrics read: the traced part of the
    window when the run was traced, else the whole window."""
    tr = ctx.record.get("traced")
    if tr and tr[0] is not None and tr[1] is not None:
        return tr[0], tr[1]
    return 0.0, ctx.record["window_s"]


def tick_host_ms(ctx):
    """Mean host time per scheduler tick: the harness's clock around
    ``Engine.step()`` minus the engine's own chunk time (launch, device
    wait and fetch of the decode chunk)."""
    ticks, chunks = ctx.record["ticks"], ctx.record["chunk_s"]
    if not ticks or len(ticks) != len(chunks):
        return None
    lo, hi = span(ctx)
    host = [(b - a) - c for (a, b), c in zip(ticks, chunks)
            if a >= lo and b <= hi]
    return 1e3 * float(np.mean(host)) if host else None


def idle_share(ctx):
    tr = ctx.trace
    if tr is None or not tr.chips or tr.window_s <= 0:
        return None
    busy = ctx.tracereduce.device_busy_s(tr)
    return 100.0 * (1.0 - busy / tr.window_s)


def program_runs(ctx, pattern):
    tr = ctx.trace
    if tr is None or not tr.chips:
        return []
    return ctx.tracereduce.modules_matching(tr.chips[0], pattern, tr.window)


def decode_steps(ctx):
    return len(program_runs(ctx, DECODE_PROGRAM)) \
        * ctx.settings["decode_chunk"]


def device_s(runs) -> float:
    return sum(r.end - r.start for r in runs)


def kernel_s(ctx, kernel, pattern) -> float:
    tr = ctx.trace
    if tr is None or not tr.chips:
        return 0.0
    ops = ctx.tracereduce.kernel_ops(tr.chips[0], kernel, pattern, tr.window)
    return sum(o.end - o.start for o in ops)


def decode_roofline(ctx, kernel):
    """Share of the roofline: the least time the chip could take for the
    kernel's calls at decode M (= slots) over the measured time of its
    events inside decode-loop runs."""
    t = kernel_s(ctx, kernel, DECODE_PROGRAM)
    steps = decode_steps(ctx)
    if t <= 0 or steps == 0:
        return None
    ideal = steps * ctx.workcount.kernel_ideal_s(
        ctx.cfg, kernel, ctx.settings["slots"], ctx.peaks)
    return 100.0 * ideal / t


def decode_mfu(ctx):
    """Operations of the tokens decoded in the traced span over the decode
    loop's device time at peak.  A request's first token comes from its
    prefill; token ``j >= 1`` attends to ``prompt + j`` rows."""
    t = device_s(program_runs(ctx, DECODE_PROGRAM))
    if t <= 0:
        return None
    wc = ctx.workcount
    lo, hi = span(ctx)
    ops = sum(wc.decode_token_ops(ctx.cfg, r["prompt_len"] + j)
              for r in ctx.record["requests"]
              for j, t in enumerate(r["times"]) if j >= 1 and lo <= t <= hi)
    return 100.0 * ops / (t * ctx.peaks["bf16_flops_per_s"])
