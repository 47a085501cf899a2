"""One run of one cell: set-up, warm-up, the measured window, the
correctness comparison, and the metrics — everything but the device
check and the printing, which ``run.py`` does.

A cell is found by name.  ``BENCHMARK.json`` names its configuration
(whose file holds the model's sizes and sparsity recipe) and its traffic
mix (``traffic/<mix>.json``); ``limits/<cell>.json`` holds how many served
tokens the correctness sample takes and the limit of each number compared,
and
each metric is read by ``metrics/<metric>.py``.  The configuration's
``model_type`` picks the plain reference (``reference/<type>.py``, which
also makes the weights) and the adapter that hands those weights to the
program (``adapters/<type>.py``).  Adding a cell, a mix or a metric is
adding files and entries; nothing here changes.

A cell's ``chips`` sets its mesh, ``data=1 x model=chips`` on the first
``chips`` devices (``cell_mesh``), and the mesh sets everything else: the
weights are made in place on it, the ``Engine`` is built on it (and
picks its sharded backend from it), the reference runs on it, the
memory reading takes every chip of it and the fullest one is reported,
and the trace reduction reads its chips' planes.

A reference module provides ``make_weights(cfg, seed, mesh)``: one
jitted call with ``out_shardings``, each weight split over the mesh's
``model`` axis along an axis the reference names (replicated only where
that axis does not divide), the same values on any mesh; and
``best_and_picked(weights, cfg, tokens, positions, pick, control)``,
which the harness calls under ``jax.set_mesh(mesh)``.  A reference for a
model larger than one chip keeps its weights in the configuration's
dtype and upcasts one layer at a time to float32 at ``highest``
precision, since the whole model in float32 would not fit the mesh.

From the program the run uses only its normal entry points: the
adapter's ``ModelConfig`` and ``pack_params``, ``launch.mesh.make_mesh``
for the cell's mesh, ``serving.Engine`` on the paged backend with
``submit`` / ``step`` / ``run`` / ``stats`` / ``reset_stats``, and
``kernels.dispatch.fallback_count``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import loadgen  # noqa: E402

FAULT_COUNTERS = ("kernel_failures", "numeric_faults", "fetch_errors")
TRACE_S = 3.0           # seconds of the window a --trace 1 run traces
COUNTED_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec",
                  "/jax/core/compile/jaxpr_trace_duration")


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


# ---------------------------------------------------------------------------
# Finding a cell by name
# ---------------------------------------------------------------------------

def load_module(path: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"missing {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(root: str, workload: str) -> types.SimpleNamespace:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    chip_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(root, centry["file"])) as f:
        cfg = json.load(f)
    mix = loadgen.load_mix(os.path.join(chip_dir, "traffic",
                                        w["traffic"] + ".json"))
    with open(os.path.join(chip_dir, "limits", workload + ".json")) as f:
        limits = json.load(f)
    return types.SimpleNamespace(
        name=workload, chips=w["chips"], cfg=cfg, mix=mix,
        settings=loadgen.serve_settings(mix), limits=limits,
        chip_dir=chip_dir, root=root,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def plugin(cell, kind: str, name: str) -> types.ModuleType:
    """``<chip_dir>/<kind>/<name>.py`` as a module."""
    return load_module(os.path.join(cell.chip_dir, kind, name + ".py"),
                       f"bench_{kind}_{name}".replace(".", "_")
                       .replace("-", "_"))


# ---------------------------------------------------------------------------
# Compile counting (jax.monitoring), copied from chip_smoke.CompileLog
# ---------------------------------------------------------------------------

class CompileLog:
    """Counts compiles, cache loads and traces per phase."""

    def __init__(self):
        self.phase = "setup"
        self.rows: List[tuple] = []

    def __call__(self, event, secs, **kw):
        if event in COUNTED_EVENTS:
            self.rows.append((self.phase, event.rsplit("/", 1)[-1], secs))

    def count(self, phase: str, since: int = 0) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for p, ev, _ in self.rows[since:]:
            if p == phase:
                out[ev] = out.get(ev, 0) + 1
        return out


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def serve_config(cell, seed32: int):
    from repro.serving import ServeConfig
    s = cell.settings
    return ServeConfig(
        slots=s["slots"], max_len=s["max_len"], prompt_pad=s["prompt_pad"],
        max_new_tokens=s["max_new_tokens"], decode_chunk=s["decode_chunk"],
        temperature=0.0, eos_token=-1, page_size=s["page_size"],
        prompt_buckets=s["prompt_buckets"],
        page_view_chunk=s["page_view_chunk"], seed=seed32)


def cell_mesh(cell):
    """``data=1 x model=chips`` on the first ``chips`` devices."""
    import jax
    from repro.launch.mesh import make_mesh
    return make_mesh((1, cell.chips), devices=jax.devices()[:cell.chips])


def build_engine(cell, seed: int, parts: Optional[dict] = None):
    """Weights from ``seed`` (one jitted call, in place on the cell's
    mesh), packed by the program, served by a paged ``Engine`` on that
    mesh.  ``parts`` collects the seconds of each step."""
    import jax
    from repro.serving import Engine
    parts = {} if parts is None else parts
    t = time.monotonic()
    mesh = cell_mesh(cell)
    ref = plugin(cell, "reference", cell.cfg["model_type"])
    adapter = plugin(cell, "adapters", cell.cfg["model_type"])
    weights = jax.block_until_ready(ref.make_weights(cell.cfg, seed, mesh))
    parts["weights"] = time.monotonic() - t
    t = time.monotonic()
    mcfg = adapter.model_config(cell.cfg)
    params = jax.block_until_ready(adapter.program_params(weights, mcfg))
    del weights
    parts["pack"] = time.monotonic() - t
    t = time.monotonic()
    engine = Engine(mcfg, mesh, serve_config(cell, ref.seed32(seed)),
                    params)
    parts["engine"] = time.monotonic() - t
    return engine


def warm_up(engine, cell) -> int:
    """Serve one request per prompt bucket (one decode chunk each, at
    that bucket's attention view), then one of the largest bucket and the
    longest answer (every larger view).  With buckets no wider than a
    view step this reaches every program the window can run; the compile
    count printed for the window checks it.  Returns requests served."""
    rng = np.random.default_rng(0)
    vocab = cell.cfg["vocab_size"]
    bks = loadgen.buckets(cell.settings, cell.mix)
    plan = [(b, 1) for b in bks] + [(bks[-1],
                                     cell.settings["max_new_tokens"])]
    for rows, max_new in plan:
        engine.submit(rng.integers(0, vocab, size=rows, dtype=np.int32),
                      max_new=max_new)
        engine.run()
    return len(plan)


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------

class _Rec:
    __slots__ = ("req", "rows", "start", "first", "last", "tokens", "times",
                 "done", "handle")

    def __init__(self, req, rows, start, handle):
        self.req, self.rows, self.start = req, rows, start
        self.first = self.last = None
        self.tokens: List[int] = []
        self.times: List[float] = []
        self.done = False
        self.handle = handle


def trace_span(seconds: float) -> tuple:
    """The part of the window a ``--trace 1`` run traces: ``TRACE_S``
    seconds from one second in (a quarter of a short window)."""
    a = min(1.0, seconds / 4)
    return a, min(seconds, a + TRACE_S)


class _Tracer:
    """Starts the profiler at the span's start and stops it at its end,
    between scheduler ticks; ``bench.traced`` marks what it covered."""

    def __init__(self, span, log_dir):
        self.span, self.log_dir = span, log_dir
        self.state, self.ann, self.at = "idle", None, [None, None]

    def poll(self, now: float, clock) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        if self.state == "idle" and now >= self.span[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.ann = TraceAnnotation("bench.traced")
            self.ann.__enter__()
            self.at[0], self.state = clock(), "on"
        elif self.state == "on" and now >= self.span[1]:
            self.stop(clock)

    def stop(self, clock) -> None:
        import jax
        if self.state == "on":
            self.at[1] = clock()
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


def run_window(engine, cell, seed: int, seconds: float,
               tracer: Optional[_Tracer] = None) -> dict:
    """Offer the mix's load for ``seconds`` and record every request.
    Times are host-clock seconds from the window's start."""
    from jax.profiler import TraceAnnotation
    mix, s = cell.mix, cell.settings
    gen = loadgen.stream(mix, seed, cell.cfg["vocab_size"])
    recs: Dict[int, _Rec] = {}
    ticks: List[List[float]] = []
    lateness: List[float] = []
    pending = next(gen)
    closed = mix["loop"] == "closed"

    def submit(req, start):
        h = engine.submit(req.prompt, max_new=req.max_new)
        recs[h.uid] = _Rec(req, loadgen.prompt_rows(s, len(req.prompt)),
                           start, h)

    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0           # noqa: E731
        if closed:
            with TraceAnnotation("bench.submit"):
                for _ in range(mix["clients"]):
                    submit(pending, 0.0)
                    pending = next(gen)
        while True:
            now = clock()
            if tracer is not None:
                tracer.poll(now, clock)
            if now >= seconds:
                break
            if not closed:
                with TraceAnnotation("bench.submit"):
                    while pending.due_s <= now:
                        lateness.append(now - pending.due_s)
                        submit(pending, pending.due_s)
                        pending = next(gen)
                if not (engine.num_live or engine.num_queued):
                    with TraceAnnotation("bench.wait"):
                        time.sleep(max(0.0, min(pending.due_s, seconds)
                                       - clock()))
                    continue
            with TraceAnnotation("bench.step"):
                a = clock()
                events = engine.step()
                b = clock()
            ticks.append([a, b])
            for ev in events:
                r = recs[ev.uid]
                if r.first is None:
                    r.first = b
                r.tokens.append(int(ev.token))
                r.times.append(b)
                r.last = b
                if ev.final:
                    r.done = True
                    if closed:
                        with TraceAnnotation("bench.submit"):
                            submit(pending, b)
                            pending = next(gen)
        t_end = clock()
        if tracer is not None:
            tracer.stop(clock)
        if not closed:                  # due in the window, never sent
            while pending.due_s < t_end:
                recs[-1 - pending.index] = _Rec(
                    pending, loadgen.prompt_rows(s, len(pending.prompt)),
                    pending.due_s, None)
                pending = next(gen)
    return {"t_end": t_end, "recs": recs, "ticks": ticks,
            "lateness": lateness,
            "traced": tracer.at if tracer is not None else None}


def summarize(window: dict, engine) -> dict:
    """The window's record as plain data (what the metric readers see)."""
    from repro.serving.state import RequestStatus
    t_end = window["t_end"]
    reqs = []
    for uid, r in sorted(window["recs"].items()):
        status = r.handle.status if r.handle else RequestStatus.QUEUED
        reqs.append({
            "uid": uid, "prompt_len": int(len(r.req.prompt)),
            "rows": r.rows, "max_new": r.req.max_new, "start": r.start,
            "first": r.first, "last": r.last,
            "times": r.times,
            "n": len(r.tokens), "done": r.done,
            "failed": status not in (RequestStatus.DONE,
                                     RequestStatus.RUNNING,
                                     RequestStatus.QUEUED),
            "due_in_window": r.start < t_end})
    st = engine.stats()
    return {"window_s": t_end, "traced": window["traced"],
            "requests": reqs, "ticks": window["ticks"],
            "chunk_s": list(st.chunk_s), "lateness": window["lateness"],
            "stats": {k: getattr(st, k) for k in
                      FAULT_COUNTERS + ("degraded", "prefills", "sync_count",
                                        "admission_waits", "peak_pages")}}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def check_sample(window: dict, seed: int, min_tokens: int) -> List[_Rec]:
    """Finished requests drawn from the seed, the longest first, until
    they hold ``min_tokens`` served tokens."""
    done = [r for r in window["recs"].values() if r.done]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.rows + len(r.tokens)), r.req.index))
    out, n = [done[0]], len(done[0].tokens)
    rest = done[1:]
    for i in np.random.default_rng(int(seed)).permutation(len(rest)):
        if n >= min_tokens:
            break
        out.append(rest[int(i)])
        n += len(rest[int(i)].tokens)
    return out


def served_sequence(rec) -> tuple:
    """What the user sent and was served: the prompt as submitted, then
    every served token but the last; and the positions whose logits chose
    each served token.  (The program left-pads a prompt to its bucket and
    attends to the padding; the reference computes the model on the
    prompt alone.)"""
    prompt = np.asarray(rec.req.prompt, np.int32)
    seq = np.concatenate([prompt, np.asarray(rec.tokens[:-1], np.int32)])
    positions = np.arange(len(prompt) - 1, len(prompt) - 1 + len(rec.tokens))
    return seq, positions


def _gap_numbers(g: np.ndarray) -> dict:
    return {"max_logit_gap": float(g.max(initial=0.0)),
            "mean_logit_gap": float(g.mean()) if g.size else 0.0,
            "compared_tokens": int(g.size)}


def logit_gaps(cell, mesh, seed: int, sample, control: bool = False
               ) -> dict:
    """How far the served tokens' reference logits lie below the
    reference's best, over the sample: the widest gap and the mean gap
    per served token.  With ``control``, the same numbers for the control
    in the program's place: at each of those positions, the token that
    the reference computed one precision step lower puts first.  The
    reference makes the weights from ``seed`` anew on ``mesh`` and runs
    there; call it once the program's state is freed."""
    import jax
    ref = plugin(cell, "reference", cell.cfg["model_type"])
    weights = ref.make_weights(cell.cfg, seed, mesh)
    gaps, ctl_gaps = [], []
    with jax.set_mesh(mesh):
        for rec in sample:
            seq, pos = served_sequence(rec)
            served = np.asarray(rec.tokens, np.int32)
            pick = served[:, None]
            if control:
                _, ctl_first, _ = ref.best_and_picked(
                    weights, cell.cfg, seq, pos, pick, control=True)
                pick = np.stack([served, ctl_first], 1)
            best, _, picked = ref.best_and_picked(weights, cell.cfg, seq,
                                                  pos, pick)
            gaps.append(best - picked[:, 0])
            if control:
                ctl_gaps.append(best - picked[:, 1])
    out = {"program": _gap_numbers(np.concatenate(gaps) if gaps
                                   else np.zeros(0))}
    if control:
        out["control"] = _gap_numbers(np.concatenate(ctl_gaps) if ctl_gaps
                                      else np.zeros(0))
    return out


def checks(cell, record: dict, gaps: dict, sample) -> Dict[str, dict]:
    """Each number compared, beside its limit: the gap numbers the cell's
    limits file names, then the fault and sample checks (limit 0)."""
    st = record["stats"]
    faults = sum(int(st[c]) for c in FAULT_COUNTERS) + int(st["degraded"]) \
        + int(record["dispatch_fallbacks"])
    short = sum(len(r.tokens) != r.req.max_new for r in sample)
    out = {name: {"value": gaps[name], "limit": limit}
           for name, limit in cell.limits["compare"].items()}
    out.update({
        "compared_tokens_short": {
            "value": max(0, cell.limits["sample_tokens"]
                         - gaps["compared_tokens"]), "limit": 0},
        "short_answers": {"value": short, "limit": 0},
        "fault_counters": {"value": faults, "limit": 0},
    })
    return out


def passed(chk: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in chk.values())


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def read_metrics(cell, entries: List[dict], ctx) -> Dict[str, dict]:
    """Each metric's reader; a reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for m in entries:
        v = plugin(cell, "metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def memory_peaks(mesh) -> Dict[int, Optional[int]]:
    """``peak_bytes_in_use`` of each chip of ``mesh`` by device id, in
    mesh order (None where the backend reports none)."""
    return {int(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in mesh.devices.flat}


def fullest(peaks: Dict[int, Optional[int]]) -> Optional[int]:
    """The fullest chip's peak."""
    known = [p for p in peaks.values() if p is not None]
    return max(known) if known else None


def device_ids(mesh) -> List[int]:
    """The ids of ``mesh``'s chips, in mesh order."""
    return [int(d.id) for d in mesh.devices.flat]


def context(cell, record, trace=None, peaks=None):
    import tracereduce
    import workcount
    return types.SimpleNamespace(cfg=cell.cfg, settings=cell.settings,
                                 mix=cell.mix, record=record, trace=trace,
                                 peaks=peaks, workcount=workcount,
                                 tracereduce=tracereduce)


# ---------------------------------------------------------------------------
# One whole run
# ---------------------------------------------------------------------------

def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        log: CompileLog, trace_dir: Optional[str] = None,
        peaks: Optional[dict] = None, control: bool = False) -> dict:
    """Set up, measure, compare; returns the result object (without the
    device record, which the caller adds).  ``control`` (never in a
    benchmark run) compares the lower-precision control in place of the
    served tokens, which has to come out not correct."""
    from repro.kernels import dispatch

    log.phase = "setup"
    parts = {"start": time.monotonic() - t_start}
    engine = build_engine(cell, seed, parts)
    t = time.monotonic()
    warmed = warm_up(engine, cell)
    parts["warm_up"] = time.monotonic() - t
    engine.reset_stats()
    fallbacks0 = dispatch.fallback_count()
    tracer = None
    if trace:
        if os.path.isdir(trace_dir):
            shutil.rmtree(trace_dir)
        tracer = _Tracer(trace_span(seconds), trace_dir)
    setup_s = time.monotonic() - t_start
    mark = len(log.rows)
    log.phase = "window"
    window = run_window(engine, cell, seed, seconds, tracer)
    log.phase = "after"
    record = summarize(window, engine)
    record["setup_parts"] = parts
    for r in window["recs"].values():
        r.handle = None                 # handles hold the engine
    record["setup_s"] = setup_s
    record["dispatch_fallbacks"] = dispatch.fallback_count() - fallbacks0
    record["warmup_requests"] = warmed
    record["window_compiles"] = log.count("window", since=mark)
    mesh = engine.mesh
    chip_peaks = memory_peaks(mesh)
    # free the program's state before the reference takes the chips
    sample = check_sample(window, seed, cell.limits["sample_tokens"])
    del engine
    gc.collect()
    t_ref = time.monotonic()
    gaps = logit_gaps(cell, mesh, seed, sample, control=control)
    record["reference_s"] = time.monotonic() - t_ref
    # with ``control`` the control stands in the program's place
    chk = checks(cell, record, gaps["control" if control else "program"],
                 sample)
    program_correct = passed(checks(cell, record, gaps["program"], sample))
    red = None
    if trace:
        import tracereduce
        t = time.monotonic()
        path = tracereduce.find_xplane(trace_dir)
        red = tracereduce.load(path, device_ids(mesh)) if path else None
        record["trace_read_s"] = time.monotonic() - t
        record["trace_chips"] = len(red.chips) if red else 0
    ctx = context(cell, record, red, peaks)
    metrics = read_metrics(cell, cell.per_layer if trace
                           else cell.end_to_end, ctx)
    reqs = [r for r in record["requests"] if r["due_in_window"]]
    result = {
        "correct": passed(chk),
        "attempted": len(reqs),
        "failed": sum(r["failed"] for r in reqs),
        "metrics": metrics,
        "memory_peak_bytes": fullest(chip_peaks),
        "memory_peaks": chip_peaks,
        "record": record,
        "gaps": gaps,
        "checks": chk,
        "program_correct": program_correct,
    }
    if red is not None and red.chips:
        import tracereduce
        result["busy_s"] = tracereduce.device_busy_s(red)
        result["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": tracereduce.top_ops(red),
                               "idle_gaps": tracereduce.idle_gaps(red)}
        result["top_ops"] = tracereduce.top_ops(red, 30)
    return result
