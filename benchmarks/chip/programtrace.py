"""The program's own spans, scopes and counters in a ``--trace 1`` run,
read from the same ``.xplane.pb`` that ``tracereduce`` reduces.

The program marks its scheduler tick with host spans (``serve.step``,
``serve.admit``, ``serve.prefill``, ``serve.launch``, ``serve.fetch``,
``serve.collect``, ``serve.journal``; ``serve.collect`` carries the pool
pages in use and the pool's size as args) and names the parts of its
model step with ``jax.named_scope`` (``SCOPES``), which land in the
``op_name`` metadata of each compiled program's HLO.  The profiler keeps
that HLO on its metadata plane, one ``hlo_proto`` per program, keyed by
the program id that also names the program's runs on ``XLA Modules``
(``jit_loop(<id>)``).  From those:

  * host spans with their args, inside the traced window;
  * the scope map: each device op of a program run attributed to the
    innermost of ``SCOPES`` in its instruction's ``op_name``, ``(none)``
    when it has none (instructions the compiler adds);
  * idle gaps named by the innermost host span, ``serve.*`` included,
    and the idle time split among the host spans open over it.

A program without these spans or scopes (an older checkout) yields
nothing here, and the readers built on it return None.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

import readers
import tracereduce
from tracereduce import CONTAINERS, Op, gaps, innermost, union

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")    # run.py's trace_dir

SCOPES = ("layers", "attention", "mlp", "kv_cache", "lm_head", "sample")
NONE = "(none)"
SPAN_PREFIX = "serve."
PREFILL_PROGRAM = r"jit_prefill_slot\b"


# ---------------------------------------------------------------------------
# Protocol-buffer wire format, as far as the xplane and HLO protos need it
# ---------------------------------------------------------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one serialized message:
    an int for varint and fixed-width fields, a memoryview for
    length-delimited ones (sub-messages, strings, bytes)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(v) -> Tuple[int, Optional[memoryview]]:
    key, val = 0, None
    for num, x in fields(v):
        if num == 1:
            key = x
        elif num == 2:
            val = x
    return key, val


def _is_hlo(stat_name: str) -> bool:
    """The stat holding a program's HLO ("Hlo Proto" in jax 0.9)."""
    return stat_name.lower().replace(" ", "_") == "hlo_proto"


# XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5;
# XEventMetadata.id 1, name 2, stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, bytes_value 6
def hlo_protos(data: bytes) -> Dict[int, Tuple[str, memoryview]]:
    """{program id: (program name, serialized HloProto)} from the
    metadata plane of a serialized XSpace."""
    out: Dict[int, Tuple[str, memoryview]] = {}
    for num, plane in fields(data):
        if num != 1:
            continue
        parts = list(fields(plane))
        if not any(n == 2 and _text(v) == "/host:metadata"
                   for n, v in parts):
            continue
        stat_names = {}
        for n, v in parts:
            if n == 5:
                sid, sm = _map_entry(v)
                stat_names[sid] = next((_text(x) for k, x in fields(sm or b"")
                                        if k == 2), "")
        for n, v in parts:
            if n != 4:
                continue
            eid, em = _map_entry(v)
            name, proto = "", None
            for k, x in fields(em or b""):
                if k == 2:
                    name = _text(x)
                elif k == 5:
                    st = dict(fields(x))
                    if _is_hlo(stat_names.get(st.get(1), "")) and 6 in st:
                        proto = st[6]
            if proto is not None:
                out[eid] = (name, proto)
    return out


# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# opcode 2, metadata 7; OpMetadata.op_name 2
def instruction_op_names(proto) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (opcode, op_name)} over every computation of
    one serialized HloProto."""
    out: Dict[str, Tuple[str, str]] = {}
    for num, module in fields(proto):
        if num != 1:
            continue
        for k, comp in fields(module):
            if k != 3:
                continue
            for c, inst in fields(comp):
                if c != 2:
                    continue
                name = opcode = op_name = ""
                for f, x in fields(inst):
                    if f == 1:
                        name = _text(x)
                    elif f == 2:
                        opcode = _text(x)
                    elif f == 7:
                        op_name = next((_text(y) for g, y in fields(x)
                                        if g == 2), "")
                out[name] = (opcode, op_name)
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return NONE


def program_id(module: str) -> Optional[int]:
    """``jit_loop(1105)`` -> 1105."""
    m = re.search(r"\((\d+)\)\s*$", module)
    return int(m.group(1)) if m else None


# ---------------------------------------------------------------------------
# What the readers share: the program's spans and scope map of one trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: Dict[str, str]

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                          # serve.*, whole trace
    scopes: Dict[int, Dict[str, Tuple[str, str]]]   # program id -> map


def read_spans(path: str, prefix: str = SPAN_PREFIX) -> List[Span]:
    """Host events whose names start with ``prefix``, with their args."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = ev.start_ns * 1e-9
                    out.append(Span(ev.name, s, s + ev.duration_ns * 1e-9,
                                    {k: str(v) for k, v in ev.stats}))
    return sorted(out, key=lambda sp: sp.start)


def read(path: str) -> ProgramTrace:
    with open(path, "rb") as f:
        data = f.read()
    scopes = {pid: instruction_op_names(proto)
              for pid, (_, proto) in hlo_protos(data).items()}
    return ProgramTrace(spans=read_spans(path), scopes=scopes)


def of(ctx) -> Optional[ProgramTrace]:
    """The program trace of the run ``ctx`` reads (its traced window's
    ``.xplane.pb``), read once and kept on ``ctx`` for the other
    readers; None when the run was not traced or the file cannot be
    read."""
    if not hasattr(ctx, "program_trace"):
        ctx.program_trace = None
        path = None
        if getattr(ctx, "trace", None) is not None:
            path = ctx.tracereduce.find_xplane(
                getattr(ctx, "trace_dir", TRACE_DIR))
        if path is not None:
            try:
                ctx.program_trace = read(path)
            except (OSError, ValueError, IndexError) as e:
                print(f"[trace] program trace unreadable: {e!r}")
            else:
                report(ctx, ctx.program_trace)
    return ctx.program_trace


def in_window(spans: List[Span], window) -> List[Span]:
    return [s for s in spans if s.start >= window[0] and s.end <= window[1]]


def mean_span_ms(ctx, name: str) -> Optional[float]:
    """Mean milliseconds of span ``name`` over its runs in the traced
    window (one per tick for the tick's parts)."""
    pt = of(ctx)
    if pt is None:
        return None
    runs = [s.dur for s in in_window(pt.spans, ctx.trace.window)
            if s.name == name]
    return 1e3 * sum(runs) / len(runs) if runs else None


def live_share(spans: List[Span], window) -> Optional[float]:
    """Mean over the ticks in ``window`` of pool pages in use over pool
    pages (%), from the args of ``serve.collect``."""
    vals = []
    for s in in_window(spans, window):
        if s.name == "serve.collect" and "pages" in s.args:
            pool = float(s.args.get("pool", 0))
            if pool > 0:
                vals.append(float(s.args["pages"]) / pool)
    return 100.0 * sum(vals) / len(vals) if vals else None


def scope_table(trace, scopes, pattern: str, window
                ) -> Optional[Dict[str, List[float]]]:
    """Device seconds of the ops inside runs of programs matching
    ``pattern``, by innermost scope: {scope: [seconds, of which custom
    calls]}, plus ``"_runs"``: [program run seconds, op count].  None when
    no run's program has a scoped instruction."""
    chip = trace.chips[0]
    runs = tracereduce.modules_matching(chip, pattern, window)
    rx = re.compile(pattern)
    table: Dict[str, List[float]] = {}
    scoped = False
    n_ops = 0
    for o in chip.ops:
        if (o.name.startswith(CONTAINERS) or not rx.search(o.module)
                or o.start < window[0] or o.end > window[1]):
            continue
        hlo = scopes.get(program_id(o.module), {})
        opcode, op_name = hlo.get(o.name, ("", ""))
        sc = scope_of(op_name)
        scoped |= sc != NONE
        row = table.setdefault(sc, [0.0, 0.0])
        row[0] += o.end - o.start
        if opcode == "custom-call" or o.detail:
            row[1] += o.end - o.start
        n_ops += 1
    if not scoped:
        return None
    table["_runs"] = [sum(r.end - r.start for r in runs), float(n_ops)]
    return table


def decode_scope_ms(ctx, scope: str, custom_calls: bool = True
                    ) -> Optional[float]:
    """Device ms per decode step of the ops whose innermost scope is
    ``scope`` (custom calls left out with ``custom_calls=False``)."""
    pt = of(ctx)
    steps = readers.decode_steps(ctx)
    if pt is None or not steps:
        return None
    table = scope_table(ctx.trace, pt.scopes, readers.DECODE_PROGRAM,
                        ctx.trace.window)
    if table is None:
        return None
    sec, cc = table.get(scope, [0.0, 0.0])
    return 1e3 * (sec if custom_calls else sec - cc) / steps


def _idle(trace, spans: List[Span]):
    """The host spans (``bench.*`` and ``serve.*``) and chip 0's idle
    gaps in the traced window."""
    host = list(trace.host) + [Op(s.name, s.start, s.end) for s in spans]
    busy = union([(o.start, o.end) for o in trace.chips[0].ops],
                  trace.window)
    return host, gaps(busy, trace.window)


def named_gaps(trace, spans: List[Span], top: int = 10) -> List[list]:
    """The longest idle gaps of chip 0, each named by the innermost host
    span open at its midpoint."""
    host, holes = _idle(trace, spans)
    holes = sorted(holes, key=lambda g: g[0] - g[1])
    return [[innermost(host, (s + e) / 2), e - s] for s, e in holes[:top]]


def idle_by_span(trace, spans: List[Span]) -> Dict[str, float]:
    """Idle seconds of chip 0 in the traced window by host span: each
    idle gap is cut at every host span boundary inside it, and each
    piece goes to the innermost span open over it."""
    host, holes = _idle(trace, spans)
    out: Dict[str, float] = {}
    for a, b in holes:
        cuts = sorted({a, b} | {t for h in host for t in (h.start, h.end)
                                if a < t < b})
        for s, e in zip(cuts, cuts[1:]):
            name = innermost(host, (s + e) / 2)
            out[name] = out.get(name, 0.0) + (e - s)
    return out


def report(ctx, pt: ProgramTrace) -> None:
    """Earlier lines of a traced run: the span means per tick, the
    decode-loop scope table and the named idle gaps."""
    tr = ctx.trace
    spans = in_window(pt.spans, tr.window)
    for name in ("serve.step", "serve.admit", "serve.prefill",
                 "serve.launch", "serve.fetch", "serve.collect",
                 "serve.journal"):
        d = [s.dur for s in spans if s.name == name]
        if d:
            print(f"[trace] span {name}: {len(d)} runs, mean "
                  f"{1e3 * sum(d) / len(d):.4f} ms, total {sum(d):.6f} s")
    if not tr.chips:
        return
    table = scope_table(tr, pt.scopes, readers.DECODE_PROGRAM, tr.window)
    steps = readers.decode_steps(ctx)
    if table is not None and steps:
        run_s, n_ops = table.pop("_runs")
        ops_s = sum(v[0] for v in table.values())
        for sc, (sec, cc) in sorted(table.items(), key=lambda kv: -kv[1][0]):
            print(f"[trace] scope {sc}: {1e3 * sec / steps:.4f} ms a decode "
                  f"step ({1e3 * cc / steps:.4f} in custom calls), "
                  f"{100 * sec / run_s:.2f}% of the decode loop")
        print(f"[trace] scopes sum {1e3 * ops_s / steps:.4f} ms a decode step"
              f" over {int(n_ops)} ops; decode loop {1e3 * run_s / steps:.4f}"
              f" ms a step ({100 * ops_s / run_s:.2f}% covered)")
    for name, secs in named_gaps(tr, pt.spans):
        print(f"[trace] idle gap {name}: {secs:.6f} s")
    idle = idle_by_span(tr, pt.spans)
    total = sum(idle.values())
    for name, secs in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"[trace] idle inside {name}: {secs:.6f} s, "
              f"{100 * secs / total:.1f}% of the idle time")
