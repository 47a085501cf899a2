"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<id>``) whose
``XLA Ops`` line has one event per device operation and whose
``XLA Modules`` line has one event per run of a compiled program, plus
the host plane, where the harness's own ``TraceAnnotation`` spans
(``bench.*``) sit on the same clock.  Only the planes of the cell's
chips are read, in mesh order; a reading of one chip reads the first.
From those:

  * busy time: the union of the operation intervals inside the traced
    window (the harness's ``bench.traced`` span, else ``bench.window``),
    averaged over the cell's chips;
  * idle gaps: the holes in that union, each named by the innermost
    harness span open at the gap's midpoint;
  * per-name operation totals, and the operations of one kernel inside
    the runs of the programs whose name matches a pattern.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start s, end s)


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    module: str = ""                    # the program run it lies in
    detail: str = ""                    # stat text (custom-call names)


@dataclasses.dataclass
class Chip:
    ops: List[Op]
    modules: List[Op]


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    host: List[Op]                      # harness spans (bench.*)
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


# ---------------------------------------------------------------------------
# Interval arithmetic (the part the tests pin to hand numbers)
# ---------------------------------------------------------------------------

def union(intervals: List[Interval], window: Interval) -> List[Interval]:
    """Merged union of ``intervals`` clipped to ``window``."""
    lo, hi = window
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The idle holes of the window around the merged ``busy`` union."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def busy_s(chip: Chip, window: Interval) -> float:
    return sum(e - s for s, e in union([(o.start, o.end) for o in chip.ops],
                                       window))


def device_busy_s(trace: Trace) -> float:
    """Busy seconds averaged over the chips traced."""
    return sum(busy_s(c, trace.window) for c in trace.chips) \
        / max(len(trace.chips), 1)


def innermost(spans: List[Op], t: float) -> str:
    """Name of the shortest span containing time ``t`` ("host" if none)."""
    best = None
    for sp in spans:
        if sp.start <= t <= sp.end and (
                best is None or sp.end - sp.start < best.end - best.start):
            best = sp
    return best.name if best else "host"


def idle_gaps(trace: Trace, top: int = 10) -> List[list]:
    """The longest idle gaps of chip 0, as [what the host did, seconds]."""
    chip = trace.chips[0]
    busy = union([(o.start, o.end) for o in chip.ops], trace.window)
    holes = sorted(gaps(busy, trace.window), key=lambda g: g[0] - g[1])
    return [[innermost(trace.host, (s + e) / 2), e - s]
            for s, e in holes[:top]]


def op_totals(chip: Chip, window: Interval) -> Dict[str, float]:
    """Device seconds per operation (``program:instruction``), leaving out
    control-flow instructions, whose events enclose their bodies' ops."""
    out: Dict[str, float] = {}
    for o in chip.ops:
        if o.name.startswith(CONTAINERS):
            continue
        d = min(o.end, window[1]) - max(o.start, window[0])
        if d > 0:
            key = f"{program_name(o.module) or '?'}:{o.name}"
            out[key] = out.get(key, 0.0) + d
    return out


def top_ops(trace: Trace, top: int = 10) -> List[list]:
    tot = op_totals(trace.chips[0], trace.window)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]


def attribute_modules(chip: Chip) -> None:
    """Stamp each op with the program run whose interval contains it."""
    mods = sorted(chip.modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    for o in chip.ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.end <= mods[i].end + 1e-9:
            o.module = mods[i].name


def modules_matching(chip: Chip, pattern: str, window: Interval
                     ) -> List[Op]:
    rx = re.compile(pattern)
    return [m for m in chip.modules if rx.search(m.name)
            and m.start >= window[0] and m.end <= window[1]]


def kernel_ops(chip: Chip, kernel: str, module_pattern: str,
               window: Interval) -> List[Op]:
    """Device events of ``kernel`` inside runs of matching programs."""
    rx = re.compile(module_pattern)
    return [o for o in chip.ops
            if (o.name.startswith(kernel) or kernel in o.detail)
            and rx.search(o.module)
            and o.start >= window[0] and o.end <= window[1]]


# ---------------------------------------------------------------------------
# Reading the .xplane.pb
# ---------------------------------------------------------------------------

def find_xplane(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


OPERAND = re.compile(r"%[\w.\-]+")
TPU_PLANE = re.compile(r"/device:TPU:(\d+)(?!\d)")
CONTAINERS = ("while", "conditional", "call")


def split_hlo(text: str) -> Tuple[str, str]:
    """An ``XLA Ops`` event name is the HLO instruction's text,
    ``%name = shape op(operands), attributes``: returns the instruction's
    name and, for a custom call (where a Pallas kernel's name lives), the
    rest of the text with operand references removed — so that an
    instruction that merely consumes a kernel's output never matches."""
    if " = " not in text:
        return text, ""
    name, rest = text.split(" = ", 1)
    name = name.lstrip("%")
    if "custom-call" not in rest:
        return name, ""
    return name, OPERAND.sub("", rest)


def program_name(module: str) -> str:
    """``jit_loop(1105...)`` -> ``jit_loop``."""
    return module.split("(", 1)[0]


def load(path: str, device_ids: List[int], host_prefix: str = "bench."
         ) -> Trace:
    """The trace at ``path``: the planes of the chips ``device_ids``
    names, in that order, and the host's ``host_prefix`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    order = {d: i for i, d in enumerate(device_ids)}
    chips, host = [], []
    for plane in pd.planes:
        tpu = TPU_PLANE.match(plane.name)
        if tpu and "Host" not in plane.name:
            at = order.get(int(tpu.group(1)))
            if at is None:
                continue
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        name, det = split_hlo(ev.name)
                        ops.append(Op(name, s, s + ev.duration_ns * 1e-9,
                                      detail=det))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        mods.append(Op(ev.name, s,
                                       s + ev.duration_ns * 1e-9))
            chip = Chip(ops, mods)
            attribute_modules(chip)
            chips.append((at, chip))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        s = ev.start_ns * 1e-9
                        host.append(Op(ev.name, s,
                                       s + ev.duration_ns * 1e-9))
    chips = [c for _, c in sorted(chips, key=lambda ic: ic[0])]
    win = ([h for h in host if h.name == host_prefix + "traced"]
           or [h for h in host if h.name == host_prefix + "window"])
    if win:
        window = (win[0].start, win[0].end)
    else:
        ends = [o.end for c in chips for o in c.ops]
        starts = [o.start for c in chips for o in c.ops]
        window = (min(starts, default=0.0), max(ends, default=0.0))
    return Trace(chips=chips, host=host, window=window)
