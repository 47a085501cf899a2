"""The program's spans, scopes and live-pages counter as the benchmark
reads them: spans of a reduced engine under the profiler, the scope map
from hand-written HLO protos over a synthetic trace, the live share of
the pool, and idle gaps named by the innermost ``serve.*`` span."""

import os
import time
import types

import jax
import numpy as np
import pytest

import bench_chip_helpers as H
import harness
import programtrace as pt
import readers
import tracereduce as tr

TICK_PARTS = ("serve.admit", "serve.launch", "serve.fetch", "serve.collect")


# ---------------------------------------------------------------------------
# Spans of a real engine under the profiler
# ---------------------------------------------------------------------------

def _within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_serve_spans_nest_under_the_profiler(tmp_path):
    from repro import models as MZ
    from repro.configs import qwen3_0_6b
    from repro.launch.mesh import make_host_mesh
    from repro.serving import Engine, ServeConfig
    cfg = qwen3_0_6b.reduced()
    params = MZ.init_model(jax.random.key(0), cfg)
    eng = Engine(cfg, make_host_mesh(), ServeConfig(
        slots=2, max_len=48, prompt_pad=16, max_new_tokens=8,
        decode_chunk=4, temperature=0.0, eos_token=-1, page_size=4,
        prompt_buckets=8), params)
    rng = np.random.default_rng(0)
    for n in (12, 7):                            # compile outside
        eng.submit(rng.integers(1, cfg.vocab_size, n), max_new=6)
    eng.run()
    eng.reset_stats()
    uids = [eng.submit(rng.integers(1, cfg.vocab_size, n), max_new=6).uid
            for n in (12, 7, 9)]
    jax.profiler.start_trace(str(tmp_path))
    ticks = 0
    while eng.queue or eng.num_live:
        eng.step()
        ticks += 1
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    host = tr.load(path, [0], host_prefix="serve.").host
    steps = [s for s in host if s.name == "serve.step"]
    assert len(steps) == ticks
    for s in host:
        if s.name in TICK_PARTS:
            assert sum(_within(s, st) for st in steps) == 1, s
        if s.name == "serve.prefill":
            assert any(_within(s, a) for a in host
                       if a.name == "serve.admit"), s
    # every profiled tick had live slots: one fetch each
    assert sum(s.name == "serve.fetch" for s in host) == ticks
    spans = pt.read_spans(path)
    pre = [s for s in spans if s.name == "serve.prefill"]
    assert [(int(s.args["uid"]), int(s.args["rows"])) for s in pre] \
        == [(uids[0], 16), (uids[1], 8), (uids[2], 16)]
    tick = [int(s.args["tick"]) for s in spans if s.name == "serve.step"]
    assert tick == list(range(tick[0], tick[0] + ticks))
    pages = [int(s.args["pages"]) for s in spans if s.name == "serve.collect"]
    assert pages == eng.stats().chunk_pages
    assert all(int(s.args["pool"]) == 2 * 12 for s in spans
               if s.name == "serve.collect")


def test_traced_run_reads_the_span_metrics(tmp_path, monkeypatch):
    """``harness.run`` with the profiler on, on the CPU: the span and
    counter metrics read something; the device-trace ones find no chip
    plane and are left out."""
    root = H.make_checkout(tmp_path)
    cell = harness.resolve(root, "small-cell")
    cell.per_layer = harness.resolve(root, "nm-chat").per_layer
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setattr(pt, "TRACE_DIR", trace_dir)
    log = harness.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        res = harness.run(cell, 2**33 + 5, 5.0, True, time.monotonic(),
                          log, trace_dir=trace_dir)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["tick_admit_ms.tput"] > 0 and m["tick_collect_ms.tput"] > 0
    assert 0 < m["kv_pool_live_share"] <= 100
    for name in ("prefill_ms", "decode_attn_ms", "decode_kv_ms"):
        assert name not in m


# ---------------------------------------------------------------------------
# The scope map on hand-written HLO and a synthetic trace
# ---------------------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _ld(num, payload):
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num, v):
    return _varint(num << 3) + _varint(v)


def _str(num, text):
    return _ld(num, text.encode())


def hlo_proto(module, insts):
    """HloProto{hlo_module{name, computations[{name, instructions}]}}."""
    body = b"".join(
        _ld(2, _str(1, n) + _str(2, opc) + (_ld(7, _str(2, opn)) if opn
                                            else b""))
        for n, opc, opn in insts)
    return _ld(1, _str(1, module) + _ld(3, _str(1, "body") + body))


def xspace(programs):
    """An XSpace whose metadata plane holds one ``Hlo Proto`` stat per
    program, keyed by program id, beside an empty host plane."""
    stat_md = _ld(5, _int(1, 3) + _ld(2, _int(1, 3) + _str(2, "Hlo Proto")))
    events = b"".join(
        _ld(4, _int(1, pid) + _ld(2, _int(1, pid) + _str(2, f"{name}({pid})")
                                  + _ld(5, _int(1, 3) + _ld(6, proto))))
        for pid, (name, proto) in programs.items())
    return (_ld(1, _str(2, "/host:CPU"))
            + _ld(1, _str(2, "/host:metadata") + events + stat_md)
            + _str(4, "localhost"))


LOOP = "jit(loop)/while/body/layers/while/body"
INSTS = [
    ("fusion.1", "fusion", LOOP + "/attention/dot_general"),
    ("custom-call.2", "custom-call", LOOP + "/attention/pallas_call"),
    ("scatter.3", "scatter", LOOP + "/attention/kv_cache/scatter"),
    ("copy.4", "copy", ""),
    ("fusion.6", "fusion", "jit(loop)/while/body/sample/argmax"),
    ("fusion.7", "fusion", LOOP + "/add"),
    ("while.5", "while", "jit(loop)/while"),
]


def _scoped_trace():
    """jit_loop(7) runs over [0, 10] s: attention 3 s + a 2-s custom call,
    kv_cache 1, the compiler's copy 2, layers' own add 1, sample 1; the
    ``while`` container encloses them all.  A prefill run follows."""
    ops = [tr.Op("fusion.1", 0.0, 3.0), tr.Op("custom-call.2", 3.0, 5.0,
                                               detail="nm_spmm"),
           tr.Op("scatter.3", 5.0, 6.0), tr.Op("copy.4", 6.0, 8.0),
           tr.Op("fusion.7", 8.0, 9.0), tr.Op("fusion.6", 9.0, 10.0),
           tr.Op("while.5", 0.0, 10.0), tr.Op("fusion.1", 11.0, 11.5)]
    mods = [tr.Op("jit_loop(7)", 0.0, 10.0),
            tr.Op("jit_prefill_slot(8)", 11.0, 12.0)]
    chip = tr.Chip(ops, mods)
    tr.attribute_modules(chip)
    return tr.Trace([chip], [tr.Op("bench.traced", 0.0, 20.0)], (0.0, 20.0))


def test_hlo_protos_and_op_names_from_hand_written_protos():
    data = xspace({7: ("jit_loop", hlo_proto("jit_loop", INSTS)),
                   8: ("jit_prefill_slot", hlo_proto("jit_prefill_slot", []))})
    protos = pt.hlo_protos(data)
    assert {pid: name for pid, (name, _) in protos.items()} == {
        7: "jit_loop(7)", 8: "jit_prefill_slot(8)"}
    ops = pt.instruction_op_names(protos[7][1])
    assert {n: pt.scope_of(o) for n, (_, o) in ops.items()} == {
        "fusion.1": "attention", "custom-call.2": "attention",
        "scatter.3": "kv_cache", "copy.4": "(none)", "fusion.6": "sample",
        "fusion.7": "layers", "while.5": "(none)"}
    assert ops["custom-call.2"][0] == "custom-call"
    assert pt.program_id("jit_loop(7)") == 7


def test_scope_table_sums_to_the_loop_time(monkeypatch):
    scopes = {7: pt.instruction_op_names(hlo_proto("jit_loop", INSTS))}
    trace = _scoped_trace()
    table = pt.scope_table(trace, scopes, readers.DECODE_PROGRAM,
                           trace.window)
    runs = table.pop("_runs")
    assert table == {"attention": [5.0, 2.0], "kv_cache": [1.0, 0.0],
                     "(none)": [2.0, 0.0], "layers": [1.0, 0.0],
                     "sample": [1.0, 0.0]}
    assert sum(v[0] for v in table.values()) == runs[0] == 10.0
    assert runs[1] == 6                         # the container left out
    ctx = types.SimpleNamespace(trace=trace, tracereduce=tr,
                                settings={"decode_chunk": 4})
    monkeypatch.setattr(pt, "of", lambda c: pt.ProgramTrace([], scopes))
    # one run of 4 steps: (5 - 2) s and 1 s over 4 steps
    assert pt.decode_scope_ms(ctx, "attention", custom_calls=False) \
        == pytest.approx(750.0)
    assert pt.decode_scope_ms(ctx, "kv_cache") == pytest.approx(250.0)
    for name, want in (("decode_attn_ms", 750.0), ("decode_kv_ms", 250.0),
                       ("prefill_ms", 1000.0)):
        mod = harness.load_module(
            os.path.join(H.CHIP, "metrics", name + ".py"), "t_" + name)
        assert mod.read(ctx) == pytest.approx(want)


def test_readers_find_nothing_in_an_unscoped_program(monkeypatch):
    """An older program: no ``serve.*`` spans, no scopes in its HLO, its
    prefill under the shared ``jit_step`` name."""
    unscoped = [(n, opc, "jit(loop)/while/body/dot") for n, opc, _ in INSTS]
    scopes = {7: pt.instruction_op_names(hlo_proto("jit_loop", unscoped))}
    trace = _scoped_trace()
    trace.chips[0].modules[1].name = "jit_step(8)"
    ctx = types.SimpleNamespace(trace=trace, tracereduce=tr,
                                settings={"decode_chunk": 4})
    monkeypatch.setattr(pt, "of", lambda c: pt.ProgramTrace([], scopes))
    for name in ("decode_attn_ms", "decode_kv_ms", "prefill_ms",
                 "tick_admit_ms.tput", "tick_collect_ms.tput",
                 "kv_pool_live_share"):
        mod = harness.load_module(
            os.path.join(H.CHIP, "metrics", name + ".py"), "u_" + name)
        assert mod.read(ctx) is None, name


# ---------------------------------------------------------------------------
# The live share of the pool, and idle gaps named by serve.* spans
# ---------------------------------------------------------------------------

def test_kv_pool_live_share_on_a_hand_record():
    """Ticks in the window hold 30, 45 and 60 of 150 pages (20, 30, 40%);
    the tick outside the window is left out."""
    spans = [pt.Span("serve.collect", t, t + 0.1,
                     {"pages": str(p), "pool": "150"})
             for t, p in ((1.0, 30), (2.0, 45), (3.0, 60), (9.5, 150))]
    spans.append(pt.Span("serve.admit", 1.5, 1.6, {}))
    assert pt.live_share(spans, (0.5, 9.0)) == pytest.approx(30.0)
    assert pt.live_share(spans[:1], (5.0, 9.0)) is None


def test_idle_gaps_named_by_the_innermost_serve_span():
    """Device idle over [4, 6] and [7, 9.5]: the first gap's midpoint
    lies in serve.collect inside serve.step inside bench.step, the
    second's in bench.wait alone."""
    ops = [tr.Op("fusion.1", 1.0, 4.0), tr.Op("fusion.1", 6.0, 7.0),
           tr.Op("fusion.1", 9.5, 10.0)]
    chip = tr.Chip(ops, [])
    host = [tr.Op("bench.window", 0.0, 10.0), tr.Op("bench.step", 3.0, 6.5),
            tr.Op("bench.wait", 7.2, 9.6)]
    trace = tr.Trace([chip], host, (0.0, 10.0))
    spans = [pt.Span("serve.step", 3.1, 6.4, {"tick": "3"}),
             pt.Span("serve.fetch", 3.2, 4.5, {}),
             pt.Span("serve.collect", 4.6, 5.5, {})]
    gaps = pt.named_gaps(trace, spans)
    assert gaps[0] == ["bench.wait", pytest.approx(2.5)]
    assert gaps[1] == ["serve.collect", pytest.approx(2.0)]
    assert gaps[2] == ["bench.window", pytest.approx(1.0)]
    assert tr.idle_gaps(trace)[1][0] == "bench.step"   # bench.* alone
    # [4, 6] splits into fetch 0.5, step 0.1, collect 0.9, step 0.5;
    # [7, 9.5] into window 0.2, wait 2.3; [0, 1] is the window's
    assert pt.idle_by_span(trace, spans) == pytest.approx({
        "serve.fetch": 0.5, "serve.step": 0.6, "serve.collect": 0.9,
        "bench.window": 1.2, "bench.wait": 2.3})
