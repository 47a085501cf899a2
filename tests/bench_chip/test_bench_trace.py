"""The trace reduction and the roofline arithmetic on a synthetic trace,
against numbers worked by hand."""

import types

import pytest

import bench_chip_helpers  # noqa: F401  (puts the benchmark on sys.path)
import readers
import tracereduce as tr
import workcount

PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}


def _trace():
    """Window [0, 10] s; ops cover [1, 3] and [2, 4] (overlapping),
    [6, 7] and [9.5, 11] (clipped at 10): busy 2 + 1 + 0.5 = 4.5 s."""
    ops = [tr.Op("fusion.1", 1.0, 3.0), tr.Op("nm_spmm", 2.0, 4.0),
           tr.Op("nm_spmm", 6.0, 7.0), tr.Op("fusion.1", 9.5, 11.0)]
    mods = [tr.Op("jit_loop(7)", 0.5, 4.5), tr.Op("jit_step(3)", 5.5, 7.5)]
    chip = tr.Chip(ops, mods)
    tr.attribute_modules(chip)
    host = [tr.Op("bench.window", 0.0, 10.0), tr.Op("bench.step", 4.2, 5.0),
            tr.Op("bench.wait", 7.2, 9.6)]
    return tr.Trace(chips=[chip], host=host, window=(0.0, 10.0))


def test_union_gaps_and_busy():
    t = _trace()
    busy = tr.union([(o.start, o.end) for o in t.chips[0].ops], t.window)
    assert busy == [(1.0, 4.0), (6.0, 7.0), (9.5, 10.0)]
    assert tr.gaps(busy, t.window) == [(0.0, 1.0), (4.0, 6.0), (7.0, 9.5)]
    assert tr.device_busy_s(t) == pytest.approx(4.5)
    ctx = types.SimpleNamespace(trace=t, tracereduce=tr)
    assert readers.idle_share(ctx) == pytest.approx(55.0)


def test_idle_gaps_named_by_host_span():
    gaps = tr.idle_gaps(_trace())
    assert gaps[0] == ["bench.wait", pytest.approx(2.5)]     # 7.0-9.5
    assert gaps[1] == ["bench.step", pytest.approx(2.0)]     # 4.0-6.0
    assert gaps[2] == ["bench.window", pytest.approx(1.0)]   # 0.0-1.0


def test_op_totals_and_modules():
    t = _trace()
    tot = dict(tr.top_ops(t))
    assert tot == pytest.approx({"jit_loop:fusion.1": 2.0,
                                 "jit_loop:nm_spmm": 2.0,
                                 "jit_step:nm_spmm": 1.0,
                                 "?:fusion.1": 0.5})       # clipped at 10
    chip = t.chips[0]
    assert [o.module for o in chip.ops] == ["jit_loop(7)", "jit_loop(7)",
                                            "jit_step(3)", ""]
    dec = tr.kernel_ops(chip, "nm_spmm", readers.DECODE_PROGRAM, t.window)
    pre = tr.kernel_ops(chip, "nm_spmm", r"jit_step\b", t.window)
    assert [(o.start, o.end) for o in dec] == [(2.0, 4.0)]
    assert [(o.start, o.end) for o in pre] == [(6.0, 7.0)]


def test_roofline_and_mfu_from_the_trace():
    """One decode-loop run of 4 steps at M=2 on a one-layer model whose
    sparse projections are q, k, v, o (K=N=128, 2:4): kept 8192 values, so a
    call is 2*2*8192 = 32768 operations and 2*(8192 + 2*128 + 2*128)
    = 17408 bytes; bytes bound it: 17408 / 800e9 = 21.76 ns a call."""
    cfg = {"hidden_size": 128, "intermediate_size": 256,
           "num_hidden_layers": 1, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 128, "vocab_size": 1000,
           "sparsity": {"attention": {"format": "nm", "n": 2, "m": 4,
                                      "group": 128}}}
    assert workcount.projection(cfg, "q", 2) == (32768.0, 17408.0)
    assert workcount.kernel_ideal_s(cfg, "nm_spmm", 2, PEAKS) \
        == pytest.approx(4 * 17408 / 800e9)     # q, k, v, o share it
    ops = [tr.Op("nm_spmm", 1e-6 * i, 1e-6 * i + 1e-7) for i in range(16)]
    chip = tr.Chip(ops, [tr.Op("jit_loop", 0.0, 2e-5)])
    tr.attribute_modules(chip)
    trace = tr.Trace([chip], [], (0.0, 1.0))
    ctx = types.SimpleNamespace(trace=trace, tracereduce=tr, cfg=cfg,
                                settings={"slots": 2, "decode_chunk": 4},
                                peaks=PEAKS, workcount=workcount,
                                record={"window_s": 1.0, "requests": [
                                    {"prompt_len": 10, "n": 3,
                                     "times": [0.1, 0.2, 0.3]}]})
    # 4 steps x 4 calls x 21.76 ns ideal over 16 x 100 ns measured
    assert readers.decode_roofline(ctx, "nm_spmm") \
        == pytest.approx(100 * 16 * 21.76e-9 / 1.6e-6)
    # tokens 1 and 2 of the request: contexts 11 and 12
    per_row = 2 * 4 * 8192 + 2 * 128 * 256 * 3
    want = sum(per_row + 2 * 128 * 1000 + 4 * 128 * c for c in (11, 12))
    assert readers.decode_mfu(ctx) == pytest.approx(
        100 * want / (2e-5 * 200e12))


def test_hlo_text_split():
    name, det = tr.split_hlo(
        "%fusion.3 = bf16[8,2048]{1,0} fusion(bf16[8,2048]{1,0} %nm_spmm.2)")
    assert (name, det) == ("fusion.3", "")     # a consumer never matches
    name, det = tr.split_hlo(
        '%custom-call.7 = bf16[8,2048]{1,0} custom-call(bf16[8,1024]{1,0} '
        '%nm_spmm.2), custom_call_target="tpu_custom_call", '
        'backend_config="csa_matmul"')
    assert name == "custom-call.7"
    assert "csa_matmul" in det and "nm_spmm" not in det
    assert tr.program_name("jit_loop(1105)") == "jit_loop"


def test_reader_finds_nothing_returns_none():
    ctx = types.SimpleNamespace(trace=None, tracereduce=tr)
    assert readers.idle_share(ctx) is None
    assert readers.decode_roofline(
        types.SimpleNamespace(trace=None, tracereduce=tr, settings={
            "decode_chunk": 16}), "nm_spmm") is None
