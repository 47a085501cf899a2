"""Operations and bytes at the Qwen3-0.6B shapes, against hand numbers,
and the table of peaks."""

import json
import os

import pytest

from bench_chip_helpers import CHIP
import workcount


def _cfg(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def test_nm_projections_at_decode_m():
    cfg = _cfg("qwen3-0.6b-nm")
    # q: K=1024, N=2048, 2:4 keeps 1,048,576 values
    assert workcount.kept(cfg, "q") == 1024 * 2048 / 2
    ops, nbytes = workcount.projection(cfg, "q", 8)
    assert ops == 2 * 8 * 1048576
    assert nbytes == 2 * (1048576 + 8 * 1024 + 8 * 2048)
    # down: K=3072, N=1024
    assert workcount.kept(cfg, "down") == 3072 * 1024 / 2
    assert workcount.kernel_of(cfg, "down") == "nm_spmm"


def test_combined_mlp_keeps_a_quarter():
    cfg = _cfg("qwen3-0.6b-csa")
    # down: 24 K-tiles per strip, 12 kept, 2:4 inside: 1/4 of 3072 x 1024
    assert workcount.kept(cfg, "down") == 3072 * 1024 / 4
    assert workcount.kept(cfg, "gate") == 1024 * 3072 / 4
    assert workcount.kernel_of(cfg, "up") == "csa_matmul"
    assert workcount.kernel_of(cfg, "q") == "nm_spmm"


def test_decoded_token_operations():
    cfg = _cfg("qwen3-0.6b-nm")
    kept = (1024 * 2048 * 2 + 1024 * 1024 * 2 + 1024 * 3072 * 3) / 2
    per_row = 2 * 28 * kept                          # 431 MFLOP a token
    head = 2 * 1024 * 151936
    attn = 4 * 16 * 128 * 28
    assert workcount.decode_token_ops(cfg, 100) == pytest.approx(
        per_row + head + attn * 100)


def test_roofline_takes_the_larger_bound():
    peaks = workcount.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    cfg = _cfg("qwen3-0.6b-nm")
    ops, nbytes = workcount.projection(cfg, "up", 8)
    assert workcount.roofline_s(ops, nbytes, peaks) == nbytes / 819e9
    ops, nbytes = workcount.projection(cfg, "up", 2048)
    assert workcount.roofline_s(ops, nbytes, peaks) == ops / 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        workcount.load_peaks("TPU v9 imaginary")
