"""The traffic generator: deterministic per seed, inside the mix's
parameters, and the same work for every seed in another order."""

import itertools
import json
import os

import numpy as np
import pytest

from bench_chip_helpers import CHIP
import loadgen


def _mix(name):
    return loadgen.load_mix(os.path.join(CHIP, "traffic", name + ".json"))


def _take(mix, seed, n):
    return list(itertools.islice(loadgen.stream(mix, seed, 151936), n))


@pytest.mark.parametrize("name", ["chat", "longctx"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a, b = _take(mix, 2**33 + 5, 70), _take(mix, 2**33 + 5, 70)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)
    c = _take(mix, 2**33 + 6, 70)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat", "longctx"])
def test_within_parameters_and_same_multiset(name):
    mix = _mix(name)
    n = mix["sizes_per_block"]
    blocks = [_take(mix, s, n) for s in (1, 2, 3)]
    for blk in blocks:
        for r in blk:
            assert mix["prompt_tokens"]["min"] <= len(r.prompt) \
                <= mix["prompt_tokens"]["max"]
            assert mix["output_tokens"]["min"] <= r.max_new \
                <= mix["output_tokens"]["max"]
            assert r.prompt.dtype == np.int32 and r.prompt.max() < 151936
    sizes = [sorted((len(r.prompt), r.max_new) for r in blk)
             for blk in blocks]
    assert sizes[0] == sizes[1] == sizes[2]
    orders = [[(len(r.prompt), r.max_new) for r in blk] for blk in blocks]
    assert orders[0] != orders[1]


def test_open_loop_arrivals_same_span_every_seed():
    mix = _mix("longctx")
    n = mix["sizes_per_block"]
    ends = [_take(mix, s, n)[-1].due_s for s in (1, 2)]
    assert ends[0] == pytest.approx(ends[1])
    # the block's gaps are exponential quantiles: mean 1/rate
    assert ends[0] / n == pytest.approx(1.0 / mix["rate_per_s"], rel=0.05)
    due = [r.due_s for r in _take(mix, 1, 3 * n)]
    assert all(b > a for a, b in zip(due, due[1:]))


def test_closed_loop_has_no_schedule():
    assert all(r.due_s is None for r in _take(_mix("chat"), 1, 10))


def test_serve_settings_sized_to_the_traffic():
    s = loadgen.serve_settings(_mix("chat"))
    assert (s["prompt_pad"], s["max_new_tokens"], s["max_len"]) \
        == (448, 922, 448 + 922 + 16)
    assert loadgen.buckets(s, _mix("chat")) == list(range(32, 449, 32))
    assert loadgen.prompt_rows(s, 33) == 64
    lc = _mix("longctx")
    s = loadgen.serve_settings(lc)
    assert loadgen.buckets(s, lc) == list(range(1024, 3073, 128))
    assert s["max_len"] == 3072 + 127 + 16


@pytest.mark.parametrize("name", ["chat", "longctx"])
def test_prompts_fill_their_bucket(name):
    """Every prompt is a whole number of buckets, so the program pads
    none (it attends to left padding)."""
    mix = _mix(name)
    s = loadgen.serve_settings(mix)
    for r in _take(mix, 2**40 + 1, mix["sizes_per_block"]):
        assert loadgen.prompt_rows(s, len(r.prompt)) == len(r.prompt)


def test_chat_lengths_keep_the_source_means():
    """The chat mix's block keeps the means the vLLM paper gives for
    ShareGPT (161.31 prompt and 337.99 answer tokens) within 2%."""
    sizes = loadgen.size_block(_mix("chat"))
    assert np.mean([p for p, _ in sizes]) == pytest.approx(161.31, rel=0.02)
    assert np.mean([o for _, o in sizes]) == pytest.approx(337.99, rel=0.02)


def test_committed_mixes_parse():
    for f in os.listdir(os.path.join(CHIP, "traffic")):
        with open(os.path.join(CHIP, "traffic", f)) as fh:
            assert json.load(fh)["loop"] in ("open", "closed")
