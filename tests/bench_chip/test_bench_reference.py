"""The plain reference against the program on the CPU, at the program's
``qwen3_0_6b.reduced()`` sizes with a 2:4 pack: the reference's logits
against the program's own forward pass on the same pruned weights, and
whole benchmark runs (the Engine's served tokens compared by the
reference) under both committed traffic shapes."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_chip_helpers as H
import harness


def test_reference_logits_match_the_program_forward():
    from repro import models as MZ
    ref = harness.load_module(
        os.path.join(H.CHIP, "reference", "qwen3.py"), "ref_qwen3_t")
    adapter = harness.load_module(
        os.path.join(H.CHIP, "adapters", "qwen3.py"), "adapter_qwen3_t")
    cfg = H.reduced_config()
    w = ref.make_weights(cfg, 2**40 + 3, H.mesh())
    mcfg = adapter.model_config(cfg)
    params = adapter.program_params(w, mcfg)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 37)
    got = np.asarray(MZ.model_logits(
        params, mcfg, {"tokens": jnp.asarray(tokens[None])}))[0]
    want = np.asarray(ref.logits_at(w, cfg, tokens, np.arange(37)))
    got = got[:, :cfg["vocab_size"]]
    # bf16 activations in the program against float32: a few 1e-2
    assert np.abs(got - want).max() < 0.05
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9


def test_reduced_gaps_match_the_full_logits():
    ref = harness.load_module(
        os.path.join(H.CHIP, "reference", "qwen3.py"), "ref_qwen3_g")
    cfg = H.reduced_config()
    w = ref.make_weights(cfg, 11, H.mesh())
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], 45)
    pos = np.arange(20, 45)
    pick = np.stack([tokens[21:46].tolist() + [0], np.arange(25)], 1)[:25]
    full = np.asarray(ref.logits_at(w, cfg, tokens, pos))
    best, first, picked = ref.best_and_picked(w, cfg, tokens, pos, pick)
    assert np.allclose(best, full.max(-1), atol=1e-5)
    assert (first == full.argmax(-1)).all()
    assert np.allclose(picked, np.take_along_axis(full, pick, 1), atol=1e-5)


def test_reference_reads_the_prompt_unpadded():
    import loadgen
    req = loadgen.Req(index=0, prompt=np.array([5, 6, 7], np.int32),
                      max_new=3, due_s=None)
    rec = harness._Rec(req, 16, 0.0, None)
    rec.tokens = [8, 9, 10]
    seq, pos = harness.served_sequence(rec)
    assert seq.tolist() == [5, 6, 7, 8, 9]
    assert pos.tolist() == [2, 3, 4]


def test_pruning_is_what_the_recipe_states():
    ref = harness.load_module(
        os.path.join(H.CHIP, "reference", "qwen3.py"), "ref_qwen3_p")
    cfg = H.reduced_config("qwen3-0.6b-csa")
    cfg.update(hidden_size=256, intermediate_size=512)
    w = ref.make_weights(cfg, 5, H.mesh())
    up = np.asarray(w["layers"]["up"][0], np.float32)        # (256, 512)
    groups = (up.reshape(64, 4, 4, 128) != 0).any(-1)       # (K/4, 4, N/g)
    tiles = (up.reshape(2, 128, 4, 128) != 0).any((1, 3))   # (Kb, Nb)
    assert (tiles.sum(0) == 1).all()        # half the tiles of each strip
    kept_groups = groups.sum(1)[np.repeat(tiles, 32, 0)]
    assert (kept_groups == 2).all()         # 2:4 inside the kept tiles
    q = np.asarray(w["layers"]["q"][0], np.float32)
    assert ((q.reshape(-1, 4, 1, 128) != 0).any(-1).sum(1) == 2).all()


@pytest.mark.parametrize("mix", ["chat", "longctx"])
def test_engine_serves_what_the_reference_computes(tmp_path, mix):
    root = H.make_checkout(tmp_path, mix=mix)
    res = H.run_small(root, seed=2**35 + 17)
    rec = res["record"]
    assert res["correct"], res["checks"]
    assert res["gaps"]["program"]["compared_tokens"] >= 40
    assert res["gaps"]["program"]["max_logit_gap"] < 0.05
    assert rec["window_compiles"] == {}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p95_ms", "tpot_p95_ms"}
    json.dumps(res["metrics"])
    cell = harness.resolve(root, "small-cell")
    rate = harness.plugin(cell, "metrics", "out_tok_s").read(
        harness.context(cell, rec))
    assert rate == pytest.approx(
        sum(r["n"] for r in rec["requests"]) / rec["window_s"])
