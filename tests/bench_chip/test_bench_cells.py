"""A cell is files plus a BENCHMARK.json entry; the comparison fails the
faults a serving cell can have and the lower-precision control."""


import bench_chip_helpers as H
import harness


def test_a_new_cell_is_files_and_an_entry(tmp_path):
    root = H.make_checkout(tmp_path)
    cell = harness.resolve(root, "small-cell")
    assert cell.cfg["hidden_size"] == 64
    assert cell.limits == {"sample_tokens": 40,
                           "compare": {"max_logit_gap": 0.05}}
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "ttft_p95_ms", "tpot_p95_ms"}
    assert cell.per_layer == []
    for m in harness.resolve(root, "nm-chat").per_layer:
        assert callable(harness.plugin(cell, "metrics", m["name"]).read)


def test_a_token_altered_where_it_is_produced_fails(tmp_path, monkeypatch):
    from repro.serving import Engine
    from repro.serving.state import TokenEvent
    step = Engine.step

    def bad_step(self):
        out = step(self)
        return [TokenEvent(e.uid, (e.token + 1) % self.cfg.vocab_size,
                           e.index, e.final) if e.index == 2 else e
                for e in out]

    monkeypatch.setattr(Engine, "step", bad_step)
    root = H.make_checkout(tmp_path)
    res = H.run_small(root, seed=2**35 + 17)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 0.05


def test_a_prefill_that_leaves_the_cache_unchanged_fails(tmp_path,
                                                         monkeypatch):
    """The per-slot prefill's merge returns the shared cache as it was:
    the slot decodes against pages its prompt never reached."""
    from repro import models as MZ
    monkeypatch.setattr(MZ, "merge_cache_slot",
                        lambda cache, slot_cache, slot: cache)
    root = H.make_checkout(tmp_path)
    res = H.run_small(root, seed=2**35 + 17)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 0.05


def test_control_fails_where_the_program_passes(tmp_path):
    """The control, the reference computed in float8 e4m3, put in the
    program's place on the same prompts and served tokens: the run's own
    comparison has to come out not correct, while the program's served
    tokens pass it."""
    root = H.make_checkout(tmp_path, limit=0.05, min_tokens=400)
    for seed in (1, 4):
        res = H.run_small(root, seed=seed, control=True)
        g = res["gaps"]
        assert res["program_correct"], g
        assert not res["correct"], res["checks"]
        assert res["checks"]["max_logit_gap"]["value"] \
            == g["control"]["max_logit_gap"] > 0.05
        assert g["program"]["max_logit_gap"] < 0.05, g
