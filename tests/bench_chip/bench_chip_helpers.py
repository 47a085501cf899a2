"""Helpers of the benchmark's own tests: a copy of the benchmark in a
temporary checkout with one more cell, added as files and a
``BENCHMARK.json`` entry only — the model at the program's
``qwen3_0_6b.reduced()`` sizes with a 2:4 pack, under a small version of
a committed traffic mix."""

from __future__ import annotations

import json
import os
import shutil
import sys
import types

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CHIP = os.path.join(REPO, "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)


def mesh(chips: int = 1):
    """The mesh of a cell on ``chips`` chips."""
    import harness
    return harness.cell_mesh(types.SimpleNamespace(chips=chips))


def reduced_config(base: str = "qwen3-0.6b-nm", kv_heads=None) -> dict:
    """The committed configuration at ``qwen3_0_6b.reduced()``'s sizes
    (with ``kv_heads`` key/value heads, where given)."""
    from repro.configs import qwen3_0_6b
    r = qwen3_0_6b.reduced()
    with open(os.path.join(CHIP, "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name="qwen3-reduced", vocab_size=r.vocab_size,
               hidden_size=r.d_model, intermediate_size=r.d_ff,
               num_hidden_layers=r.n_layers, num_attention_heads=r.n_heads,
               num_key_value_heads=kv_heads or r.n_kv_heads,
               head_dim=r.head_dim)
    return cfg


def small_mix(name: str) -> dict:
    with open(os.path.join(CHIP, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["prompt_tokens"] = {"min": 8, "max": 40, "dist": "log-uniform",
                            "multiple_of": 16}
    mix["output_tokens"] = {"min": 4, "max": 24, "dist": "log-uniform"}
    mix["serve"].update(prompt_buckets=16, slots=4)
    if mix["loop"] == "open":
        mix["rate_per_s"] = 4.0
    else:
        mix["clients"] = 4
    return mix


def make_checkout(tmp, mix: str = "chat", limit: float = 0.05,
                  base: str = "qwen3-0.6b-nm", min_tokens: int = 40,
                  chips: int = 1, kv_heads=None) -> str:
    """A checkout holding the benchmark plus the cell ``small-cell`` on
    ``chips`` chips."""
    root = str(tmp)
    shutil.copytree(CHIP, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(chip, "configs", "qwen3-reduced.json"), "w") as f:
        json.dump(reduced_config(base, kv_heads), f)
    with open(os.path.join(chip, "traffic", "small.json"), "w") as f:
        json.dump(small_mix(mix), f)
    with open(os.path.join(chip, "limits", "small-cell.json"), "w") as f:
        json.dump({"sample_tokens": min_tokens,
                   "compare": {"max_logit_gap": limit}}, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "qwen3-reduced", "source": "test",
        "file": "benchmarks/chip/configs/qwen3-reduced.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "small-cell",
                               "config": "qwen3-reduced",
                               "traffic": "small", "chips": chips,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_small(root: str, seed: int, seconds: float = 2.0,
              control: bool = False) -> dict:
    """``run.py``'s run of ``small-cell`` with the device check skipped."""
    import time
    import jax
    import harness
    cell = harness.resolve(root, "small-cell")
    log = harness.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        return harness.run(cell, seed, seconds, False, time.monotonic(),
                           log, control=control)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
