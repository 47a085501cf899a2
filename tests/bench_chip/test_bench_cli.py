"""The command refuses to run without a TPU, and in a checkout that holds
only the benchmark, printing no result."""

import json
import os
import shutil
import subprocess
import sys

import bench_chip_helpers as H


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"),
         "--workload", "nm-chat", "--seed", str(2**32 + 9), "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=120)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except ValueError:
            pass


def test_exits_nonzero_on_the_cpu():
    p = _run(H.REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p)


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    with open(os.path.join(H.REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for rel in paths:
        shutil.copytree(os.path.join(H.REPO, rel), tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(H.REPO, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    _no_result(p)
