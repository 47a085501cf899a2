"""``chip_smoke.py``'s refusals, exercised on the CPU.

The smoke run itself needs a TPU; these tests check that it refuses to
run anywhere else, and that its gates refuse an engine that degraded:
a kernel failure, a dispatch-level ref fallback, or a plan that is not
on the compiled sparse kernels each make it exit non-zero.
"""

import importlib.util
import json
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro import models as MZ
from repro.core.sparse_linear import SparsityConfig, pack_params
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.serving import Engine, ServeConfig

ROOT = os.path.join(os.path.dirname(__file__), "..")
SMOKE = os.path.join(ROOT, "chip_smoke.py")

NM = SparsityConfig(format="nm", n=2, m=4, block_n=128)
TINY = ModelConfig(name="tiny-nm", n_layers=2, d_model=128, vocab_size=512,
                   n_heads=2, n_kv_heads=1, head_dim=128, d_ff=256,
                   remat=False, mlp_sparsity=NM, attn_sparsity=NM)
SCFG = ServeConfig(slots=2, max_len=64, prompt_pad=16, max_new_tokens=4,
                   decode_chunk=2, page_size=8, eos_token=-1)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return pack_params(MZ.init_model(jax.random.key(0), TINY), TINY)


@pytest.fixture(autouse=True)
def clear_override():
    yield
    dispatch.set_mode_override(None)


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_refuses_other_dispatch_modes(smoke, monkeypatch, mode):
    monkeypatch.setenv("REPRO_DISPATCH_MODE", mode)
    with pytest.raises(smoke.SmokeFailure, match="REPRO_DISPATCH_MODE"):
        smoke.check_dispatch_mode()


def test_gates_pass_a_clean_compiled_plan(smoke, params, monkeypatch):
    """Plans are resolved at engine construction: with the mode resolved
    to ``compiled`` (what a TPU gives) every packed weight's rows name
    ``nm_spmm`` and the fresh counters are zero."""
    monkeypatch.setattr(dispatch, "resolve_mode",
                        lambda impl="auto": "compiled")
    eng = Engine(TINY, make_mesh(), SCFG, params)
    lines = smoke.check_plans(eng, params)
    assert sum("nm_spmm compiled" in line for line in lines) == 3 * 7
    assert smoke.check_counters(eng)["kernel_failures"] == 0


def test_gates_refuse_ref_plans(smoke, params):
    eng = Engine(TINY, make_mesh(), SCFG, params)       # CPU: ref plans
    with pytest.raises(smoke.SmokeFailure, match="expected nm_spmm"):
        smoke.check_plans(eng, params)


def test_gates_refuse_a_kernel_failure(smoke, params):
    """A decode chunk whose dispatch raises is retried on the ref path
    and the requests still finish DONE — the smoke run must not pass."""
    eng = Engine(TINY, make_mesh(), SCFG, params)
    real = eng._invoke_loop
    calls = []

    def fail_once(loop, args):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("injected kernel failure")
        return real(loop, args)

    eng._invoke_loop = fail_once
    rng = np.random.default_rng(0)
    handles = [eng.submit(rng.integers(0, 512, size=8).astype(np.int32))
               for _ in range(2)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        eng.run()
    smoke.check_done(handles)
    with pytest.raises(smoke.SmokeFailure, match="kernel_failures"):
        smoke.check_counters(eng)


def test_gates_refuse_a_dispatch_fallback(smoke, params, monkeypatch):
    eng = Engine(TINY, make_mesh(), SCFG, params)
    monkeypatch.setattr(dispatch, "_FALLBACKS", 1)
    with pytest.raises(smoke.SmokeFailure, match="dispatch_fallbacks"):
        smoke.check_counters(eng)
