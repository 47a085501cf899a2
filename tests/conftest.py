import os
import sys

# tests must see the real single CPU device (the 512-device override is
# dryrun.py-private); keep any user XLA_FLAGS out of the picture.
# Exception: REPRO_TEST_DEVICES=N (the sharded-smoke CI job) forces an
# N-way simulated host platform so the tensor-parallel serving tests run
# on a real multi-device mesh.
_n_dev = os.environ.get("REPRO_TEST_DEVICES")
if _n_dev:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(_n_dev)}")
else:
    os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    import hypothesis  # noqa: F401
except ImportError:
    # the hermetic container can't pip-install; register the deterministic
    # fallback so the property-test modules still collect and run.  CI
    # installs real hypothesis via `pip install -e .[test]`.
    import importlib.util

    _spec = importlib.util.spec_from_file_location(
        "hypothesis",
        os.path.join(os.path.dirname(__file__), "_hypothesis_fallback.py"))
    _mod = importlib.util.module_from_spec(_spec)
    sys.modules["hypothesis"] = _mod
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis.strategies"] = _mod.strategies

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled executables between test modules.  The suite
    compiles thousands of distinct program geometries; on the CPU
    backend letting them all accumulate in one process eventually
    segfaults inside XLA's compiler (deterministically, once the suite
    grew past ~350 tests).  Per-module clearing bounds the resident
    program count; callers re-jit transparently."""
    yield
    jax.clear_caches()


def reference_decode(params, cfg, prompt, max_new, eos, prompt_pad, max_len):
    """1-token-at-a-time greedy oracle for ONE request: batch-1 prefill,
    one decode_step + one host sync per token — seed-engine semantics.
    Shared by the serving and paged-serving suites (one oracle, two
    consumers)."""
    import jax.numpy as jnp
    import numpy as np
    from repro import models as MZ

    prompts = np.zeros((1, prompt_pad), np.int32)
    L = min(len(prompt), prompt_pad)
    prompts[0, prompt_pad - L:] = prompt[-L:]
    cache = MZ.init_cache(cfg, 1, max_len)
    logits, cache = MZ.prefill(params, cfg,
                               {"tokens": jnp.asarray(prompts)}, cache)
    tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)
    out = []
    pos = prompt_pad
    for t in range(max_new):
        tk = int(tok[0])
        out.append(tk)
        if tk == eos or t == max_new - 1 or pos + 1 >= max_len:
            break
        logits, cache = MZ.decode_step(params, cfg, tok, cache,
                                       jnp.asarray(pos))
        tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)
        pos += 1
    return out


def shaped_ops(hlo: str, shape) -> list:
    """``(name, opcode)`` of every instruction of an optimized HLO module,
    fused computations included, whose output has ``shape`` (any element
    type): what a program materializes at that shape."""
    import re
    dims = re.escape(",".join(str(int(d)) for d in shape))
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[" + dims
                      + r"\]\S* ([\w\-]+)\(", hlo, re.M)
