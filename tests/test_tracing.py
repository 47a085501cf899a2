"""What the engine leaves for a profile to read: the pool pages in use
per decode chunk, the named scopes in the compiled decode loop's HLO,
and the per-slot prefill program's name."""

import re

import jax
import numpy as np
import pytest

from repro import models as MZ
from repro.configs import qwen3_0_6b
from repro.launch.mesh import make_host_mesh
from repro.serving import Engine, ServeConfig, loops
from repro.serving.state import init_decode_state

SCOPES = ("layers", "attention", "mlp", "kv_cache", "lm_head", "sample")


@pytest.fixture(scope="module")
def model():
    cfg = qwen3_0_6b.reduced()
    return cfg, MZ.init_model(jax.random.key(0), cfg)


def _scfg(**kw):
    base = dict(slots=2, max_len=32, prompt_pad=8, max_new_tokens=10,
                decode_chunk=4, temperature=0.0, eos_token=-1, page_size=4,
                prompt_buckets=8)
    base.update(kw)
    return ServeConfig(**base)


def test_chunk_pages_counts_the_pool_pages_in_use(model):
    """Two requests on two slots, 4-row pages, 4-token chunks, both
    prompts in the 8-row bucket.  A (10 tokens) needs ceil(18/4) = 5
    pages at most, B (5 tokens) ceil(13/4) = 4.  Before each chunk a
    slot holds ceil((rows + 4) / 4) pages, capped at its need: chunk 1
    rows 8 and 8 -> 3 + 3; chunk 2 rows 12 and 12 -> 4 + 4 (B then
    finishes and frees its pages); chunk 3 rows 16 -> 5."""
    cfg, params = model
    eng = Engine(cfg, make_host_mesh(), _scfg(), params)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, cfg.vocab_size, 8), max_new=10)
    eng.submit(rng.integers(1, cfg.vocab_size, 3), max_new=5)
    eng.run()
    st = eng.stats()
    assert st.chunk_pages == [6, 8, 5]
    assert len(st.chunk_pages) == len(st.chunk_s)
    assert st.peak_pages == 8
    assert not hasattr(st, "chunk_tokens")


def test_chunk_pages_is_zero_without_a_pool(model):
    cfg, params = model
    eng = Engine(cfg, make_host_mesh(), _scfg(page_size=0,
                                              prompt_buckets=0), params)
    eng.submit(np.arange(1, 6), max_new=6)
    eng.run()
    st = eng.stats()
    assert st.chunk_pages == [0] * len(st.chunk_s) and st.chunk_s


def _op_name_scopes(text: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', text)
    return {p for n in names for p in n.split("/") if p in SCOPES}


def test_decode_loop_hlo_names_every_scope(model):
    """The compiled paged decode loop at ``qwen3_0_6b.reduced()`` carries
    every scope name in its instructions' ``op_name`` metadata, and the
    per-slot prefill program is named ``prefill_slot``."""
    cfg, params = model
    scfg = _scfg(page_view_chunk=2)
    mesh = make_host_mesh()
    ap = jax.eval_shape(lambda: params)
    ac = jax.eval_shape(lambda: MZ.init_cache(
        cfg, scfg.slots, scfg.max_len, page_size=scfg.page_size,
        num_pages=scfg.pool_pages))
    with jax.set_mesh(mesh):
        loop = loops.build_decode_loop(cfg, mesh, scfg, ap, ac, paged=True,
                                       view_pages=4)
        state = jax.eval_shape(lambda: init_decode_state(scfg.slots))
        temps = jax.ShapeDtypeStruct((scfg.slots,), np.float32)
        key = jax.eval_shape(lambda: jax.random.key(0))
        ptab = jax.ShapeDtypeStruct((scfg.slots, scfg.max_pages), np.int32)
        text = loop.lower(ap, ac, state, temps, key, ptab).compile().as_text()
        assert _op_name_scopes(text) == set(SCOPES)
        prefill = loops.build_prefill_slot_step(cfg, mesh, scfg, ap, ac,
                                                prompt_rows=8, paged=True)
        i32 = jax.ShapeDtypeStruct((), np.int32)
        lowered = prefill.lower(
            ap, {"tokens": jax.ShapeDtypeStruct((1, 8), np.int32)}, ac,
            state, i32, i32, jax.ShapeDtypeStruct((), np.float32), key,
            jax.ShapeDtypeStruct((scfg.max_pages,), np.int32))
    assert lowered.as_text().startswith("module @jit_prefill_slot")
