"""Dispatcher coverage: registry selection matches the sparsity
descriptor, the CPU fallback equals the ref numerics, and the autotune
cache round-trips through its JSON file."""

import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pruning, sparsity
from repro.kernels import dispatch, ref


def rand(seed, shape):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.fixture()
def packs():
    w = rand(1, (256, 128))
    out = {}
    wp, _ = pruning.n_m(w, 2, 4, group=128)
    out["nm"] = sparsity.pack_nm(wp, 2, 4, g=128)
    wb, _ = pruning.block_semi_structured(w, 0.5, block=128)
    out["block"] = sparsity.pack_block_sparse(wb, 128, 128)
    wc, _ = pruning.combined_nm(w, 0.5, 2, 4, group=128, block=128)
    out["combined"] = sparsity.pack_combined(wc, 2, 4, 128, 128)
    wl, _ = pruning.block_semi_structured(w, 0.5, block=4)
    out["lookahead"] = sparsity.LookaheadPack.from_float(wl)
    return out


@pytest.fixture()
def isolated_cache(tmp_path):
    cache = dispatch.AutotuneCache(str(tmp_path / "autotune.json"))
    old = dispatch.set_autotune_cache(cache)
    yield cache
    dispatch.set_autotune_cache(old)


class TestDescriptor:
    def test_kinds(self, packs):
        assert dispatch.SparsityDescriptor.of(packs["nm"]).kind == "nm"
        assert dispatch.SparsityDescriptor.of(packs["block"]).kind == "block"
        assert dispatch.SparsityDescriptor.of(
            packs["combined"]).kind == "combined"
        assert dispatch.SparsityDescriptor.of(
            packs["lookahead"]).kind == "lookahead"
        assert dispatch.SparsityDescriptor.of(
            jnp.zeros((8, 8))).kind == "dense"

    def test_pattern_strings(self, packs):
        assert dispatch.SparsityDescriptor.of(packs["nm"]).pattern \
            == "2:4g128"
        assert dispatch.SparsityDescriptor.of(
            packs["block"]).pattern.startswith("bsr128x128")

    def test_abstract_leaves_ok(self, packs):
        """Descriptors build from eval_shape'd packs (serving plan path)."""
        ab = jax.eval_shape(lambda: packs["block"])
        d = dispatch.SparsityDescriptor.of(ab)
        assert d.kind == "block" and d.density is not None


class TestSelection:
    def test_registry_matches_descriptor(self, packs):
        expect = {"nm": "nm_spmm", "block": "bsr_matmul",
                  "combined": "csa_matmul", "lookahead": "lookahead_decode"}
        for kind, kernel in expect.items():
            d = dispatch.select(packs[kind], M=128)
            assert d.kernel == kernel, (kind, d)

    def test_cpu_auto_resolves_ref(self, packs):
        assert not dispatch.has_tpu()        # suite runs on the CPU backend
        assert dispatch.select(packs["nm"], M=128).mode == "ref"

    def test_kernel_impl_resolves_interpret_off_tpu(self, packs):
        d = dispatch.select(packs["nm"], M=128, impl="kernel")
        assert d.mode == "interpret"
        assert d.blocks.get("bm") == 128

    def test_env_override(self, packs, monkeypatch):
        monkeypatch.setenv("REPRO_DISPATCH_MODE", "ref")
        assert dispatch.select(packs["nm"], M=128, impl="kernel").mode \
            == "ref"
        monkeypatch.setenv("REPRO_DISPATCH_MODE", "bogus")
        with pytest.raises(ValueError):
            dispatch.resolve_mode("auto")

    def test_bad_impl_raises(self, packs):
        with pytest.raises(ValueError):
            dispatch.select(packs["nm"], M=128, impl="nope")


class TestNumerics:
    """CPU fallback (ref) and forced interpret agree with the oracles."""

    def test_cpu_fallback_equals_ref(self, packs):
        x = rand(2, (64, 256))
        oracles = {"nm": ref.nm_spmm_ref, "block": ref.bsr_matmul_ref,
                   "combined": ref.csa_matmul_ref,
                   "lookahead": ref.lookahead_matmul_ref}
        for kind, oracle in oracles.items():
            out = dispatch.sparse_matmul(x, packs[kind])
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(oracle(x, packs[kind])),
                rtol=2e-5, atol=1e-4, err_msg=kind)

    def test_interpret_equals_ref(self, packs):
        x = rand(3, (100, 256))              # M=100: exercises bm padding
        for kind in ("nm", "block", "combined"):
            out_i = dispatch.sparse_matmul(x, packs[kind], impl="kernel")
            out_r = dispatch.sparse_matmul(x, packs[kind], impl="ref")
            np.testing.assert_allclose(
                np.asarray(out_i), np.asarray(out_r),
                rtol=2e-5, atol=1e-3, err_msg=kind)

    def test_dense_passthrough(self):
        x, w = rand(4, (32, 64)), rand(5, (64, 16))
        np.testing.assert_allclose(
            np.asarray(dispatch.sparse_matmul(x, w)),
            np.asarray(x @ w), rtol=2e-5)

    def test_under_jit(self, packs):
        x = rand(6, (64, 256))
        f = jax.jit(lambda x: dispatch.sparse_matmul(x, packs["nm"]))
        np.testing.assert_allclose(
            np.asarray(f(x)),
            np.asarray(dispatch.sparse_matmul(x, packs["nm"])),
            rtol=2e-5, atol=1e-4)

    def test_attention_modes_agree(self):
        q, k, v = (rand(s, (1, 2, 128, 64)) for s in (7, 8, 9))
        a_ref = dispatch.attention(q, k, v, impl="ref")
        a_int = dispatch.attention(q, k, v, impl="kernel")
        np.testing.assert_allclose(np.asarray(a_int), np.asarray(a_ref),
                                   rtol=2e-5, atol=2e-5)


class TestAutotuneCache:
    def test_roundtrip_through_json(self, packs, isolated_cache):
        x = rand(10, (64, 256))
        best = dispatch.tune(x, packs["nm"], mode="ref",
                             candidates=[{"bm": 64}, {"bm": 128}], reps=1)
        assert best in ({"bm": 64}, {"bm": 128})
        # persisted: a fresh cache object reads the same decision back
        fresh = dispatch.AutotuneCache(isolated_cache.path)
        key = dispatch.cache_key(
            "nm_spmm", 64, dispatch.SparsityDescriptor.of(packs["nm"]),
            "ref")
        stored = fresh.get(key)
        assert stored is not None and stored["bm"] == best["bm"]
        assert "us" in stored
        # raw file is valid JSON with exactly that key
        with open(isolated_cache.path) as f:
            raw = json.load(f)
        assert set(raw) == {key}

    def test_cache_hit_skips_sweep(self, packs, isolated_cache):
        x = rand(11, (64, 256))
        key = dispatch.cache_key(
            "nm_spmm", 64, dispatch.SparsityDescriptor.of(packs["nm"]),
            "ref")
        isolated_cache.put(key, {"bm": 64, "us": 1.0})
        # candidates that would fail if actually run prove no sweep happens
        best = dispatch.tune(x, packs["nm"], mode="ref",
                             candidates=[{"bm": -1}])
        assert best == {"bm": 64}

    def test_select_uses_cached_blocks(self, packs, isolated_cache):
        desc = dispatch.SparsityDescriptor.of(packs["nm"])
        key = dispatch.cache_key("nm_spmm", 64, desc, "interpret")
        isolated_cache.put(key, {"bm": 64, "bkc": 64, "us": 2.0})
        d = dispatch.select(packs["nm"], M=64, impl="kernel")
        assert d.blocks == {"bm": 64, "bkc": 64}

    def test_corrupt_file_starts_empty(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        cache = dispatch.AutotuneCache(str(p))
        assert len(cache) == 0
        cache.put("k", {"bm": 128})
        assert dispatch.AutotuneCache(str(p)).get("k") == {"bm": 128}

    def test_truncated_json_warns_once_and_rebuilds(self, tmp_path):
        """A crash mid-write leaves a truncated file: the cache must
        warn exactly once, start empty, and rebuild on the next put —
        never raise into the serving path."""
        p = tmp_path / "autotune.json"
        p.write_text('{"k1": {"bm": 64, "us": 1.0}, "k2": {"bm"')
        with pytest.warns(RuntimeWarning, match="autotune cache"):
            cache = dispatch.AutotuneCache(str(p))
            assert cache.get("k1") is None
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # no second warning
            assert cache.get("k2") is None
            cache.put("k3", {"bm": 128})
        assert dispatch.AutotuneCache(str(p)).get("k3") == {"bm": 128}
        assert json.load(open(p)) == {"k3": {"bm": 128}}

    def test_wrong_shape_payload_salvages_dict_entries(self, tmp_path):
        p = tmp_path / "autotune.json"
        p.write_text('{"good": {"bm": 64}, "bad": 3}')
        cache = dispatch.AutotuneCache(str(p))
        with pytest.warns(RuntimeWarning):    # load is lazy: first read
            assert cache.get("good") == {"bm": 64}
        assert cache.get("bad") is None
        p.write_text('[1, 2, 3]')               # valid JSON, wrong shape
        with pytest.warns(RuntimeWarning):
            assert dispatch.AutotuneCache(str(p)).get("x") is None


class TestModeOverride:
    """set_mode_override: the engine's degraded-mode lever — outranks
    both the caller's impl and the REPRO_DISPATCH_MODE env."""

    def test_override_beats_env_and_impl(self, packs, monkeypatch):
        monkeypatch.setenv("REPRO_DISPATCH_MODE", "interpret")
        prev = dispatch.set_mode_override("ref")
        try:
            assert prev is None
            assert dispatch.mode_override() == "ref"
            assert dispatch.resolve_mode("compiled") == "ref"
            assert dispatch.select(packs["nm"], M=128,
                                   impl="kernel").mode == "ref"
        finally:
            dispatch.set_mode_override(None)
        assert dispatch.resolve_mode("compiled") == "interpret"

    def test_override_validated(self):
        with pytest.raises(ValueError):
            dispatch.set_mode_override("bogus")

    def test_raising_kernel_falls_back_to_ref(self, packs, monkeypatch):
        """A sparse fast path that raises at run time degrades that call
        to the jnp oracle with a warning — it never takes the caller
        down (satellite of the engine's degraded mode)."""
        real = dispatch._REGISTRY["nm_spmm"]

        def boom(x, w, mode, blocks):
            raise RuntimeError("tile explosion")
        monkeypatch.setitem(
            dispatch._REGISTRY, "nm_spmm",
            dataclasses.replace(real, run=boom))
        x = rand(21, (64, 256))
        # the counter is process-wide: put it back at teardown so later
        # tests in this worker (chip_smoke's counter gate) start clean
        monkeypatch.setattr(dispatch, "_FALLBACKS", dispatch.fallback_count())
        before = dispatch.fallback_count()
        with pytest.warns(RuntimeWarning, match="falling back"):
            out = dispatch.sparse_matmul(x, packs["nm"], impl="kernel")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.nm_spmm_ref(x, packs["nm"])),
            rtol=2e-5, atol=1e-4)
        assert dispatch.fallback_count() == before + 1

    def test_compiled_tune_with_no_candidate_raises(self, packs,
                                                     monkeypatch,
                                                     isolated_cache):
        """On the chip a sweep in which no candidate compiles is a fault:
        it raises with the last error instead of returning defaults."""
        real = dispatch._REGISTRY["nm_spmm"]

        def boom(x, w, mode, blocks):
            raise RuntimeError("tile explosion")
        monkeypatch.setitem(
            dispatch._REGISTRY, "nm_spmm",
            dataclasses.replace(real, run=boom))
        x = rand(22, (64, 256))
        with pytest.raises(RuntimeError, match="no block candidate") as ei:
            dispatch.tune(x, packs["nm"], mode="compiled", reps=1)
        assert "tile explosion" in str(ei.value.__cause__)
        # interpret sweeps keep the defaults (CPU tests of the machinery)
        assert dispatch.tune(x, packs["nm"], mode="interpret", reps=1)
        assert len(isolated_cache) == 0


class TestPlan:
    def test_plan_params_lists_packed_weights(self, packs):
        params = {"layers": {"mlp": {"w_in": packs["nm"],
                                     "w_out": packs["block"]},
                             "norm": {"scale": jnp.ones((8,))}}}
        plan = dispatch.plan_params(params, M=64)
        by_name = {p["param"]: p for p in plan}
        assert set(by_name) == {"layers/mlp/w_in", "layers/mlp/w_out"}
        assert by_name["layers/mlp/w_in"]["kernel"] == "nm_spmm"
        assert by_name["layers/mlp/w_out"]["kernel"] == "bsr_matmul"
        assert all(p["mode"] == "ref" for p in plan)   # CPU backend
