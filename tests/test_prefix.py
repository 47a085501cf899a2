"""Prefix-sharing radix cache over the paged backend: bit-parity of
shared-prefix serving, copy-on-write at divergence, refcounted page
lifecycle (cancel / retire / release), the pinned register_prefix API,
the one-sync-per-chunk contract under sharing and the typed stats
surface."""

import warnings

import jax
import numpy as np
import pytest

from conftest import reference_decode
from repro import models as MZ
from repro.models.config import ModelConfig
from repro.serving import Engine, EngineStats, ServeConfig
from repro.serving.prefix import PrefixIndex
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)
PS = 8


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Every Engine here builds its own jitted prefill/decode programs;
    drop them at module teardown so the single-process tier-1 run's
    live-executable count stays at its pre-PR level (XLA's CPU backend
    has crashed compiling late files when it doesn't)."""
    yield
    jax.clear_caches()


def scfg_shared(**kw):
    base = dict(slots=2, max_len=64, prompt_pad=16, max_new_tokens=5,
                decode_chunk=4, eos_token=-1, page_size=PS,
                prefix_cache=True)
    base.update(kw)
    return ServeConfig(**base)


class TestPrefixIndex:
    """Host-side trie logic, no device arrays involved."""

    def _blocks(self, *vals):
        return [np.full(PS, v, np.int32) for v in vals]

    def test_match_walks_full_blocks(self):
        idx = PrefixIndex(PS)
        a, b = self._blocks(1, 2)
        n1, _ = idx.insert(None, a, 10)
        n2, _ = idx.insert(n1, b, 11)
        idx.acquire(n1), idx.acquire(n2)
        tokens = np.concatenate([a, b, self._blocks(3)[0]])
        nodes, partial = idx.match(tokens, len(tokens))
        assert [n.page for n in nodes] == [10, 11]
        assert partial is None

    def test_partial_match_longest_common_row_prefix(self):
        idx = PrefixIndex(PS)
        blk = np.arange(PS, dtype=np.int32)
        node, _ = idx.insert(None, blk, 7)
        idx.acquire(node)
        query = blk.copy()
        query[5:] += 100                    # diverges at row 5
        nodes, partial = idx.match(query, PS)
        assert nodes == []
        assert partial is not None and partial[0] is node
        assert partial[1] == 5
        # divergence at row 0 is no match at all
        nodes, partial = idx.match(query + 1, PS)
        assert nodes == [] and partial is None

    def test_insert_duplicate_not_created(self):
        idx = PrefixIndex(PS)
        blk = self._blocks(4)[0]
        n1, created1 = idx.insert(None, blk, 3)
        n2, created2 = idx.insert(None, blk, 9)
        assert created1 and not created2 and n2 is n1
        assert n1.page == 3                 # first page wins

    def test_release_retains_then_capacity_evicts_lru(self):
        idx = PrefixIndex(PS, capacity=1)
        a, b = self._blocks(1, 2)
        n1, _ = idx.insert(None, a, 10)
        n2, _ = idx.insert(None, b, 11)
        idx.acquire(n1), idx.acquire(n2)
        assert idx.release(n1) == []        # retained, within cap
        assert idx.retained_pages == 1 and idx.live_pages == 1
        freed = idx.release(n2)             # over cap → LRU (n1) evicted
        assert freed == [10]
        assert idx.retained_pages == 1 and idx.live_pages == 0
        # evicted node is gone from the trie
        nodes, _ = idx.match(a, PS)
        assert nodes == []

    def test_evict_one_skips_inner_nodes(self):
        idx = PrefixIndex(PS)
        a, b = self._blocks(1, 2)
        n1, _ = idx.insert(None, a, 10)
        n2, _ = idx.insert(n1, b, 11)
        idx.acquire(n1), idx.acquire(n2)
        idx.release(n2), idx.release(n1)
        # n1 still has a child → only the leaf n2 is evictable first
        assert idx.evict_one() == 11
        assert idx.evict_one() == 10
        assert idx.evict_one() is None


def _engines(params, shared_kw=None, unshared_kw=None):
    shared = Engine(TINY, mesh11(), scfg_shared(**(shared_kw or {})),
                    params)
    unshared = Engine(TINY, mesh11(),
                      scfg_shared(prefix_cache=False,
                                  **(unshared_kw or {})), params)
    return shared, unshared


class TestSharedParity:
    def test_shared_bit_parity_and_fewer_pages(self, params):
        """Two prompts with a 12-token common head must decode to the
        same tokens whether pages are shared, private, or monolithic —
        and sharing must hold fewer pages at peak."""
        head = np.arange(1, 13, dtype=np.int32)
        prompts = [np.concatenate([head, [101, 102, 103, 104]]).astype(
                       np.int32),
                   np.concatenate([head, [201, 202, 203, 204]]).astype(
                       np.int32)]
        shared, unshared = _engines(params)
        mono = Engine(TINY, mesh11(),
                      ServeConfig(slots=2, max_len=64, prompt_pad=16,
                                  max_new_tokens=5, decode_chunk=4,
                                  eos_token=-1), params)
        outs = {name: eng.generate(prompts)
                for name, eng in [("shared", shared),
                                  ("unshared", unshared), ("mono", mono)]}
        assert outs["shared"] == outs["unshared"] == outs["mono"]
        s = shared.stats()
        assert s.prefix_hits >= 1 and s.shared_pages >= 1
        assert s.peak_pages < unshared.stats().peak_pages

    def test_cow_on_divergence_mid_page(self, params):
        """Prompts diverging inside a page: the partial block is
        copy-on-write'd and every output still matches its oracle."""
        head = np.arange(1, 13, dtype=np.int32)     # rows 8..12 shared
        prompts = [np.concatenate([head, [101, 102, 103, 104]]).astype(
                       np.int32),
                   np.concatenate([head, [201, 202, 203, 204]]).astype(
                       np.int32)]
        eng = Engine(TINY, mesh11(), scfg_shared(), params)
        outs = eng.generate(prompts)
        assert eng.stats().cow_copies >= 1
        for p, out in zip(prompts, outs):
            assert out == reference_decode(params, TINY, p, 5, -1, 16, 64)

    def test_identical_prompts_full_match_truncates(self, params):
        """A byte-identical resident prompt full-matches; the tail page
        is COW'd so at least one suffix row still computes the first
        token's logits — outputs stay identical."""
        p = np.arange(1, 17, dtype=np.int32)        # fills the pad
        eng = Engine(TINY, mesh11(), scfg_shared(), params)
        a, b = eng.generate([p, p])
        assert a == b == reference_decode(params, TINY, p, 5, -1, 16, 64)
        s = eng.stats()
        assert s.prefix_hits >= 1 and s.cow_copies >= 1


class TestPageLifecycle:
    def test_cancel_midflight_decrefs_without_freeing_shared(self, params):
        """cancel() on one of two slots sharing head pages must drop only
        its refcounts — the surviving slot keeps decoding on the still-
        resident pages and the pool accounting closes at drain."""
        head = np.arange(1, 13, dtype=np.int32)
        pa = np.concatenate([head, [101, 102, 103, 104]]).astype(np.int32)
        pb = np.concatenate([head, [201, 202, 203, 204]]).astype(np.int32)
        eng = Engine(TINY, mesh11(), scfg_shared(max_new_tokens=12), params)
        ha = eng.submit(pa)
        hb = eng.submit(pb)
        eng.step()                          # both admitted, first chunk
        b = eng._backend
        survivor_nodes = list(b.slot_shared[0])
        assert survivor_nodes, "slot 0 shares no pages — bad setup"
        hb.cancel()
        eng.step()                          # retire the cancelled slot
        assert not ha.done                  # survivor still mid-flight
        for nd in survivor_nodes:
            assert nd.refs >= 1             # survivor's pins intact
            assert nd.page not in b.free_pages
        assert ha.result() == reference_decode(params, TINY, pa, 12, -1,
                                               16, 64)
        eng.run()
        idx = b.index
        assert (len(b.free_pages) + idx.total_pages
                == eng.scfg.pool_pages)
        assert b.reserved == 0

    def test_pages_return_to_pool_only_at_refcount_zero(self, params):
        """While any slot still maps a shared page it must stay out of
        the free list; after the last unmap it is retained (warm) and
        only eviction hands it back."""
        head = np.arange(1, 13, dtype=np.int32)
        pa = np.concatenate([head, [101, 102, 103, 104]]).astype(np.int32)
        pb = np.concatenate([head, [201, 202, 203, 204]]).astype(np.int32)
        eng = Engine(TINY, mesh11(), scfg_shared(), params)
        ha = eng.submit(pa, max_new=2)      # finishes a chunk early
        hb = eng.submit(pb, max_new=12)
        eng.step()
        b = eng._backend
        shared_nodes = list(b.slot_shared[0]) or list(b.slot_shared[1])
        while not ha.done:
            eng.step()
        eng.step()                          # slot 0 retired, slot 1 live
        assert not hb.done
        for nd in shared_nodes:
            if nd.refs:                     # still mapped by slot 1
                assert nd.page not in b.free_pages
        eng.run()
        idx = b.index
        # refs all zero now: pages retained, not free — but accounted
        assert idx.live_pages == 0
        assert all(nd.refs == 0 for nd in shared_nodes)
        assert (len(b.free_pages) + idx.total_pages
                == eng.scfg.pool_pages)

    def test_pool_pressure_evicts_retained_pages(self, params):
        """A pool with zero slack: serving works only if the retained
        pages of a released pin are reclaimed by the allocator."""
        scfg = scfg_shared(slots=1, num_pages=0)
        need = scfg.request_pages(16, 5)
        scfg = scfg_shared(slots=1, num_pages=need)
        eng = Engine(TINY, mesh11(), scfg, params)
        h = eng.register_prefix(np.arange(50, 58, dtype=np.int32))
        assert eng._backend.index.live_pages == 1
        h.release()                         # retained, still holds a page
        assert eng._backend.index.retained_pages == 1
        p = np.arange(1, 17, dtype=np.int32)    # needs the whole pool
        out = eng.generate([p])[0]
        assert out == reference_decode(params, TINY, p, 5, -1, 16, 64)
        assert eng._backend.index.total_pages < need  # pin was reclaimed


class TestRegisterPrefix:
    def test_roundtrip_hit_and_parity(self, params):
        """register_prefix + submit(prefix=) must hit the pinned pages
        and produce exactly the tokens of the unshared concatenation."""
        scfg = scfg_shared(prompt_pad=24, max_len=64)
        head = np.arange(1, 17, dtype=np.int32)     # 2 pinned pages
        tails = [np.asarray([101, 102, 103, 104, 105, 106, 107, 108],
                            np.int32),
                 np.asarray([201, 202, 203, 204, 205, 206, 207, 208],
                            np.int32)]
        eng = Engine(TINY, mesh11(), scfg, params)
        h = eng.register_prefix(head)
        assert h.n_pages == 2 and not h.released
        handles = [eng.submit(t, prefix=h) for t in tails]
        eng.run()
        ref = Engine(TINY, mesh11(),
                     scfg_shared(prompt_pad=24, max_len=64,
                                 prefix_cache=False), params)
        expect = ref.generate([np.concatenate([head, t]) for t in tails])
        assert [r.tokens for r in handles] == expect
        assert eng.stats().prefix_hits == 2
        h.release()
        assert h.released
        h.release()                         # idempotent
        with pytest.raises(ValueError):
            eng.submit(tails[0], prefix=h)  # released handle refused

    def test_validation(self, params):
        eng = Engine(TINY, mesh11(), scfg_shared(), params)
        with pytest.raises(ValueError):     # not a whole page count
            eng.register_prefix(np.arange(1, 6, dtype=np.int32))
        off = Engine(TINY, mesh11(), scfg_shared(prefix_cache=False),
                     params)
        with pytest.raises(ValueError):     # feature not enabled
            off.register_prefix(np.arange(1, 9, dtype=np.int32))

    def test_prefix_cache_requires_paged(self):
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=4, page_size=0, prefix_cache=True)
        with pytest.raises(ValueError, match="paged"):
            scfg.validate()                 # what Engine() runs at boot


class TestContracts:
    def test_one_sync_per_chunk_under_sharing(self, params, monkeypatch):
        """Prefix sharing must not add device→host transfers: still
        exactly ceil(tokens/decode_chunk) fetches, counted at the
        engine's single fetch point."""
        import repro.serving.engine as engine
        calls = []
        orig = engine._device_fetch
        monkeypatch.setattr(engine, "_device_fetch",
                            lambda tree: calls.append(1) or orig(tree))
        eng = Engine(TINY, mesh11(), scfg_shared(max_new_tokens=8),
                     params)
        p = np.arange(1, 17, dtype=np.int32)
        eng.submit(p)
        eng.submit(p)                       # full-match + COW path
        done = eng.run()
        assert all(len(r.out) == 8 for r in done)
        assert len(calls) == 2              # 8 tokens / 4 per chunk
        assert eng.sync_count == 2
        assert eng.stats().prefix_hits >= 1

    def test_stats_typed_and_dict_access_deprecated(self, params):
        eng = Engine(TINY, mesh11(), scfg_shared(), params)
        eng.generate([np.arange(1, 17, dtype=np.int32)])
        s = eng.stats()
        assert isinstance(s, EngineStats)
        assert s.prefills >= 1
        assert s.prefix_hits == s.shared_pages == 0     # nothing resident
        with pytest.warns(DeprecationWarning):
            legacy = eng.stats["prefills"]
        assert legacy == s.prefills

    def test_prefix_cache_off_is_legacy_exact(self, params):
        """prefix_cache=False keeps the PR 3 allocator behavior bit-for-
        bit: same outputs, same free-list length after drain."""
        eng = Engine(TINY, mesh11(), scfg_shared(prefix_cache=False),
                     params)
        p = np.arange(1, 17, dtype=np.int32)
        out = eng.generate([p])[0]
        assert out == reference_decode(params, TINY, p, 5, -1, 16, 64)
        assert eng._backend.index is None
        assert len(eng._backend.free_pages) == eng.scfg.pool_pages

    def test_mixed_hit_and_miss_slots(self, params):
        """A sharing slot and a non-sharing slot decode side by side —
        both must match their oracles."""
        share_a = np.concatenate([np.arange(1, 13), [101, 102, 103, 104]]
                                 ).astype(np.int32)
        share_b = np.concatenate([np.arange(1, 13), [201, 202, 203, 204]]
                                 ).astype(np.int32)
        lone = np.asarray([90, 91, 92], np.int32)
        eng = Engine(TINY, mesh11(), scfg_shared(slots=3), params)
        outs = eng.generate([share_a, share_b, lone])
        for p, out in zip([share_a, share_b, lone], outs):
            assert out == reference_decode(params, TINY, p, 5, -1, 16, 64)
        assert eng.stats().prefix_hits >= 1


class TestWarnings:
    def test_v1_shim_import_warned_once(self):
        """The serving.engine shim's DeprecationWarning fires at module
        import (once per process), not per Server construction."""
        import repro.serving.engine as engine
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # a second warn would raise
            srv = engine.Server.__new__(engine.Server)
            assert srv is not None
