"""Serving API v2: Engine/step()/streaming semantics — greedy bit-parity
with the v1 Server across mono/paged/spec, mid-run admission into freed
slots, cancel() retiring slots and freeing pages, in-order stream
iterators, per-request temperature, submit() input validation, TTFT
stamping, the sync-count contract through step(), and the serving-module
size gate."""

import os

import jax
import numpy as np
import pytest

from conftest import reference_decode
from repro import models as MZ
from repro.models.config import LayerKind, ModelConfig
from repro.serving import (Engine, RequestStatus, ServeConfig, Server)
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)

HYBRID = ModelConfig(
    name="hy", n_layers=3, d_model=64, vocab_size=256, n_heads=4,
    n_kv_heads=2, d_ff=128, remat=False,
    layer_kinds=(int(LayerKind.MAMBA), int(LayerKind.SHARED_ATTN),
                 int(LayerKind.MAMBA)))

BASE = dict(slots=2, max_len=64, prompt_pad=8, max_new_tokens=16,
            decode_chunk=4, eos_token=-1)

PROMPTS = [np.arange(1, 6, dtype=np.int32),
           np.arange(3, 11, dtype=np.int32),
           np.asarray([7, 9, 11], np.int32)]
BUDGETS = [5, 9, 3]


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


@pytest.fixture(scope="module")
def hybrid_params():
    return MZ.init_model(jax.random.key(0), HYBRID)


class TestModuleSize:
    def test_serving_modules_under_700_lines(self):
        """The split stays honest: no serving module regrows past 700
        lines (CI enforces the same bound in the lint job)."""
        import repro.serving
        pkg = os.path.dirname(repro.serving.__file__)
        for name in sorted(os.listdir(pkg)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(pkg, name)) as f:
                n = sum(1 for _ in f)
            assert n <= 700, f"serving/{name} has {n} lines (> 700)"


class TestEngineParity:
    """Engine greedy output must be bit-identical to the v1 Server (and
    the 1-token oracle) for mono, paged and spec configs."""

    @pytest.mark.parametrize("extra", [
        {}, {"page_size": 8}, {"spec_k": 4},
        {"spec_k": 4, "page_size": 8},
    ], ids=["mono", "paged", "spec", "spec-paged"])
    def test_tiny(self, params, extra):
        scfg = ServeConfig(**BASE, **extra)
        eng = Engine(TINY, mesh11(), scfg, params)
        handles = [eng.submit(p, max_new=n)
                   for p, n in zip(PROMPTS, BUDGETS)]
        eng.run()
        srv = Server(TINY, mesh11(), scfg, params)
        uids = [srv.submit(p, max_new=n) for p, n in zip(PROMPTS, BUDGETS)]
        done = {r.uid: r.out for r in srv.run()}
        for h, uid, p, n in zip(handles, uids, PROMPTS, BUDGETS):
            ref = reference_decode(params, TINY, p, n, -1, 8, 64)
            assert h.tokens == ref
            assert done[uid] == ref
            assert h.status is RequestStatus.DONE

    @pytest.mark.parametrize("extra", [
        {}, {"page_size": 8}, {"spec_k": 3},
    ], ids=["mono", "paged", "spec"])
    def test_hybrid(self, hybrid_params, extra):
        scfg = ServeConfig(**BASE, **extra)
        eng = Engine(HYBRID, mesh11(), scfg, hybrid_params)
        handles = [eng.submit(p, max_new=n)
                   for p, n in zip(PROMPTS[:2], BUDGETS[:2])]
        eng.run()
        for h, p, n in zip(handles, PROMPTS[:2], BUDGETS[:2]):
            ref = reference_decode(hybrid_params, HYBRID, p, n, -1, 8, 64)
            assert h.tokens == ref

    def test_generate_wrapper(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**BASE), params)
        outs = eng.generate(PROMPTS, max_new=4)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_decode(params, TINY, p, 4, -1, 8, 64)


class TestSubmitValidation:
    @pytest.fixture(scope="class")
    def eng(self, params):
        return Engine(TINY, mesh11(), ServeConfig(**BASE), params)

    def test_accepts_lists_and_any_int_dtype(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**BASE), params)
        prompt = [1, 2, 3, 4, 5]
        hs = [eng.submit(prompt),
              eng.submit(np.asarray(prompt, np.int64)),
              eng.submit(np.asarray(prompt, np.int16)),
              eng.submit(np.asarray(prompt, np.uint8))]
        eng.run()
        ref = reference_decode(params, TINY, np.asarray(prompt, np.int32),
                               16, -1, 8, 64)
        for h in hs:
            assert h.tokens == ref

    def test_empty_prompt_rejected(self, eng):
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros((0,), np.int32))

    def test_non_integer_prompt_rejected(self, eng):
        with pytest.raises(ValueError, match="integer"):
            eng.submit(np.asarray([1.5, 2.0]))

    def test_non_1d_prompt_rejected(self, eng):
        with pytest.raises(ValueError, match="1-D"):
            eng.submit(np.ones((2, 3), np.int32))

    def test_overlong_prompt_rejected(self, eng):
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.arange(64, dtype=np.int32))    # max_len is 64
        eng.submit(np.arange(63, dtype=np.int32))        # 63 fits

    def test_nonpositive_max_new_rejected(self, eng):
        with pytest.raises(ValueError, match="max_new"):
            eng.submit([1, 2], max_new=0)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit([1, 2], max_new=-3)

    def test_spec_rejects_divergent_temperature(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**BASE, spec_k=2), params)
        with pytest.raises(ValueError, match="temperature"):
            eng.submit([1, 2, 3], temperature=0.7)
        eng.submit([1, 2, 3], temperature=0.0)           # matching is fine


class TestScheduler:
    def test_midrun_admission_lands_in_freed_slot(self, params):
        """A request submitted while the engine is mid-stream is
        admitted into the slot its predecessor freed — and still decodes
        exactly its oracle stream."""
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=16, decode_chunk=4, eos_token=-1)
        eng = Engine(TINY, mesh11(), scfg, params)
        h1 = eng.submit(PROMPTS[0], max_new=4)       # 4 tokens = 1 chunk
        eng.step()
        assert h1.done and h1.slot == 0
        h2 = eng.submit(PROMPTS[1], max_new=4)       # mid-run admission
        assert h2.status is RequestStatus.QUEUED
        eng.step()
        assert h2.status in (RequestStatus.RUNNING, RequestStatus.DONE)
        assert h2.slot == 0                          # the freed slot
        eng.run()
        assert h2.tokens == reference_decode(params, TINY, PROMPTS[1], 4,
                                             -1, 8, 64)

    def test_step_events_in_emission_order(self, params):
        scfg = ServeConfig(**BASE)
        eng = Engine(TINY, mesh11(), scfg, params)
        handles = [eng.submit(p, max_new=n)
                   for p, n in zip(PROMPTS[:2], BUDGETS[:2])]
        per_uid = {h.uid: [] for h in handles}
        while not all(h.done for h in handles):
            events = eng.step()
            assert events, "live engine tick must emit"
            for ev in events:
                per_uid[ev.uid].append(ev.token)
                assert ev.index == len(per_uid[ev.uid]) - 1
        for h in handles:
            assert per_uid[h.uid] == h.tokens
        finals = [ev for evs in [eng.step()] for ev in evs]
        assert finals == []                          # drained engine idles

    def test_stream_iterator_yields_in_order(self, params):
        scfg = ServeConfig(**BASE)
        eng = Engine(TINY, mesh11(), scfg, params)
        h1 = eng.submit(PROMPTS[0], max_new=6, stream=True)
        h2 = eng.submit(PROMPTS[1], max_new=6, stream=True)
        streamed = list(h1)                          # drives step()
        assert streamed == h1.tokens
        assert streamed == reference_decode(params, TINY, PROMPTS[0], 6,
                                            -1, 8, 64)
        # h2 decoded alongside; its iterator replays without stepping
        syncs = eng.sync_count
        assert list(h2) == h2.tokens
        assert eng.sync_count == syncs

    def test_result_drives_to_completion(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**BASE), params)
        h = eng.submit(PROMPTS[0], max_new=5)
        assert h.result() == reference_decode(params, TINY, PROMPTS[0], 5,
                                              -1, 8, 64)

    def test_ttft_recorded(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**BASE), params)
        h = eng.submit(PROMPTS[0], max_new=4)
        assert h.ttft_s is None
        eng.run()
        assert h.ttft_s is not None and h.ttft_s > 0
        assert eng.ttfts_s() == [h.ttft_s]

    def test_per_request_temperature_mixed_batch(self, params):
        """A greedy request batched beside a sampled one still matches
        its oracle exactly; the sampled one is deterministic per seed."""
        scfg = ServeConfig(**BASE, temperature=0.9, seed=7)
        outs = []
        for _ in range(2):
            eng = Engine(TINY, mesh11(), scfg, params)
            hg = eng.submit(PROMPTS[0], max_new=6, temperature=0.0)
            hs = eng.submit(PROMPTS[1], max_new=6)   # scfg default 0.9
            eng.run()
            assert hg.tokens == reference_decode(params, TINY, PROMPTS[0],
                                                 6, -1, 8, 64)
            assert len(hs.tokens) == 6
            assert all(0 <= t < TINY.vocab_size for t in hs.tokens)
            outs.append(hs.tokens)
        assert outs[0] == outs[1]


class TestCancel:
    def test_cancel_running_frees_pages_and_stops_tokens(self, params):
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=32, decode_chunk=4, eos_token=-1,
                           page_size=8)
        eng = Engine(TINY, mesh11(), scfg, params)
        h1 = eng.submit(PROMPTS[0])
        h2 = eng.submit(PROMPTS[1])
        eng.step()
        assert not h1.done and len(h1.tokens) == 4
        h1.cancel()
        n_at_cancel = len(h1.tokens)
        eng.run()
        assert h1.status is RequestStatus.CANCELLED
        assert len(h1.tokens) == n_at_cancel         # no further tokens
        assert h2.status is RequestStatus.DONE
        assert len(h2.tokens) == 32                  # unperturbed
        # every page came back (both slots retired)
        assert len(eng._backend.free_pages) == scfg.pool_pages
        assert (eng._backend.ptab == 0).all()

    def test_cancelled_slot_is_refilled(self, params):
        """The slot a cancel frees admits the next queued request."""
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=32, decode_chunk=4, eos_token=-1)
        eng = Engine(TINY, mesh11(), scfg, params)
        h1 = eng.submit(PROMPTS[0])
        eng.step()
        h1.cancel()
        h2 = eng.submit(PROMPTS[1], max_new=4)
        eng.run()
        assert h1.status is RequestStatus.CANCELLED
        assert h2.slot == 0
        assert h2.tokens == reference_decode(params, TINY, PROMPTS[1], 4,
                                             -1, 8, 64)

    def test_cancel_queued_never_runs(self, params):
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=8, decode_chunk=4, eos_token=-1)
        eng = Engine(TINY, mesh11(), scfg, params)
        h1 = eng.submit(PROMPTS[0])
        h2 = eng.submit(PROMPTS[1])                  # waits for the slot
        h2.cancel()
        eng.run()
        assert h2.status is RequestStatus.CANCELLED
        assert h2.tokens == []
        assert eng.stats["prefills"] == 1

    def test_cancel_done_is_noop(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**BASE), params)
        h = eng.submit(PROMPTS[0], max_new=4)
        eng.run()
        h.cancel()
        assert h.status is RequestStatus.DONE

    def test_double_cancel_is_idempotent(self, params):
        """Cancelling a terminal handle is a no-op — in particular the
        second cancel can never re-arm the flag and double-release the
        slot's pages on a later tick (regression for the paged pool)."""
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=32, decode_chunk=4, eos_token=-1,
                           page_size=8)
        eng = Engine(TINY, mesh11(), scfg, params)
        h1 = eng.submit(PROMPTS[0])
        h2 = eng.submit(PROMPTS[1])
        eng.step()
        h1.cancel()
        eng.step()                      # cancel takes effect, slot retires
        assert h1.status is RequestStatus.CANCELLED
        assert not h1._req.cancel_requested or h1.done
        h1.cancel()                     # terminal: must not re-arm
        h1.cancel()
        assert not h1._req.cancel_requested
        eng.run()
        assert h2.status is RequestStatus.DONE
        # pool conserved: every page owned exactly once
        assert sorted(eng._backend.free_pages) == \
            list(range(1, scfg.pool_pages + 1))
        eng.audit()

    def test_cancel_after_finish_keeps_status_and_pages(self, params):
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=4, decode_chunk=4, eos_token=-1,
                           page_size=8)
        eng = Engine(TINY, mesh11(), scfg, params)
        h = eng.submit(PROMPTS[0])
        eng.run()
        assert h.status is RequestStatus.DONE
        free_before = sorted(eng._backend.free_pages)
        for _ in range(3):
            h.cancel()
            eng.step()
        assert h.status is RequestStatus.DONE       # not CANCELLED
        assert sorted(eng._backend.free_pages) == free_before
        eng.audit()


class TestSyncContract:
    def test_one_fetch_per_step(self, params, monkeypatch):
        """Each step() with live work performs exactly ONE device→host
        transfer; admission/prefill/cancel perform none."""
        import repro.serving.engine as engine
        calls = []
        orig = engine._device_fetch
        monkeypatch.setattr(engine, "_device_fetch",
                            lambda tree: calls.append(1) or orig(tree))
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=8, decode_chunk=4, eos_token=-1,
                           page_size=8)
        eng = Engine(TINY, mesh11(), scfg, params)
        for _ in range(2):
            eng.submit(PROMPTS[0])
        n = 0
        while eng.num_live or eng.num_queued:
            before = len(calls)
            eng.step()
            assert len(calls) - before == 1
            n += 1
        assert n == 2                   # 8 tokens / 4 per chunk
        assert eng.sync_count == 2
        assert eng.step() == []         # idle tick
        assert len(calls) == 2          # …and fetches nothing
