"""Serving engine: determinism, batching, cache consistency, the chunked
decode loop's one-sync-per-chunk contract and per-phase dispatch plans."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference_decode
from repro import models as MZ
from repro.kernels import dispatch
from repro.core.sparse_linear import SparsityConfig, pack_params
from repro.models.config import ModelConfig
from repro.serving import ServeConfig, Server, sample_token
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.0, 3.0, 1.0]])
        assert int(sample_token(logits, jax.random.key(0), 0.0)[0]) == 1

    def test_temperature_varies(self):
        logits = jnp.zeros((64, 16))
        t1 = sample_token(logits, jax.random.key(1), 1.0)
        t2 = sample_token(logits, jax.random.key(2), 1.0)
        assert not np.array_equal(np.asarray(t1), np.asarray(t2))


class TestServer:
    def test_greedy_matches_manual_decode(self, params):
        """The server's output must equal a hand-rolled prefill+decode."""
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=6, eos_token=-1)
        mesh = mesh11()
        server = Server(TINY, mesh, scfg, params)
        prompt = np.arange(1, 9, dtype=np.int32)
        server.submit(prompt)
        out = server.run()[0].out

        # manual: prefill the same left-padded prompt, greedy decode
        cache = MZ.init_cache(TINY, 1, 64)
        logits, cache = MZ.prefill(params, TINY,
                                   {"tokens": jnp.asarray(prompt[None])},
                                   cache)
        manual = []
        tok = jnp.argmax(logits[:, :TINY.vocab_size], -1).astype(jnp.int32)
        manual.append(int(tok[0]))
        pos = 8
        for _ in range(5):
            logits, cache = MZ.decode_step(params, TINY, tok, cache,
                                           jnp.asarray(pos))
            tok = jnp.argmax(logits[:, :TINY.vocab_size], -1).astype(
                jnp.int32)
            manual.append(int(tok[0]))
            pos += 1
        assert out == manual

    def test_multiple_requests_batched(self, params):
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=4, eos_token=-1)
        server = Server(TINY, mesh11(), scfg, params)
        uids = [server.submit(np.arange(1, 6, dtype=np.int32))
                for _ in range(5)]          # 5 requests, 2 slots → 3 waves
        done = server.run()
        assert sorted(r.uid for r in done) == sorted(uids)
        assert all(len(r.out) == 4 for r in done)

    def test_identical_prompts_identical_outputs(self, params):
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=4, eos_token=-1)
        server = Server(TINY, mesh11(), scfg, params)
        p = np.asarray([5, 6, 7], np.int32)
        server.submit(p)
        server.submit(p)
        a, b = server.run()
        assert a.out == b.out   # slots don't leak into each other

    def test_eos_stops_early(self, params):
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=16, eos_token=0)
        server = Server(TINY, mesh11(), scfg, params)
        server.submit(np.asarray([1, 2, 3], np.int32))
        r = server.run()[0]
        if 0 in r.out:
            assert r.out.index(0) == len(r.out) - 1


class TestChunkedLoop:
    """The on-device chunked decode loop against 1-token-at-a-time
    oracles: refill, heterogeneous budgets, EOS mid-chunk, sync count."""

    def test_heterogeneous_max_new_and_refill(self, params):
        """3 requests on 2 slots with different budgets: slot A finishes
        mid-stream and is refilled (per-slot prefill) while slot B keeps
        decoding — every output must equal its independent oracle."""
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=16, decode_chunk=4, eos_token=-1)
        server = Server(TINY, mesh11(), scfg, params)
        prompts = [np.arange(1, 6, dtype=np.int32),
                   np.arange(3, 11, dtype=np.int32),
                   np.asarray([7, 9, 11], np.int32)]
        budgets = [5, 9, 3]
        uids = [server.submit(p, max_new=n)
                for p, n in zip(prompts, budgets)]
        done = {r.uid: r for r in server.run()}
        assert sorted(done) == sorted(uids)
        for uid, p, n in zip(uids, prompts, budgets):
            ref = reference_decode(params, TINY, p, n, -1, 8, 64)
            assert done[uid].out == ref, f"request {uid}"

    def test_eos_mid_chunk(self, params):
        """Re-serve with eos set to a token the model actually emits in
        the middle of a chunk: the output must truncate exactly there."""
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=12, decode_chunk=8, eos_token=-1)
        prompt = np.arange(1, 9, dtype=np.int32)
        free = reference_decode(params, TINY, prompt, 12, -1, 8, 64)
        eos = free[2]                 # third emitted token, mid-chunk
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=12, decode_chunk=8, eos_token=eos)
        server = Server(TINY, mesh11(), scfg, params)
        server.submit(prompt)
        out = server.run()[0].out
        cut = free.index(eos)
        assert out == free[:cut + 1]
        assert out[-1] == eos

    def test_one_sync_per_chunk(self, params, monkeypatch):
        """The decode hot loop performs exactly ceil(tokens/decode_chunk)
        device→host transfers — counted by intercepting the engine's
        single fetch point, not self-reported."""
        import repro.serving.engine as engine
        calls = []
        orig = engine._device_fetch
        monkeypatch.setattr(engine, "_device_fetch",
                            lambda tree: calls.append(1) or orig(tree))
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=8, decode_chunk=4, eos_token=-1)
        server = Server(TINY, mesh11(), scfg, params)
        for _ in range(2):
            server.submit(np.arange(1, 6, dtype=np.int32))
        done = server.run()
        assert all(len(r.out) == 8 for r in done)
        # 8 tokens per slot / 4 per chunk = 2 chunks; prefill syncs: none
        assert len(calls) == 2
        assert server.sync_count == 2

    def test_temperature_chunked_runs(self, params):
        """Sampling path through the on-device loop: deterministic per
        seed, right token count, in-vocab tokens."""
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=6, decode_chunk=4,
                           temperature=0.7, eos_token=-1, seed=3)
        outs = []
        for _ in range(2):
            server = Server(TINY, mesh11(), scfg, params)
            server.submit(np.arange(1, 6, dtype=np.int32))
            outs.append(server.run()[0].out)
        assert outs[0] == outs[1]
        assert len(outs[0]) == 6
        assert all(0 <= t < TINY.vocab_size for t in outs[0])


NM_TINY = ModelConfig(name="tiny-nm", n_layers=2, d_model=128,
                      vocab_size=512, n_heads=4, n_kv_heads=2, d_ff=256,
                      remat=False,
                      mlp_sparsity=SparsityConfig(format="nm", n=2, m=4,
                                                  block_n=64))


class TestPhasePlans:
    """dispatch.plan_params re-invoked at decode geometry (M = slots)."""

    @pytest.fixture(scope="class")
    def sparse_server(self):
        params = pack_params(MZ.init_model(jax.random.key(0), NM_TINY),
                             NM_TINY)
        scfg = ServeConfig(slots=8, max_len=256, prompt_pad=128,
                           max_new_tokens=4, decode_chunk=4, eos_token=-1)
        return Server(NM_TINY, mesh11(), scfg, params), params

    def test_plans_recorded_per_phase(self, sparse_server):
        server, _ = sparse_server
        assert server.prefill_plan and server.decode_plan
        # prefill covers both geometries: wave (slots*pad) + slot refill
        assert {p["M"] for p in server.prefill_plan} == {8 * 128, 128}
        assert all(p["M"] == 8 for p in server.decode_plan)
        assert all(p["kernel"] == "nm_spmm" for p in server.decode_plan)
        assert server.dispatch_plan == server.prefill_plan   # back-compat

    def test_decode_plan_differs_when_m_changes_selection(self,
                                                          sparse_server):
        """At kernel-impl resolution the decode geometry (M = slots)
        picks different block sizes than prefill M — the grids now carry
        decode-shaped rows."""
        _, params = sparse_server
        prefill = dispatch.plan_params(params, M=128, impl="kernel")
        decode = dispatch.plan_params(params, M=8, impl="kernel")
        assert [p["blocks"] for p in prefill] != \
            [p["blocks"] for p in decode]
        assert all(p["blocks"]["bm"] == 128 for p in prefill)
        assert all(p["blocks"]["bm"] <= 8 for p in decode)

    def test_serves_through_sparse_kernels(self, sparse_server):
        server, params = sparse_server
        prompt = np.arange(1, 9, dtype=np.int32)
        server.submit(prompt)
        out = server.run()[0].out
        ref = reference_decode(params, NM_TINY, prompt, 4, -1, 128, 256)
        assert out == ref
