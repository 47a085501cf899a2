"""Speculative decoding: greedy bit-parity with the plain loops per
family and per pack format (for ANY draft — the defining property),
EOS inside a drafted block, paged-pool rollback consistency, the
one-sync-per-chunk contract, PRNG fold_in determinism, and the
verify/draft dispatch-plan geometries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models as MZ
from repro.core.sparse_linear import (SparsityConfig, make_draft_params,
                                      pack_params)
from repro.models.config import LayerKind, ModelConfig
from repro.serving import ServeConfig, Server, build_spec_decode_loop
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)

PROMPTS = [np.arange(1, 6, dtype=np.int32),
           np.arange(3, 11, dtype=np.int32),
           np.asarray([7, 9, 11], np.int32)]
BUDGETS = [5, 9, 3]

BASE = dict(slots=2, max_len=64, prompt_pad=8, max_new_tokens=16,
            decode_chunk=4, eos_token=-1)


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


def serve(cfg, params, scfg, prompts=PROMPTS, budgets=BUDGETS, draft=None):
    server = Server(cfg, mesh11(), scfg, params, draft_params=draft)
    uids = [server.submit(p, max_new=n) for p, n in zip(prompts, budgets)]
    done = {r.uid: r.out for r in server.run()}
    assert sorted(done) == sorted(uids)
    return [done[u] for u in uids], server


class TestGreedyParity:
    """Greedy speculative output must be bit-identical to the plain
    chunked loop: accepted drafts equal the verify argmax and the
    correction token IS the verify argmax, so the committed stream is
    the dense model's greedy stream for any draft."""

    def test_mono_self_draft(self, params):
        plain, _ = serve(TINY, params, ServeConfig(**BASE))
        spec, s = serve(TINY, params, ServeConfig(**BASE, spec_k=3))
        assert plain == spec
        assert s.acceptance_rate() > 0.9        # self-draft ≈ always

    def test_paged_self_draft(self, params):
        plain, _ = serve(TINY, params, ServeConfig(**BASE, page_size=8))
        spec, s = serve(TINY, params,
                        ServeConfig(**BASE, spec_k=3, page_size=8))
        assert plain == spec
        assert s.stats["drafted"] > 0

    def test_paged_view_bucketed(self, params):
        plain, _ = serve(TINY, params, ServeConfig(**BASE))
        spec, _ = serve(TINY, params, ServeConfig(
            **BASE, spec_k=3, page_size=8, page_view_chunk=1))
        assert plain == spec

    @pytest.mark.parametrize("fmt", ["nm", "combined"])
    def test_sparse_pack_draft(self, fmt):
        """The sparse-draft/dense-verify split: verify params stay
        dense, the draft is the pack — outputs must still equal the
        dense greedy stream, acceptance is whatever the pack earns."""
        scfg_pack = {
            "nm": SparsityConfig(format="nm", n=2, m=4, block_n=64),
            "combined": SparsityConfig(format="combined", sparsity=0.5,
                                       n=2, m=4, block_k=64, block_n=64),
        }[fmt]
        cfg = ModelConfig(name=f"tiny-{fmt}", n_layers=2, d_model=128,
                          vocab_size=256, n_heads=4, n_kv_heads=2,
                          d_ff=256, remat=False, mlp_sparsity=scfg_pack)
        p = MZ.init_model(jax.random.key(0), cfg)
        plain, _ = serve(cfg, p, ServeConfig(**BASE),
                         prompts=PROMPTS[:2], budgets=BUDGETS[:2])
        spec, s = serve(cfg, p,
                        ServeConfig(**BASE, spec_k=4, spec_draft="pack",
                                    page_size=8),
                        prompts=PROMPTS[:2], budgets=BUDGETS[:2])
        assert plain == spec
        # the draft really is packed (plan shows the sparse kernel) …
        kernels = {r["kernel"] for r in s.draft_plan}
        assert {"nm": "nm_spmm", "combined": "csa_matmul"}[fmt] in kernels
        # … and really disagrees with the dense verifier sometimes
        assert 0.0 <= s.acceptance_rate() < 1.0

    def test_packed_model_self_draft(self):
        """Speculation over a fully packed server (both draft and
        verify run the sparse kernels)."""
        cfg = ModelConfig(name="tiny-nm2", n_layers=2, d_model=128,
                          vocab_size=256, n_heads=4, n_kv_heads=2,
                          d_ff=256, remat=False,
                          mlp_sparsity=SparsityConfig(format="nm", n=2,
                                                      m=4, block_n=64))
        p = pack_params(MZ.init_model(jax.random.key(0), cfg), cfg)
        plain, _ = serve(cfg, p, ServeConfig(**BASE, page_size=8),
                         prompts=PROMPTS[:2], budgets=BUDGETS[:2])
        spec, _ = serve(cfg, p,
                        ServeConfig(**BASE, spec_k=3, page_size=8),
                        prompts=PROMPTS[:2], budgets=BUDGETS[:2])
        assert plain == spec

    def test_hybrid_partial_acceptance(self):
        """Hybrid family with a garbage draft: acceptance ~0 forces the
        recurrent-state rollback every step — outputs must still equal
        the dense greedy stream (the SSM snapshots are exact)."""
        cfg = ModelConfig(
            name="hy", n_layers=3, d_model=64, vocab_size=256, n_heads=4,
            n_kv_heads=2, d_ff=128, remat=False,
            layer_kinds=(int(LayerKind.MAMBA), int(LayerKind.SHARED_ATTN),
                         int(LayerKind.MAMBA)))
        p = MZ.init_model(jax.random.key(0), cfg)
        garbage = MZ.init_model(jax.random.key(42), cfg)
        for extra in ({}, {"page_size": 8}):
            plain, _ = serve(cfg, p, ServeConfig(**BASE, **extra),
                             prompts=PROMPTS[:2], budgets=BUDGETS[:2])
            spec, s = serve(cfg, p,
                            ServeConfig(**BASE, spec_k=3, **extra),
                            prompts=PROMPTS[:2], budgets=BUDGETS[:2],
                            draft=garbage)
            assert plain == spec, extra
            assert s.acceptance_rate() < 0.5

    def test_encdec_spec_loop(self):
        """Enc-dec family at the loop level (the Server feeds token
        prompts only): the spec loop over the decoder self/cross cache
        must emit the same greedy tokens as sequential decode steps."""
        cfg = ModelConfig(name="ed", n_layers=2, n_encoder_layers=2,
                          d_model=64, vocab_size=256, n_heads=4,
                          n_kv_heads=2, d_ff=128, remat=False,
                          is_encoder_decoder=True)
        p = MZ.init_model(jax.random.key(0), cfg)
        scfg = ServeConfig(slots=2, max_len=32, prompt_pad=8,
                           max_new_tokens=8, decode_chunk=3, spec_k=2,
                           eos_token=-1)
        mesh = mesh11()
        src = jax.random.normal(jax.random.key(2), (2, 6, 64), jnp.bfloat16)
        toks = jax.random.randint(jax.random.key(1), (2, 8), 1, 250)
        cache = MZ.init_cache(cfg, 2, 32, src_len=6)
        logits, cache = MZ.prefill(p, cfg, {"src": src, "tokens": toks},
                                   cache)
        first = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)

        # sequential greedy oracle
        want = [[int(first[b])] for b in range(2)]
        tok, c, pos = first, cache, jnp.full((2,), 8, jnp.int32)
        for _ in range(scfg.max_new_tokens - 1):
            lg, c = MZ.decode_step(p, cfg, tok, c, pos)
            tok = jnp.argmax(lg[:, :cfg.vocab_size], -1).astype(jnp.int32)
            for b in range(2):
                want[b].append(int(tok[b]))
            pos = pos + 1

        loop = build_spec_decode_loop(
            cfg, mesh, scfg, jax.eval_shape(lambda: p),
            jax.eval_shape(lambda: p), jax.eval_shape(lambda: cache))
        state = {"tok": first, "pos": jnp.full((2,), 8, jnp.int32),
                 "done": jnp.zeros((2,), bool),
                 "left": jnp.full((2,), scfg.max_new_tokens, jnp.int32)}
        got = [[] for _ in range(2)]
        key = jax.random.key(0)
        with jax.set_mesh(mesh):
            while not bool(jnp.all(state["done"])):
                key, sk = jax.random.split(key)
                cache, state, toks_blk, emit, _, _ = loop(
                    p, p, cache, state, sk)
                blk, em = np.asarray(toks_blk), np.asarray(emit)
                for t in range(blk.shape[0]):
                    for b in range(2):
                        if em[t, b]:
                            got[b].append(int(blk[t, b]))
        assert got == want


class TestEosAndRollback:
    def test_eos_mid_drafted_block(self, params):
        """EOS landing inside a drafted block truncates exactly there —
        later accepted drafts of the same block must not leak out."""
        free_cfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                               max_new_tokens=12, decode_chunk=8,
                               eos_token=-1)
        prompt = np.arange(1, 9, dtype=np.int32)
        free, _ = serve(TINY, params, free_cfg, [prompt], [12])
        eos = free[0][2]                  # third token: mid-block for k=4
        for extra in ({}, {"page_size": 8}):
            scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                               max_new_tokens=12, decode_chunk=4,
                               spec_k=4, eos_token=eos, **extra)
            out, server = serve(TINY, params, scfg, [prompt], [12])
            cut = free[0].index(eos)
            assert out[0] == free[0][:cut + 1], extra
            assert out[0][-1] == eos
        # paged: retirement returned every page
        assert len(server._free_pages) == server.scfg.pool_pages
        assert (server._ptab == 0).all()

    def test_rollback_keeps_pool_consistent(self, params):
        """Low-acceptance speculation over a tight pool: pages allocated
        ahead of the commit point come back at every chunk boundary,
        freed pages are reused across refills, and nothing leaks."""
        garbage = MZ.init_model(jax.random.key(7), TINY)
        prompts = [np.arange(1 + i, 7 + i, dtype=np.int32)
                   for i in range(4)]
        base = dict(slots=1, max_len=32, prompt_pad=8, max_new_tokens=4,
                    decode_chunk=2, eos_token=-1, page_size=8, spec_k=3)
        # each request reserves ceil((8 + 4) / 8) = 2 pages
        small, server = serve(TINY, params,
                              ServeConfig(**base, num_pages=2),
                              prompts, [4] * 4, draft=garbage)
        roomy, _ = serve(TINY, params, ServeConfig(**base),
                         prompts, [4] * 4, draft=garbage)
        assert small == roomy
        assert server.stats["peak_pages"] == 2
        assert len(server._free_pages) == 2
        assert (server._ptab == 0).all()
        # and the whole run equals the non-speculative outputs
        plain, _ = serve(TINY, params, ServeConfig(
            slots=1, max_len=32, prompt_pad=8, max_new_tokens=4,
            decode_chunk=2, eos_token=-1, page_size=8), prompts, [4] * 4)
        assert small == plain

    def test_spec_needs_block_headroom(self, params):
        with pytest.raises(ValueError):
            Server(TINY, mesh11(),
                   ServeConfig(slots=1, max_len=16, prompt_pad=12,
                               spec_k=8), params)


class TestSyncContract:
    def test_one_sync_per_chunk(self, params, monkeypatch):
        """Drafting, verifying and the acceptance stats all ride the
        chunk's single device→host transfer."""
        import repro.serving.engine as engine
        calls = []
        orig = engine._device_fetch
        monkeypatch.setattr(engine, "_device_fetch",
                            lambda tree: calls.append(1) or orig(tree))
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=12, decode_chunk=2, spec_k=2,
                           eos_token=-1, page_size=8, page_view_chunk=1)
        server = Server(TINY, mesh11(), scfg, params)
        for _ in range(2):
            server.submit(np.arange(1, 6, dtype=np.int32))
        done = server.run()
        assert all(len(r.out) == 12 for r in done)
        # self-draft accepts everything: 12 tokens / (2 steps × 3) = 2
        assert len(calls) == 2
        assert server.sync_count == 2
        assert server.stats["drafted"] > 0

    def test_chunk_tokens_bound(self, params):
        """A chunk emits at most decode_chunk*(spec_k+1) tokens/slot."""
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=16, decode_chunk=2, spec_k=3,
                           eos_token=-1)
        assert scfg.chunk_tokens == 8
        _, server = serve(TINY, params, scfg, [PROMPTS[0]], [16])
        assert server.sync_count == 2      # 16 tokens / 8 per chunk


class TestDeterminism:
    def test_same_seed_same_tokens_greedy(self, params):
        """Same seed ⇒ same tokens with and without speculation at
        temperature 0 (the fold_in discipline never samples there)."""
        for extra in ({}, {"page_size": 8}):
            a, _ = serve(TINY, params, ServeConfig(**BASE, seed=3, **extra))
            b, _ = serve(TINY, params,
                         ServeConfig(**BASE, seed=3, spec_k=3, **extra))
            assert a == b, extra

    def test_temperature_spec_deterministic(self, params):
        """Temperature sampling through the spec loop: per (step, slot,
        draft-position) fold_in keys ⇒ identical reruns per seed."""
        scfg = ServeConfig(**{**BASE, "max_new_tokens": 8},
                           temperature=0.7, seed=5, spec_k=3)
        outs = []
        for _ in range(2):
            out, s = serve(TINY, params, scfg,
                           prompts=PROMPTS[:2], budgets=[8, 8])
            outs.append(out)
        assert outs[0] == outs[1]
        assert all(len(o) == 8 for o in outs[0])
        assert all(0 <= t < TINY.vocab_size for o in outs[0] for t in o)

    def test_residual_acceptance_self_draft(self, params):
        """At temperature > 0 the residual rule accepts a self-draft
        with probability min(1, p/p) = 1 — speculation then matches the
        non-spec sampling path in distribution and stays deterministic
        per seed."""
        scfg = ServeConfig(**{**BASE, "max_new_tokens": 6},
                           temperature=0.9, seed=11, spec_k=2)
        _, s = serve(TINY, params, scfg, prompts=PROMPTS[:1], budgets=[6])
        assert s.acceptance_rate() == 1.0


class TestPlansAndStats:
    def test_verify_and_draft_plan_geometries(self):
        cfg = ModelConfig(name="tiny-nm3", n_layers=2, d_model=128,
                          vocab_size=256, n_heads=4, n_kv_heads=2,
                          d_ff=256, remat=False,
                          mlp_sparsity=SparsityConfig(format="nm", n=2,
                                                      m=4, block_n=64))
        p = MZ.init_model(jax.random.key(0), cfg)
        scfg = ServeConfig(slots=8, max_len=64, prompt_pad=16,
                           max_new_tokens=4, spec_k=4, spec_draft="pack",
                           page_size=8)
        server = Server(cfg, mesh11(), scfg, p)
        # draft: sparse kernels at decode geometry (M = slots)
        assert server.draft_plan
        assert all(r["M"] == 8 for r in server.draft_plan)
        assert {r["kernel"] for r in server.draft_plan} == {"nm_spmm"}
        # verify: its own M = slots*(k+1) rows (paged-attention included)
        assert any(r["M"] == 40 and r["kernel"] == "paged_attention"
                   for r in server.verify_plan)
        assert all(r["M"] == 40 for r in server.verify_plan)
        # the decode plan carries the verify rows too
        assert any(r["M"] == 40 for r in server.decode_plan)

    def test_reset_stats_clears_acceptance(self, params):
        scfg = ServeConfig(**BASE, spec_k=2)
        _, server = serve(TINY, params, scfg,
                          prompts=PROMPTS[:1], budgets=[4])
        assert server.stats["drafted"] > 0
        assert server.acceptance_rate() > 0
        server.reset_stats()
        assert server.stats["drafted"] == 0
        assert server.acceptance_rate() == 0.0

    def test_make_draft_params_shares_unpacked_leaves(self):
        cfg = ModelConfig(name="tiny-nm4", n_layers=2, d_model=128,
                          vocab_size=256, n_heads=4, n_kv_heads=2,
                          d_ff=256, remat=False,
                          mlp_sparsity=SparsityConfig(format="nm", n=2,
                                                      m=4, block_n=64))
        p = MZ.init_model(jax.random.key(0), cfg)
        d = make_draft_params(p, cfg)
        # packed: the MLP went sparse …
        from repro.core.sparsity import NMPack
        assert isinstance(d["layers"]["mlp"]["w_in"], NMPack)
        # … shared: embeddings (the big table) are the same buffer
        assert d["embed"] is p["embed"]
        # dense config ⇒ draft degenerates to the same tree
        dd = make_draft_params(MZ.init_model(jax.random.key(0), TINY), TINY)
        assert dd["embed"].shape == (512, 64)
