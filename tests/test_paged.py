"""Paged KV cache: model-level parity for every family, paged serving
parity for every pack format, page allocator behavior (refill reuse,
pool-exhaustion admission), the sync-count contract under paging, and
the paged-attention kernel family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference_decode, shaped_ops
from repro import models as MZ
from repro.core.sparse_linear import SparsityConfig, pack_params
from repro.kernels import dispatch, ref
from repro.models.config import LayerKind, ModelConfig
from repro.serving import ServeConfig, Server
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


def identity_table(batch: int, max_pages: int) -> jnp.ndarray:
    """Every slot owns its own contiguous page run (pages 1..B*mp)."""
    t = np.arange(1, batch * max_pages + 1, dtype=np.int32)
    return jnp.asarray(t.reshape(batch, max_pages))


def model_parity(cfg, params, batch_fn, steps=4, prompt=8, max_len=32,
                 page_size=4):
    """prefill + decode_steps against monolithic and paged caches must
    produce identical logits (same written rows, same masked view)."""
    mp = max_len // page_size
    cm = MZ.init_cache(cfg, 2, max_len, src_len=6)
    cp = MZ.init_cache(cfg, 2, max_len, src_len=6, page_size=page_size)
    cp = MZ.set_page_table(cp, identity_table(2, mp))
    batch = batch_fn(prompt)
    lm, cm = MZ.prefill(params, cfg, batch, cm)
    lp, cp = MZ.prefill(params, cfg, batch, cp)
    np.testing.assert_allclose(np.asarray(lm), np.asarray(lp))
    tok = jnp.argmax(lm[:, :cfg.vocab_size], -1).astype(jnp.int32)
    pos = jnp.full((2,), prompt, jnp.int32)
    for _ in range(steps):
        lm, cm = MZ.decode_step(params, cfg, tok, cm, pos)
        lp, cp = MZ.decode_step(params, cfg, tok, cp, pos)
        np.testing.assert_allclose(np.asarray(lm), np.asarray(lp))
        tok = jnp.argmax(lm[:, :cfg.vocab_size], -1).astype(jnp.int32)
        pos = pos + 1


class TestModelParity:
    """All three families serve identical logits off pages."""

    def test_lm(self, params):
        toks = jax.random.randint(jax.random.key(1), (2, 8), 1, 500)
        model_parity(TINY, params, lambda p: {"tokens": toks})

    def test_hybrid(self):
        cfg = ModelConfig(
            name="hy", n_layers=3, d_model=64, vocab_size=256, n_heads=4,
            n_kv_heads=2, d_ff=128, remat=False,
            layer_kinds=(int(LayerKind.MAMBA), int(LayerKind.SHARED_ATTN),
                         int(LayerKind.MAMBA)))
        p = MZ.init_model(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 8), 1, 250)
        model_parity(cfg, p, lambda _: {"tokens": toks})

    def test_encdec(self):
        cfg = ModelConfig(name="ed", n_layers=2, n_encoder_layers=2,
                          d_model=64, vocab_size=256, n_heads=4,
                          n_kv_heads=2, d_ff=128, remat=False,
                          is_encoder_decoder=True)
        p = MZ.init_model(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 8), 1, 250)
        src = jax.random.normal(jax.random.key(2), (2, 6, 64), jnp.bfloat16)
        model_parity(cfg, p, lambda _: {"src": src, "tokens": toks})


PROMPTS = [np.arange(1, 6, dtype=np.int32),
           np.arange(3, 11, dtype=np.int32),
           np.asarray([7, 9, 11], np.int32)]
BUDGETS = [5, 9, 3]


def serve(cfg, params, scfg, prompts=PROMPTS, budgets=BUDGETS):
    server = Server(cfg, mesh11(), scfg, params)
    uids = [server.submit(p, max_new=n) for p, n in zip(prompts, budgets)]
    done = {r.uid: r.out for r in server.run()}
    assert sorted(done) == sorted(uids)
    return [done[u] for u in uids], server


MONO = dict(slots=2, max_len=64, prompt_pad=8, max_new_tokens=16,
            decode_chunk=4, eos_token=-1)


class TestPagedServer:
    def test_exact_parity_dense(self, params):
        """Full view (page_view_chunk=0) is bit-identical to monolithic:
        same rows written, same masked attention width."""
        mono, _ = serve(TINY, params, ServeConfig(**MONO))
        paged, server = serve(TINY, params, ServeConfig(
            **MONO, page_size=8, page_view_chunk=0))
        assert mono == paged
        assert server.stats["peak_pages"] > 0

    def test_parity_view_bucketed(self, params):
        """The narrowed decode view only drops masked rows — greedy
        outputs stay identical."""
        mono, _ = serve(TINY, params, ServeConfig(**MONO))
        paged, _ = serve(TINY, params, ServeConfig(
            **MONO, page_size=8, page_view_chunk=1))
        assert mono == paged

    @pytest.mark.parametrize("fmt", ["nm", "combined"])
    def test_parity_sparse_packs(self, fmt):
        """Paged serving through the sparse kernels (packed MLP weights
        dispatching nm_spmm / csa_matmul) matches monolithic serving."""
        scfg_pack = {
            "nm": SparsityConfig(format="nm", n=2, m=4, block_n=64),
            "combined": SparsityConfig(format="combined", sparsity=0.5,
                                       n=2, m=4, block_k=64, block_n=64),
        }[fmt]
        cfg = ModelConfig(name=f"tiny-{fmt}", n_layers=2, d_model=128,
                          vocab_size=256, n_heads=4, n_kv_heads=2,
                          d_ff=256, remat=False, mlp_sparsity=scfg_pack)
        p = pack_params(MZ.init_model(jax.random.key(0), cfg), cfg)
        mono, _ = serve(cfg, p, ServeConfig(**MONO),
                        prompts=PROMPTS[:2], budgets=BUDGETS[:2])
        paged, _ = serve(cfg, p, ServeConfig(**MONO, page_size=8),
                         prompts=PROMPTS[:2], budgets=BUDGETS[:2])
        assert mono == paged

    def test_eos_mid_chunk(self, params):
        """EOS in the middle of a chunk truncates identically under
        paging (and the slot's pages are freed at retirement)."""
        free_cfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                               max_new_tokens=12, decode_chunk=8,
                               eos_token=-1)
        prompt = np.arange(1, 9, dtype=np.int32)
        free, _ = serve(TINY, params, free_cfg, [prompt], [12])
        eos = free[0][2]                      # mid-chunk token
        paged_cfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                                max_new_tokens=12, decode_chunk=8,
                                eos_token=eos, page_size=8)
        out, server = serve(TINY, params, paged_cfg, [prompt], [12])
        cut = free[0].index(eos)
        assert out[0] == free[0][:cut + 1]
        assert out[0][-1] == eos
        # retirement returned every page
        assert len(server._free_pages) == server.scfg.pool_pages

    def test_refill_reuses_freed_pages(self, params):
        """4 requests through 1 slot on a pool that only fits one
        request at a time: refills must recycle retired pages, and every
        output must match the roomy-pool run."""
        prompts = [np.arange(1 + i, 7 + i, dtype=np.int32)
                   for i in range(4)]
        budgets = [4] * 4
        base = dict(slots=1, max_len=32, prompt_pad=8, max_new_tokens=4,
                    decode_chunk=4, eos_token=-1, page_size=8)
        # each request needs ceil((8 + 4) / 8) = 2 pages
        small, server = serve(TINY, params, ServeConfig(**base, num_pages=2),
                              prompts, budgets)
        roomy, _ = serve(TINY, params, ServeConfig(**base), prompts, budgets)
        assert small == roomy
        # 4 requests × 2 pages served off a 2-page pool → reuse happened
        assert server.stats["peak_pages"] == 2
        assert len(server._free_pages) == 2

    def test_pool_exhaustion_admission(self, params):
        """2 slots but a pool that fits one request: the second request
        waits (admission_waits > 0), then serves correctly."""
        base = dict(slots=2, max_len=32, prompt_pad=8, max_new_tokens=4,
                    decode_chunk=4, eos_token=-1, page_size=8)
        prompts, budgets = PROMPTS[:2], [4, 4]
        tight, server = serve(TINY, params, ServeConfig(**base, num_pages=2),
                              prompts, budgets)
        assert server.stats["admission_waits"] > 0
        roomy, server2 = serve(TINY, params, ServeConfig(**base),
                               prompts, budgets)
        assert server2.stats["admission_waits"] == 0
        # same per-request outputs, admitted serially vs in parallel
        assert tight == roomy

    def test_submit_rejects_impossible_request(self, params):
        scfg = ServeConfig(slots=1, max_len=64, prompt_pad=8,
                           max_new_tokens=32, page_size=8, num_pages=1)
        server = Server(TINY, mesh11(), scfg, params)
        with pytest.raises(ValueError):
            server.submit(np.arange(1, 6, dtype=np.int32))

    def test_one_sync_per_chunk(self, params, monkeypatch):
        """The paging machinery (table refresh, page allocation, view
        bucketing) adds zero device→host transfers."""
        import repro.serving.engine as engine
        calls = []
        orig = engine._device_fetch
        monkeypatch.setattr(engine, "_device_fetch",
                            lambda tree: calls.append(1) or orig(tree))
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=8, decode_chunk=4, eos_token=-1,
                           page_size=8, page_view_chunk=1)
        server = Server(TINY, mesh11(), scfg, params)
        for _ in range(2):
            server.submit(np.arange(1, 6, dtype=np.int32))
        done = server.run()
        assert all(len(r.out) == 8 for r in done)
        assert len(calls) == 2                 # 8 tokens / 4 per chunk
        assert server.sync_count == 2

    def test_prompt_buckets(self, params):
        """Per-request prompt buckets: a short prompt is padded to its
        own bucket, not the uniform prompt_pad — outputs match a
        reference decode run at the same bucket width."""
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=32,
                           max_new_tokens=4, decode_chunk=4, eos_token=-1,
                           page_size=8, prompt_buckets=8)
        prompts = [np.arange(1, 6, dtype=np.int32),           # bucket 8
                   np.arange(1, 20, dtype=np.int32)]          # bucket 24
        out, server = serve(TINY, params, scfg, prompts, [4, 4])
        for p, o in zip(prompts, out):
            rows = server.scfg.prompt_rows(len(p))
            assert rows == min(32, -(-len(p) // 8) * 8)
            ref_out = reference_decode(params, TINY, p, 4, -1, rows, 64)
            assert o == ref_out


class TestPagedKernel:
    """kernels/paged_attention.py against its oracle and the plain MHA
    oracle."""

    def test_kernel_matches_oracle(self):
        rng = np.random.default_rng(0)
        B, H, Hk, D, P, ps, mp = 3, 4, 2, 16, 10, 4, 3
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(P, ps, Hk, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, ps, Hk, D)), jnp.float32)
        ptab = jnp.asarray(rng.integers(1, P, size=(B, mp)), jnp.int32)
        lens = jnp.asarray([5, 12, 0], jnp.int32)   # ragged + dead slot
        from repro.kernels.paged_attention import paged_attention
        o_ref = ref.paged_attention_ref(q, kp, vp, ptab, lens)
        o_k = paged_attention(q, kp, vp, ptab, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                                   atol=1e-5)
        assert (np.asarray(o_ref)[2] == 0).all()    # dead slot → zeros

    def test_oracle_matches_mha(self):
        """With an identity page table the paged oracle equals plain
        causal-at-last-position attention over the first ``lens`` rows."""
        rng = np.random.default_rng(1)
        B, H, D, ps, mp = 2, 4, 16, 4, 4
        L = ps * mp
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
        lens = jnp.asarray([L, L], jnp.int32)
        # pool layout: page j of seq b at pool page b*mp + j (+1 null)
        kp = jnp.concatenate([jnp.zeros((1, ps, H, D), jnp.float32),
                              k.transpose(0, 2, 1, 3).reshape(-1, ps, H, D)])
        vp = jnp.concatenate([jnp.zeros((1, ps, H, D), jnp.float32),
                              v.transpose(0, 2, 1, 3).reshape(-1, ps, H, D)])
        ptab = identity_table(B, mp)
        out = ref.paged_attention_ref(q, kp, vp, ptab, lens)
        want = ref.mha_ref(q[:, :, None], k, v, causal=True)[:, :, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)

    def test_dispatch_descriptor_and_plan(self, params):
        from repro.kernels.paged_attention import PagedKV
        kv = PagedKV(jnp.zeros((4, 8, 2, 16), jnp.bfloat16),
                     jnp.zeros((4, 8, 2, 16), jnp.bfloat16),
                     jnp.zeros((2, 3), jnp.int32),
                     jnp.zeros((2,), jnp.int32))
        d = dispatch.SparsityDescriptor.of(kv)
        assert d.kind == "paged" and d.pattern == "paged8x3"
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=8,
                           max_new_tokens=4, page_size=8)
        server = Server(TINY, mesh11(), scfg, params)
        for plan in (server.prefill_plan, server.decode_plan):
            rows = [p for p in plan if p["kernel"] == "paged_attention"]
            assert len(rows) == 1
            assert rows[0]["pattern"] == "paged8x8"
            assert rows[0]["blocks"] == {"ps": 8, "pages": 8}


# ---------------------------------------------------------------------------
# The stacked pool rides in the layer scan's carry, updated in place
# ---------------------------------------------------------------------------

def _paged_programs(scfg, view_pages):
    """The paged decode loop and per-slot prefill at ``TINY``, lowered
    and compiled for the host; returns their optimized HLO and the
    stacked pool's shape."""
    from repro.serving import loops
    from repro.serving.state import init_decode_state
    mesh = mesh11()
    ap = jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), TINY))
    ac = jax.eval_shape(lambda: MZ.init_cache(
        TINY, scfg.slots, scfg.max_len, page_size=scfg.page_size,
        num_pages=scfg.pool_pages))
    state = jax.eval_shape(lambda: init_decode_state(scfg.slots))
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32 = jax.ShapeDtypeStruct((), np.int32)
    with jax.set_mesh(mesh):
        loop = loops.build_decode_loop(TINY, mesh, scfg, ap, ac, paged=True,
                                       view_pages=view_pages)
        loop_hlo = loop.lower(
            ap, ac, state, jax.ShapeDtypeStruct((scfg.slots,), np.float32),
            key, jax.ShapeDtypeStruct((scfg.slots, scfg.max_pages),
                                      np.int32)).compile().as_text()
        prefill = loops.build_prefill_slot_step(TINY, mesh, scfg, ap, ac,
                                                prompt_rows=8, paged=True)
        prefill_hlo = prefill.lower(
            ap, {"tokens": jax.ShapeDtypeStruct((1, 8), np.int32)}, ac,
            state, i32, i32, jax.ShapeDtypeStruct((), np.float32), key,
            jax.ShapeDtypeStruct((scfg.max_pages,), np.int32)
        ).compile().as_text()
    return {"loop": loop_hlo, "prefill": prefill_hlo}, ac["kp"].shape


class TestPoolInPlace:
    """A paged step writes its rows into the stacked pool where they lie
    and reads only the viewed pages: no program copies, zero-fills,
    slices or writes back a whole pool."""

    def test_no_whole_pool_copy_slice_or_write_back(self):
        """The compiled paged decode loop and per-slot prefill hold no
        ``copy``, ``broadcast`` or ``dynamic-update-slice`` whose output
        is the stacked ``(n_layers, P+1, ps, Hk, D)`` pool, and no
        instruction at all whose output is one layer's ``(P+1, ps, Hk,
        D)`` pool.  The pool is 8x the pages the loop views.

        A layer scan that passes the pool as ``xs``/``ys`` fails this:
        its compiled loop copies both stacked pools for the carry,
        zero-fills a stacked ``ys`` buffer, slices each layer's pool out
        and writes it back with a whole-pool ``dynamic-update-slice``."""
        scfg = ServeConfig(slots=2, max_len=32, prompt_pad=8,
                           max_new_tokens=8, decode_chunk=4, eos_token=-1,
                           page_size=4, num_pages=32, page_view_chunk=2)
        hlo, pool = _paged_programs(scfg, view_pages=2)
        assert pool[1] >= 4 * scfg.slots * 2
        for name, text in hlo.items():
            whole = [op for op in shaped_ops(text, pool)
                     if op[1] in ("copy", "broadcast",
                                  "dynamic-update-slice")]
            assert whole == [], (name, whole)
            assert shaped_ops(text, pool[1:]) == [], name

    def test_pool_rows_match_monolithic_rows(self, params):
        """After a per-slot prefill of two slots and one decode chunk,
        every ``(slot, position)`` row gathered through the page table
        equals the monolithic cache's row, layer by layer, bit for bit.
        A third, frozen slot (retired: done, all-null table row) sits at
        a position past the view, so its write clips into its table's
        nulled tail: it lands in page 0, and every page outside the live
        tables and page 0 keeps its stale contents."""
        from repro.serving import loops
        from repro.serving.state import init_decode_state
        ps, slots, rows, frozen_pos = 4, 3, 8, 22
        base = dict(slots=slots, max_len=32, prompt_pad=rows,
                    max_new_tokens=8, decode_chunk=4, eos_token=-1)
        mono_cfg = ServeConfig(**base)
        paged_cfg = ServeConfig(**base, page_size=ps, page_view_chunk=4)
        # scattered, non-contiguous pages: 3 a slot hold rows 0..11
        table = np.zeros((slots, paged_cfg.max_pages), np.int32)
        table[0, :3] = [5, 17, 2]
        table[1, :3] = [9, 3, 22]
        live_pages = set(table[:2, :3].ravel().tolist())
        mesh = mesh11()
        ap = jax.eval_shape(lambda: params)
        mono = MZ.init_cache(TINY, slots, 32)
        paged = MZ.init_cache(TINY, slots, 32, page_size=ps)
        # stale pool contents: whatever earlier requests left behind
        paged = {**paged,
                 "kp": jax.random.normal(jax.random.key(7),
                                         paged["kp"].shape, jnp.bfloat16),
                 "vp": jax.random.normal(jax.random.key(8),
                                         paged["vp"].shape, jnp.bfloat16)}
        stale = {k: np.asarray(paged[k]) for k in ("kp", "vp")}
        prompts = jax.random.randint(jax.random.key(1), (2, rows), 1, 500)
        temps = jnp.zeros((slots,), jnp.float32)
        key = jax.random.key(3)
        out = {}
        with jax.set_mesh(mesh):
            for kind, scfg, cache in (("mono", mono_cfg, mono),
                                      ("paged", paged_cfg, paged)):
                is_paged = kind == "paged"
                ac = jax.eval_shape(lambda: cache)
                prefill = loops.build_prefill_slot_step(
                    TINY, mesh, scfg, ap, ac, prompt_rows=rows,
                    paged=is_paged)
                state = init_decode_state(slots)
                for s in range(2):
                    extra = (jnp.asarray(table[s]),) if is_paged else ()
                    cache, state = prefill(
                        params, {"tokens": prompts[s:s + 1]}, cache, state,
                        jnp.int32(s), jnp.int32(8), jnp.float32(0.0), key,
                        *extra)
                state = {**state,
                         "tok": state["tok"].at[2].set(11),
                         "pos": state["pos"].at[2].set(frozen_pos)}
                loop = loops.build_decode_loop(
                    TINY, mesh, scfg, ap, ac, paged=is_paged,
                    view_pages=4 if is_paged else None)
                extra = (jnp.asarray(table),) if is_paged else ()
                cache, state, _, _ = loop(params, cache, state, temps, key,
                                          *extra)
                out[kind] = jax.tree.map(np.asarray, cache)
        end = rows + scfg.decode_chunk
        assert np.asarray(state["pos"])[:2].tolist() == [end, end]
        for name in ("k", "v"):
            pool, rows_m = out["paged"][name + "p"], out["mono"][name]
            for s in range(2):
                pos = np.arange(end)
                got = pool[:, table[s, pos // ps], pos % ps]
                np.testing.assert_array_equal(got, rows_m[:, s, :end])
            # the frozen slot's write went to the null page: its layer-0
            # row (a function of its token and position alone) is there
            np.testing.assert_array_equal(
                pool[0, 0, frozen_pos % ps], rows_m[0, 2, frozen_pos])
            untouched = [p for p in range(1, pool.shape[1])
                         if p not in live_pages]
            np.testing.assert_array_equal(pool[:, untouched],
                                          stale[name + "p"][:, untouched])


_SHARDED_PARITY = r"""
import jax, numpy as np
from repro import models as MZ
from repro.launch.mesh import make_elastic_mesh, make_host_mesh
from repro.models.config import ModelConfig
from repro.serving import Engine, ServeConfig
cfg = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                  n_heads=4, n_kv_heads=2, d_ff=128, remat=False)
with jax.set_mesh(make_host_mesh()):
    params = MZ.init_model(jax.random.key(0), cfg)
r = np.random.default_rng(1)
prompts = [r.integers(1, 500, size=int(r.integers(4, 20))).astype(np.int32)
           for _ in range(4)]
for spec in (0, 2):
    scfg = ServeConfig(slots=3, max_len=64, prompt_pad=24, max_new_tokens=10,
                       decode_chunk=4, eos_token=-1, page_size=4,
                       page_view_chunk=2, spec_k=spec)
    one = Engine(cfg, make_host_mesh(), scfg, params)
    two = Engine(cfg, make_elastic_mesh(model_parallel=2), scfg, params)
    info = two._backend.shard_info()
    assert info["model_extent"] == 2 and info["kv_mode"] == "heads", info
    a, b = one.generate(prompts), two.generate(prompts)
    assert a == b, (spec, a, b)
    assert two.audit()["pool_leaves"] == 2
print("OK")
"""


def test_head_sharded_pool_serves_bit_identically():
    """On a 1x2 mesh (two CPU devices, in a process of its own) the pool
    is head-sharded and the paged Engine — plain and speculative decode
    — serves the same greedy tokens as one device."""
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": os.path.join(root, "src")}
    done = subprocess.run([sys.executable, "-c", _SHARDED_PARITY], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    assert done.stdout.strip().endswith("OK")
