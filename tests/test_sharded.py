"""Tensor-parallel sharded serving (serving/sharded.py).

Single-device half: the mesh fix, the strip-aligned sharding rules and
the shard-local plan keys — pure spec/plan arithmetic on abstract
meshes, runs in tier-1.

Multi-device half (parity, sync contract, per-shard audit) needs a real
multi-device mesh; the ``sharded-smoke`` CI job provides one via::

    REPRO_TEST_DEVICES=8 pytest tests/test_sharded.py

(conftest.py translates that into
``--xla_force_host_platform_device_count=8`` before jax loads).  On a
plain single-CPU run those tests skip.
"""

import logging

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models as MZ
from repro.core.sparse_linear import SparsityConfig, pack_params, \
    sparsify_abstract
from repro.distributed import sharding as SH
from repro.launch.mesh import make_elastic_mesh, make_host_mesh
from repro.models.config import LayerKind, ModelConfig
from repro.serving import Engine, ServeConfig
from repro.serving import sharded as SD

multi = pytest.mark.skipif(jax.device_count() < 8,
                           reason="needs REPRO_TEST_DEVICES=8")

BASE = dict(n_layers=2, d_model=64, vocab_size=256, n_heads=8,
            n_kv_heads=8, head_dim=8, d_ff=128)
SCFG = ServeConfig(slots=4, max_len=96, prompt_pad=32, max_new_tokens=12,
                   decode_chunk=4, page_size=8)


def _cfg(fmt):
    if fmt == "dense":
        return ModelConfig(name="t-dense", **BASE)
    if fmt == "nm":
        sp = SparsityConfig(format="nm", n=2, m=4, block_n=16)
        return ModelConfig(name="t-nm", **BASE, mlp_sparsity=sp,
                           attn_sparsity=sp)
    if fmt == "combined":
        return ModelConfig(
            name="t-comb", **BASE,
            mlp_sparsity=SparsityConfig(format="combined", n=2, m=4,
                                        block_k=16, block_n=16),
            attn_sparsity=SparsityConfig(format="block", block_k=16,
                                         block_n=16))
    assert fmt == "hybrid"
    return ModelConfig(name="t-hy", **BASE,
                       layer_kinds=(LayerKind.MAMBA.value,
                                    LayerKind.ATTN_GLOBAL.value),
                       ssm_state=16, ssm_head_dim=16)


def _params(cfg):
    with jax.set_mesh(make_host_mesh()):
        params = MZ.init_model(jax.random.key(0), cfg)
    if cfg.mlp_sparsity.format != "dense" \
            or cfg.attn_sparsity.format != "dense":
        params = pack_params(params, cfg)
    return params


def _prompts(cfg, n=5):
    r = np.random.default_rng(1)
    return [r.integers(0, cfg.vocab_size - 1,
                       size=int(r.integers(4, 30))).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# make_elastic_mesh fix (runs at any device count)
# ---------------------------------------------------------------------------

class TestElasticMesh:
    def test_raises_when_tp_exceeds_devices(self):
        with pytest.raises(ValueError, match="exceeds"):
            make_elastic_mesh(model_parallel=jax.device_count() + 1)

    def test_raises_nonpositive(self):
        with pytest.raises(ValueError, match=">= 1"):
            make_elastic_mesh(model_parallel=0)

    def test_exact_fit(self):
        m = make_elastic_mesh(model_parallel=1)
        assert dict(m.shape) == {"data": jax.device_count(), "model": 1}

    @multi
    def test_degrade_logs_chosen_shape(self, caplog):
        n = jax.device_count()
        with caplog.at_level(logging.WARNING, logger="repro.launch.mesh"):
            m = make_elastic_mesh(model_parallel=3)     # 3 ∤ 8
        assert dict(m.shape)["model"] < 3
        assert dict(m.shape)["model"] * dict(m.shape)["data"] <= n
        assert any("does not divide" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# Sharding rules + shard-local plan keys (abstract mesh: tier-1)
# ---------------------------------------------------------------------------

def _amesh():
    return SH.abstract_mesh((1, 8), ("data", "model"))


class TestShardRules:
    def test_shard_factors(self):
        mesh = _amesh()
        assert SH.shard_factors(("layers", "attn", "wq"), mesh) == (1, 8)
        assert SH.shard_factors(("layers", "attn", "wo"), mesh) == (8, 1)
        assert SH.shard_factors(("norm", "scale"), mesh) == (1, 1)
        host = SH.abstract_mesh((1, 1), ("data", "model"))
        assert SH.shard_factors(("layers", "attn", "wq"), host) == (1, 1)

    def test_bsr_strip_axis_aligned(self):
        cfg = _cfg("combined")
        abstract = sparsify_abstract(
            jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg)),
            cfg)
        mesh = _amesh()
        specs = SH.param_specs(abstract, cfg, mesh)
        assert SH.validate_specs(abstract, specs, mesh) == []
        # col-parent (w_in, combined): strips shard over "model", and the
        # strip metadata rides along — never the (bk, bn) tile dims
        win = specs["layers"]["mlp"]["w_in"]
        assert tuple(win.values)[-4] == "model"
        assert all(ax is None for ax in tuple(win.values)[-3:])
        assert tuple(win.indices)[-2] == "model"
        assert tuple(win.counts)[-1] == "model"
        # row-parent (wo, block): strips FSDP-shard, never "model"
        wo = specs["layers"]["attn"]["wo"]
        assert tuple(wo.values)[-4] in ("data", None)
        assert "model" not in tuple(wo.values)

    def test_nm_metadata_aligned(self):
        cfg = _cfg("nm")
        mesh = _amesh()
        abstract = sparsify_abstract(
            jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg)),
            cfg)
        specs = SH.param_specs(abstract, cfg, mesh)
        assert SH.validate_specs(abstract, specs, mesh) == []
        wq = specs["layers"]["attn"]["wq"]          # col-parallel
        assert tuple(wq.values)[-1] == "model"
        # raw rules (pre best_effort): idx (.., N//g, Kc) shards its
        # column groups aligned with the values' N axis; row-parallel
        # flips to Kc
        assert SH._param_rule(("layers", "attn", "wq", "idx"),
                              (2, 4, 16), cfg, mesh) \
            == P(None, "model", None)
        assert SH._param_rule(("layers", "attn", "wo", "idx"),
                              (2, 4, 16), cfg, mesh) \
            == P(None, None, "model")
        assert SH._param_rule(("layers", "attn", "wo", "values"),
                              (2, 16, 64), cfg, mesh) \
            == P(None, "model", "data")

    def test_plan_keys_shard_local(self):
        cfg = _cfg("nm")
        abstract = sparsify_abstract(
            jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg)),
            cfg)
        plans1 = SD.build_plans(abstract, None, cfg, SCFG, mesh=None)
        plans8 = SD.build_plans(abstract, None, cfg, SCFG, mesh=_amesh())
        assert all("shard" not in r for r in plans1["decode"])
        packs8 = [r for r in plans8["decode"]
                  if r["param"] != "attention/kv_cache"]
        assert packs8 and all(r["shard"] in ([1, 8], [8, 1])
                              for r in packs8)
        pa1 = [r for r in plans1["decode"]
               if r["param"] == "attention/kv_cache"]
        pa8 = [r for r in plans8["decode"]
               if r["param"] == "attention/kv_cache"]
        assert pa1[0]["pattern"] == "paged8x12"
        assert pa8[0]["pattern"] == "paged8x12h1"   # Hk=8 over ext=8

    def test_model_extent_and_kv_heads(self):
        assert SD.model_extent(None) == 1
        assert SD.model_extent(_amesh()) == 8
        cfg = _cfg("dense")
        assert SD.kv_heads_per_shard(cfg, _amesh()) == 1
        assert SD.kv_heads_per_shard(cfg, None) is None
        from types import SimpleNamespace
        cfg6 = SimpleNamespace(n_kv_heads=6, n_heads=6)
        assert SD.kv_heads_per_shard(cfg6, _amesh()) is None  # 6 ∤ 8


# ---------------------------------------------------------------------------
# Multi-device: parity, sync contract, per-shard audit
# ---------------------------------------------------------------------------

@multi
@pytest.mark.parametrize("fmt", ["dense", "nm", "combined", "hybrid"])
def test_sharded_greedy_parity(fmt):
    """8-way sharded greedy decode is bit-identical to the single-device
    paged Engine — weights placed by the Engine itself."""
    cfg = _cfg(fmt)
    params = _params(cfg)
    prompts = _prompts(cfg)
    e1 = Engine(cfg, make_host_mesh(), SCFG, params)
    out1 = e1.generate(prompts)
    e8 = Engine(cfg, make_elastic_mesh(model_parallel=8), SCFG, params)
    assert getattr(e8._backend, "sharded", False)
    out8 = e8.generate(prompts)
    assert out1 == out8
    assert e1.sync_count == e8.sync_count


@multi
def test_spec_decode_parity_sharded():
    """Speculative decode (self-draft, greedy) matches across meshes."""
    cfg = _cfg("dense")
    params = _params(cfg)
    prompts = _prompts(cfg, 4)
    scfg = ServeConfig(slots=4, max_len=96, prompt_pad=32,
                       max_new_tokens=10, decode_chunk=4, page_size=8,
                       spec_k=2, spec_draft="self")
    out1 = Engine(cfg, make_host_mesh(), scfg, params).generate(prompts)
    out8 = Engine(cfg, make_elastic_mesh(model_parallel=8), scfg,
                  params).generate(prompts)
    assert out1 == out8


@multi
def test_one_fetch_per_chunk_under_sharding():
    """The sync contract survives sharding: every device→host transfer
    goes through the engine's fetch seam, once per chunk."""
    cfg = _cfg("dense")
    e = Engine(cfg, make_elastic_mesh(model_parallel=8), SCFG, _params(cfg))
    calls = {"n": 0}
    inner = e._device_fetch

    def counting(tree):
        calls["n"] += 1
        return inner(tree)

    e._device_fetch = counting
    for p in _prompts(cfg):
        e.submit(p)
    ticks = 0
    while e.num_live or e.num_queued:
        before = calls["n"]
        e.step()
        ticks += 1
        assert calls["n"] - before <= 1     # ≤ one fetch per tick
    assert calls["n"] == e.sync_count > 0


@multi
def test_audit_per_shard_and_fallback():
    cfg = _cfg("dense")
    mesh8 = make_elastic_mesh(model_parallel=8)
    e = Engine(cfg, mesh8, SCFG, _params(cfg))
    e.generate(_prompts(cfg))
    report = e.audit()
    assert report["ptab_leaves"] >= 1
    assert report["pool_leaves"] == 2 * 1   # kp + vp (one attn subtree)
    info = e._backend.shard_info()
    assert info["model_extent"] == 8 and info["kv_mode"] == "heads"
    assert e._backend.pool_bytes_per_shard() > 0
    # a pool sharded along its PAGE axis must fail the audit
    from repro.serving.chaos import AuditError
    bad = jax.device_put(
        np.zeros((2, 8, 8, 8, 8), np.float32),
        NamedSharding(mesh8, P(None, "model", None, None, None)))
    with pytest.raises(AuditError, match="page axis"):
        e._backend.audit_shards({"kp": bad})


@multi
def test_mono_backend_sharded():
    cfg = _cfg("dense")
    scfg = ServeConfig(slots=4, max_len=96, prompt_pad=32,
                       max_new_tokens=8, decode_chunk=4)   # monolithic
    prompts = _prompts(cfg, 3)
    out1 = Engine(cfg, make_host_mesh(), scfg, _params(cfg)
                  ).generate(prompts)
    e8 = Engine(cfg, make_elastic_mesh(model_parallel=8), scfg,
                _params(cfg))
    assert type(e8._backend).__name__ == "ShardedMonoBackend"
    assert e8.generate(prompts) == out1


def test_single_device_fallback():
    """On a 1-wide model axis nothing sharded is selected and plans
    carry no shard keys — the untouched fast path."""
    cfg = _cfg("dense")
    e = Engine(cfg, make_host_mesh(), SCFG, _params(cfg))
    assert not getattr(e._backend, "sharded", False)
    assert all("shard" not in r for r in e.decode_plan)
    assert e.generate(_prompts(cfg, 2))
