"""Offline autotune warmer: populate the JSON block-size cache for the
serving-relevant dispatch keys *before* the first request pays for it.

  PYTHONPATH=src python -m benchmarks.run --warm-autotune

The serving engine re-plans the dispatch layer per phase geometry
(``M = slots`` for decode, ``M = prompt_pad`` for per-slot refill,
``M = slots*prompt_pad`` for wave prefill); on the compiled path each
``(kernel, M, K, N, dtype, pattern)`` key triggers a block-size sweep on
first use.  This module runs those sweeps offline over the serving
formats (nm / combined packed MLPs, the paged-attention cache geometry)
and persists the winners to the cache (``REPRO_AUTOTUNE_CACHE`` or
``~/.cache/repro/autotune.json``) — so a fresh server's first request
hits warm cache entries instead of eating the sweep (ROADMAP: "feed real
sweep timings into the cache").

On TPU the sweeps time the *compiled* kernels (real timings); elsewhere
they run in interpret mode, which exercises the exact kernel logic and
the full cache machinery on the same keys (useful for CI and for
verifying the flow, not for timing quality).
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import time
from typing import List

import jax
import jax.numpy as jnp

from repro import models as MZ
from repro.core.sparse_linear import SparsityConfig, pack_params
from repro.distributed import sharding as SH
from repro.kernels import dispatch
from repro.kernels.dispatch import PACK_TYPES
from repro.models.config import ModelConfig


SPEC_KS = (2, 4, 8)     # verify-block depths the spec rows serve at

# the packed formats warmed, on a 2-layer d_model-64 model, and the paged
# pool geometry (16-row pages, max_len 512 + 2*32) of the cache sweep
SPARSITY = {
    "nm": SparsityConfig(format="nm", n=2, m=4, block_n=64),
    "combined": SparsityConfig(format="combined", sparsity=0.5, n=2, m=4,
                               block_k=64, block_n=64),
}
WARM_PAGE = 16
WARM_MAX_LEN = 512 + 2 * 32

# shard-local warm geometry: wide enough that every model-parallel
# extent below still divides the packed blocks (d_ff/8 = 64 = block_n)
SD_D_MODEL, SD_FF, SD_HEADS = 64, 512, 8


def _serving_ms(slots: int, prompt_pad: int, interpret: bool) -> List[int]:
    """The Ms the engine plans at.  Besides the per-slot decode and
    refill geometries this includes the speculative *verify* shapes —
    ``M = slots*(k+1)`` for k ∈ SPEC_KS — so a ``spec_k`` server's one
    batched dense verify hits a warm cache row too (they are
    decode-shaped small-M keys, cheap to sweep even in interpret mode).
    Interpret mode (CPU) skips only the wave geometry — interpreting a
    ``slots*prompt_pad``-row sweep takes minutes and times nothing
    real."""
    ms = {slots, prompt_pad}
    ms.update(slots * (k + 1) for k in SPEC_KS)
    if not interpret:
        ms.add(slots * prompt_pad)
    return sorted(ms)


def _base_ndim(pack, arr) -> int:
    """ndim this array leaf has in an *unstacked* (single-layer) pack."""
    fields = {
        "values": 4 if hasattr(pack, "counts") else 2,   # bsr/csa vs nm
        "indices": 2, "counts": 1, "gidx": 3, "idx": 2,
        "enc": 2, "scale": 2,
    }
    for name, nd in fields.items():
        if getattr(pack, name, None) is arr:
            return nd
    return arr.ndim


def _layer_packs(params) -> List:
    """Distinct per-layer 2D packs from a (scan-stacked) param pytree —
    one representative slice per geometry, deduped by dispatch pattern.
    Stacked leading axes are peeled to layer 0 (the pack's static
    geometry describes the 2D slice, matching how lax.scan feeds it)."""
    seen, packs = set(), []

    def visit(leaf):
        if isinstance(leaf, PACK_TYPES):
            def peel(a, pack=leaf):
                while a.ndim > _base_ndim(pack, a):
                    a = a[0]
                return a
            sl = jax.tree.map(peel, leaf)
            d = dispatch.SparsityDescriptor.of(sl)
            key = (d.kind, d.pattern, d.K, d.N)
            if key not in seen:
                seen.add(key)
                packs.append(sl)
        return leaf

    jax.tree.map(visit, params,
                 is_leaf=lambda x: isinstance(x, PACK_TYPES))
    return packs


def _model(fmt: str):
    cfg = ModelConfig(name=f"warm-{fmt}", n_layers=2, d_model=64,
                      vocab_size=256, n_heads=4, n_kv_heads=2, d_ff=128,
                      remat=False, mlp_sparsity=SPARSITY[fmt])
    return cfg, pack_params(MZ.init_model(jax.random.key(0), cfg), cfg)


def _tp_model(scfg_sp, d_ff):
    cfg = ModelConfig(name=f"warm-tp-{d_ff}", n_layers=1,
                      d_model=SD_D_MODEL, vocab_size=256,
                      n_heads=SD_HEADS, n_kv_heads=SD_HEADS, d_ff=d_ff,
                      remat=False, mlp_sparsity=scfg_sp)
    return cfg, pack_params(MZ.init_model(jax.random.key(0), cfg), cfg)


def _shard_keys(params, mesh, M, mode):
    """(engine cache key, shard-local (kind, K, N)) for every packed
    weight that actually splits under ``mesh``'s model extent — the
    exact keys a sharded engine's ``plan_params(..., shard_of=...)``
    looks up (descriptor scaled the way ``dispatch.select`` scales it:
    K/N divided, density kept from the full pack)."""
    out, seen = [], set()

    def visit(path, leaf):
        if not isinstance(leaf, PACK_TYPES):
            return leaf
        parts = tuple(str(getattr(p, "key", getattr(p, "idx", "?")))
                      for p in path)
        kf, nf = SH.shard_factors(parts, mesh)
        d = dispatch.SparsityDescriptor.of(leaf)
        kf = kf if kf > 1 and d.K % kf == 0 else 1
        nf = nf if nf > 1 and d.N % nf == 0 else 1
        if kf == 1 and nf == 1:
            return leaf
        dsh = dataclasses.replace(d, K=d.K // kf, N=d.N // nf)
        entry = dispatch._entry_for(dsh, M)
        if entry is not None:
            key = dispatch.cache_key(entry.name, M, dsh, mode)
            if key not in seen:
                seen.add(key)
                out.append((key, (dsh.kind, dsh.K, dsh.N)))
        return leaf

    jax.tree_util.tree_map_with_path(
        visit, params, is_leaf=lambda x: isinstance(x, PACK_TYPES))
    return out


def run(slots: int = 8, prompt_pad: int = 128, reps: int = 1,
        device_counts=(2, 4, 8)) -> dict:
    """Sweep and persist; returns {"entries": [...], "cache_path": ...}.

    ``slots``/``prompt_pad`` should match the target server's
    ``ServeConfig`` (defaults mirror its defaults) — the cache keys carry
    M, so a warm at the wrong geometry warms nothing.
    """
    interpret = not dispatch.has_tpu()
    mode = "interpret" if interpret else "compiled"
    cache = dispatch.autotune_cache()
    entries = []
    t0 = time.time()
    for fmt in SPARSITY:
        cfg, params = _model(fmt)
        for pack in _layer_packs(params):
            d = dispatch.SparsityDescriptor.of(pack)
            dtype = getattr(pack, "values", getattr(pack, "enc", None)).dtype
            for M in _serving_ms(slots, prompt_pad, interpret):
                x = jax.random.normal(jax.random.key(0), (M, d.K),
                                      jnp.float32).astype(dtype)
                key = dispatch.cache_key(
                    dispatch._entry_for(d, M).name, M, d, mode)
                was_cached = cache.get(key) is not None
                blocks = dispatch.tune(x, pack, mode=mode, reps=reps)
                entries.append({"key": key, "blocks": blocks,
                                "cached": was_cached})
    # paged-attention: the decode-geometry key for the bench cache shape
    # (static config only — no weights needed for zero-filled pools)
    from repro.kernels.paged_attention import PagedKV
    cfg = ModelConfig(name="warm-paged", n_layers=1, d_model=64,
                      vocab_size=256, n_heads=4, n_kv_heads=2, d_ff=128)
    mp = -(-WARM_MAX_LEN // WARM_PAGE)
    pool = jnp.zeros((slots * mp + 1, WARM_PAGE, cfg.n_kv_heads,
                      cfg.head_dim), jnp.bfloat16)
    # decode geometry (one query per slot) plus the speculative verify
    # geometries (slots*(k+1) queries) — plan keys carry M, so each
    # depth is its own cache row
    for m in [slots] + [slots * (k + 1) for k in SPEC_KS]:
        kv = PagedKV(pool, pool,
                     jnp.zeros((m, mp), jnp.int32),
                     jnp.full((m,), WARM_PAGE, jnp.int32))
        q = jnp.zeros((m, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
        d = dispatch.SparsityDescriptor.of(kv)
        key = dispatch.cache_key("paged_attention", m, d, mode)
        was_cached = cache.get(key) is not None
        blocks = dispatch.tune(q, kv, mode=mode, reps=reps)
        entries.append({"key": key, "blocks": blocks, "cached": was_cached})
    # --- shard-local geometries (tensor-parallel serving) ------------------
    # a model-parallel engine scales each pack's descriptor to its shard
    # (column parallel: N/ext output features; row parallel: K/ext rows)
    # and keys block lookups there — distinct cache rows from the sweeps
    # above.  Packing the same model at d_ff/ext reproduces the exact
    # shard-local MLP geometry, so the sweep times real shard-sized
    # kernels; winners are recorded under the sharded plan's own keys.
    for fmt in SPARSITY:
        _, full_params = _tp_model(SPARSITY[fmt], SD_FF)
        for ext in device_counts:
            if SD_FF % ext:
                continue
            mesh = SH.abstract_mesh((1, ext), ("data", "model"))
            keys = _shard_keys(full_params, mesh, slots, mode)
            if not keys:
                continue
            _, local_params = _tp_model(SPARSITY[fmt], SD_FF // ext)
            local = {}
            for p in _layer_packs(local_params):
                dl = dispatch.SparsityDescriptor.of(p)
                local[(dl.kind, dl.K, dl.N)] = p
            for key, knk in keys:
                pack = local.get(knk)
                if pack is None:
                    continue
                dtype = getattr(pack, "values",
                                getattr(pack, "enc", None)).dtype
                x = jax.random.normal(jax.random.key(0), (slots, knk[1]),
                                      jnp.float32).astype(dtype)
                was_cached = cache.get(key) is not None
                blocks = dispatch.tune(x, pack, mode=mode, reps=reps)
                if blocks and cache.get(key) is None:
                    cache.put(key, dict(blocks))
                entries.append({"key": key, "blocks": blocks,
                                "cached": was_cached, "devices": ext})
    # head-parallel paged pools: per-shard head-count keys (h-suffixed).
    # The plain paged key does not carry a head count, so each per-shard
    # pool is swept for real against a scratch cache and the winner
    # recorded under the sharded plan's key.
    hd = SD_D_MODEL // SD_HEADS
    with tempfile.TemporaryDirectory() as td:
        scratch = dispatch.AutotuneCache(os.path.join(td, "scratch.json"))
        for ext in device_counts:
            if SD_HEADS % ext:
                continue
            hk = SD_HEADS // ext
            pool = jnp.zeros((slots * mp + 1, WARM_PAGE, hk, hd),
                             jnp.bfloat16)
            kv = PagedKV(pool, pool, jnp.zeros((slots, mp), jnp.int32),
                         jnp.full((slots,), WARM_PAGE, jnp.int32))
            q = jnp.zeros((slots, SD_HEADS // ext, hd), jnp.bfloat16)
            dsh = dispatch.SparsityDescriptor(
                kind="paged", K=mp * WARM_PAGE, N=hd, dtype="bfloat16",
                g=WARM_PAGE, bk=mp, n=hk)
            key = dispatch.cache_key("paged_attention", slots, dsh, mode)
            was_cached = cache.get(key) is not None
            blocks = dispatch.tune(q, kv, mode=mode, reps=reps,
                                   cache=scratch)
            if blocks and cache.get(key) is None:
                cache.put(key, dict(blocks))
            entries.append({"key": key, "blocks": blocks,
                            "cached": was_cached, "devices": ext})
    return {"entries": entries, "mode": mode, "wall_s": time.time() - t0,
            "cache_path": cache.path, "cache_size": len(cache),
            "device_counts": list(device_counts)}


def main(out=None) -> None:
    if out is None:
        out = run()
    print(f"# autotune warm — {len(out['entries'])} serving keys swept "
          f"({out['mode']} mode, {out['wall_s']:.1f}s)")
    for e in out["entries"]:
        print(f"  {e['key']} -> {e['blocks']}")
    print(f"cache: {out['cache_path']} ({out['cache_size']} entries)")


if __name__ == "__main__":
    main()
