"""The served kernels compiled for a described TPU v5e (no chip needed).

Qwen3-0.6B widths (d_model 1024, d_ff 3072, 16 query / 8 KV heads of
128), bf16, at decode M = 8 slots, speculative-verify M = 8·(4+1) = 40
and prefill-tile M = 128; the paged-attention kernel at 8 slots, page
size 16, Hk = 8, D = 128; and the sparse kernels under ``shard_map`` on
the 4-chip ``model`` axis.  Each compile runs the TPU compiler, so
tiling, VMEM and partitioning refusals show up here before chip time is
spent.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library, so only the test
worker that runs this file loads it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.sparse_linear import SparsityConfig, abstract_pack
from repro.distributed import sharding as SH
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh

D_MODEL, D_FF = 1024, 3072
MLP_SHAPES = [(D_MODEL, D_FF), (D_FF, D_MODEL)]        # w_in, w_out
NM = SparsityConfig(format="nm", n=2, m=4, block_n=128)
COMBINED = SparsityConfig(format="combined", n=2, m=4, block_k=128,
                          block_n=128, sparsity=0.5)
BLOCK = SparsityConfig(format="block", block_k=128, block_n=128,
                       sparsity=0.5)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh((1, 4), devices=topo.devices)


@pytest.fixture(autouse=True)
def empty_autotune_cache(tmp_path):
    """Blocks are the dispatch defaults, not whatever a local sweep
    left in the checkout's cache."""
    old = dispatch.set_autotune_cache(
        dispatch.AutotuneCache(str(tmp_path / "autotune.json")))
    yield
    dispatch.set_autotune_cache(old)


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("M", [8, 40, 128])
@pytest.mark.parametrize("K,N", MLP_SHAPES)
@pytest.mark.parametrize("scfg", [NM, COMBINED, BLOCK],
                         ids=["nm_spmm", "csa_matmul", "bsr_matmul"])
def test_sparse_kernel_compiles(one_chip, scfg, K, N, M):
    pack = _placed(abstract_pack(K, N, scfg), one_chip)
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    d = dispatch.select(pack, M=M, impl="compiled")
    entry = dispatch.registry()[d.kernel]
    hlo = _compile(lambda x, w: entry.run(x, w, "compiled", d.blocks),
                   x, pack)
    assert "tpu_custom_call" in hlo


def test_paged_attention_compiles(one_chip):
    from repro.kernels.paged_attention import paged_attention
    B, H, Hk, D, ps, pages = 8, 16, 8, 128, 16, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = sds((B * pages + 1, ps, Hk, D), jnp.bfloat16)
    hlo = _compile(paged_attention, sds((B, H, D), jnp.bfloat16), pool,
                   pool, sds((B, pages), jnp.int32), sds((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name,K,N", [("w_in", D_MODEL, D_FF),
                                      ("w_out", D_FF, D_MODEL)])
@pytest.mark.parametrize("scfg", [NM, COMBINED],
                         ids=["nm_spmm", "csa_matmul"])
def test_sharded_kernel_compiles(mesh4, scfg, name, K, N):
    """A Mosaic kernel cannot be partitioned by XLA: on a ``model=4``
    mesh dispatch runs it per shard under ``shard_map`` (column-parallel
    w_in needs no collective; row-parallel N:M w_out one all-reduce)."""
    abstract = abstract_pack(K, N, scfg)
    names = ("layers", "mlp", name)
    specs = jax.tree_util.tree_map_with_path(
        lambda path, a: SH.best_effort(
            SH._param_rule(names + SH._path_names(path), a.shape, None,
                           mesh4), a.shape, mesh4), abstract)
    pack = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh4, s)),
        abstract, specs)
    x = jax.ShapeDtypeStruct((8, K), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, P()))
    with jax.set_mesh(mesh4):
        hlo = _compile(lambda x, w: dispatch.sparse_matmul(
            x, w, impl="compiled", name=name), x, pack)
    assert "tpu_custom_call" in hlo
    collectives = set(re.findall(r"all-reduce|all-gather", hlo))
    row_nm = name == "w_out" and scfg is NM
    assert collectives == ({"all-reduce"} if row_nm else set())


@pytest.mark.parametrize("program", ["decode_loop", "prefill_slot"])
def test_paged_programs_update_the_pool_in_place(topo, program):
    """The ``nm-chat`` serving programs at Qwen3-0.6B's 28 layers on one
    chip (16 slots, a 1,393-page pool of 16 rows, a 32-page view): the
    compiled decode loop and per-slot prefill write the stacked pool in
    place — no ``copy``, ``broadcast``, ``dynamic-slice`` or
    ``dynamic-update-slice`` outputs a whole stacked pool, and nothing
    outputs one layer's pool.  A layer scan that passes the pool as
    ``xs``/``ys`` leaves two whole-pool copies and two whole-pool
    ``dynamic-update-slice`` write-backs here, and slices a 45.6 MB
    layer pool out twice every layer."""
    from conftest import shaped_ops
    from repro import models as MZ
    from repro.models.config import ModelConfig
    from repro.serving import ServeConfig, loops
    from repro.serving.state import init_decode_state
    cfg = ModelConfig(name="qwen3-0.6b", n_layers=28, d_model=D_MODEL,
                      vocab_size=151936, n_heads=16, n_kv_heads=8,
                      head_dim=128, d_ff=D_FF, qk_norm=True, rope_theta=1e6,
                      norm_eps=1e-6, tie_embeddings=True, remat=False)
    scfg = ServeConfig(slots=16, max_len=1392, prompt_pad=448,
                       max_new_tokens=960, decode_chunk=16, eos_token=-1,
                       page_size=16, prompt_buckets=32, page_view_chunk=8)
    mesh = make_mesh((1, 1), devices=topo.devices[:1])
    rep = NamedSharding(mesh, P())

    def placed(fn):
        return _placed(jax.eval_shape(fn), rep)
    ap = placed(lambda: MZ.init_model(jax.random.key(0), cfg))
    ac = placed(lambda: MZ.init_cache(cfg, scfg.slots, scfg.max_len,
                                      page_size=scfg.page_size,
                                      num_pages=scfg.pool_pages))
    state = placed(lambda: init_decode_state(scfg.slots))
    key = placed(lambda: jax.random.key(0))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
    with jax.set_mesh(mesh):
        if program == "decode_loop":
            fn = loops.build_decode_loop(cfg, mesh, scfg, ap, ac, paged=True,
                                         view_pages=32)
            args = (ap, ac, state, sds((16,), jnp.float32), key,
                    sds((16, scfg.max_pages), jnp.int32))
        else:
            fn = loops.build_prefill_slot_step(cfg, mesh, scfg, ap, ac,
                                               prompt_rows=160, paged=True)
            i32 = sds((), jnp.int32)
            args = (ap, {"tokens": sds((1, 160), jnp.int32)}, ac, state,
                    i32, i32, sds((), jnp.float32), key,
                    sds((scfg.max_pages,), jnp.int32))
        hlo = fn.lower(*args).compile().as_text()
    pool = ac["kp"].shape
    assert pool == (28, 1393, 16, 8, 128)
    whole = [op for op in shaped_ops(hlo, pool)
             if op[1] in ("copy", "broadcast", "dynamic-slice",
                          "dynamic-update-slice")]
    assert whole == []
    assert shaped_ops(hlo, pool[1:]) == []
