"""Pallas kernel sweeps (interpret=True) against the pure-jnp oracles.

Every kernel × a shape/dtype grid; assert_allclose vs ref.py and vs the
dense masked matmul ground truth.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pruning, sparsity
from repro.kernels import ops, ref

RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(seed, shape, dtype=jnp.float32):
    x = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (256, 512, 256),
                                   (64, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s", [0.0, 0.5, 0.9])
def test_bsr_matmul(M, K, N, dtype, s):
    x = rand(0, (M, K), dtype)
    w = rand(1, (K, N))
    wp, _ = pruning.block_semi_structured(w, s, block=128)
    pack = sparsity.pack_block_sparse(wp.astype(dtype), 128, 128)
    out_k = ops.block_sparse_matmul(x, pack, impl="kernel")
    out_r = ref.bsr_matmul_ref(x, pack)
    dense = jnp.dot(x.astype(jnp.float32),
                    pack.densify().astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=RTOL[dtype], atol=1e-2)
    np.testing.assert_allclose(np.asarray(out_r, np.float32),
                               np.asarray(dense), rtol=RTOL[dtype],
                               atol=2e-1 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("n,m", [(1, 4), (2, 4), (4, 8)])
@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (128, 512, 256)])
def test_nm_spmm(n, m, M, K, N):
    x = rand(2, (M, K))
    w = rand(3, (K, N))
    wp, _ = pruning.n_m(w, n, m, group=128)
    pack = sparsity.pack_nm(wp, n, m, g=128)
    bkc = min(128, pack.Kc)
    while pack.Kc % bkc:
        bkc //= 2
    out_k = ops.nm_matmul(x, pack, impl="kernel", bkc=bkc)
    out_r = ref.nm_spmm_ref(x, pack)
    dense = x @ pack.densify()
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(dense),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("M", [8, 40])             # decode, spec verify
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nm_spmm_decode(M, dtype):
    """Served geometry: M = slots rows through a bm=8 tile, one column
    tile holding several 128-wide groups (metadata block (groups, bkc))."""
    x = rand(7, (M, 512), dtype)
    wp, _ = pruning.n_m(rand(8, (512, 256)), 2, 4, group=128)
    pack = sparsity.pack_nm(wp.astype(dtype), 2, 4, g=128)
    out_k = ops.nm_matmul(x, pack, impl="kernel", bm=8, bkc=128)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32),
        np.asarray(ref.nm_spmm_ref(x, pack), np.float32),
        rtol=RTOL[dtype], atol=1e-2)


def test_nm_spmm_column_tiles():
    """bn < N: each (bkc, bn) step serves bn/g = 8 groups, and the
    metadata block (8, bkc) walks the group axis with the column tile."""
    from repro.kernels.nm_spmm import nm_spmm
    x = rand(9, (8, 256))
    wp, _ = pruning.n_m(rand(10, (256, 256)), 2, 4, group=16)
    pack = sparsity.pack_nm(wp, 2, 4, g=16)
    out_k = nm_spmm(x, pack, bm=8, bkc=128, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(ref.nm_spmm_ref(x, pack)),
                               rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(x @ pack.densify()),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("x_ss", [0.0, 0.5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_csa_matmul_decode(x_ss, dtype):
    """M = 8 slots through a bm=8 tile; the strip's whole gidx block
    (1, max_nnz, bkc) is fetched once and step t reads row t."""
    x = rand(11, (8, 512), dtype)
    wp, _ = pruning.combined_nm(rand(12, (512, 256)), x_ss, 2, 4,
                                group=128, block=128)
    pack = sparsity.pack_combined(wp.astype(dtype), 2, 4, 128, 128)
    out_k = ops.combined_matmul(x, pack, impl="kernel", bm=8)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32),
        np.asarray(ref.csa_matmul_ref(x, pack), np.float32),
        rtol=RTOL[dtype], atol=1e-2)


@pytest.mark.parametrize("x_ss", [0.0, 0.5])
@pytest.mark.parametrize("M,K,N", [(128, 512, 128), (128, 256, 256)])
def test_csa_matmul(x_ss, M, K, N):
    x = rand(4, (M, K))
    w = rand(5, (K, N))
    wp, _ = pruning.combined_nm(w, x_ss, 2, 4, group=128, block=128)
    pack = sparsity.pack_combined(wp, 2, 4, 128, 128)
    out_k = ops.combined_matmul(x, pack, impl="kernel")
    out_r = ref.csa_matmul_ref(x, pack)
    dense = x @ pack.densify()
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(dense),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (128, 128, 256)])
def test_lookahead_matmul(M, K, N):
    x = rand(6, (M, K))
    w = rand(7, (K, N))
    wp, _ = pruning.block_semi_structured(w, 0.5, block=4)
    pack = sparsity.LookaheadPack.from_float(wp)
    out_k = ops.lookahead_matmul(x, pack, impl="kernel")
    out_r = ref.lookahead_matmul_ref(x, pack)
    dense = x @ pack.decode()
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(dense),
                               rtol=2e-5, atol=1e-3)


def test_lookahead_int7_exact():
    """In-kernel bit decode must equal the host decode bit-for-bit."""
    rng = np.random.default_rng(8)
    w = rng.integers(-64, 64, size=(128, 128)).astype(np.int8)
    from repro.core import encoding
    enc = encoding.encode_weight_matrix(jnp.asarray(w))
    pack = sparsity.LookaheadPack(enc=enc,
                                  scale=jnp.ones((1, 128), jnp.float32),
                                  K=128, N=128)
    x = jnp.eye(128, dtype=jnp.float32)
    out = ops.lookahead_matmul(x, pack, impl="kernel")
    np.testing.assert_array_equal(np.asarray(out), w.astype(np.float32))


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,Hk,L,D", [(2, 4, 4, 256, 64),
                                            (1, 8, 2, 256, 64),
                                            (2, 4, 1, 128, 32)])
    def test_causal(self, B, H, Hk, L, D):
        q = rand(10, (B, H, L, D))
        k = rand(11, (B, Hk, L, D))
        v = rand(12, (B, Hk, L, D))
        out_k = ops.attention(q, k, v, causal=True, impl="kernel")
        out_r = ref.mha_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        q = rand(13, (1, 4, 256, 64))
        k = rand(14, (1, 4, 256, 64))
        v = rand(15, (1, 4, 256, 64))
        out_k = ops.attention(q, k, v, causal=True, window=window,
                              impl="kernel")
        out_r = ref.mha_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        q = rand(16, (1, 2, 128, 64))
        k = rand(17, (1, 2, 128, 64))
        v = rand(18, (1, 2, 128, 64))
        out_k = ops.attention(q, k, v, softcap=50.0, impl="kernel")
        out_r = ref.mha_ref(q, k, v, softcap=50.0)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_suffix_queries(self):
        # Lq < Lk: queries are the LAST Lq positions
        q = rand(19, (2, 4, 128, 64))
        k = rand(20, (2, 4, 512, 64))
        v = rand(21, (2, 4, 512, 64))
        out_k = ops.attention(q, k, v, causal=True, impl="kernel")
        out_r = ref.mha_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)


def test_sparse_matmul_dispatch():
    x = rand(22, (64, 128))
    w = rand(23, (128, 128))
    assert ops.sparse_matmul(x, w).shape == (64, 128)
    wp, _ = pruning.n_m(w, 2, 4, group=128)
    pack = sparsity.pack_nm(wp, 2, 4, g=128)
    out = ops.sparse_matmul(x, pack, impl="ref")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x @ pack.densify()), rtol=2e-5)
