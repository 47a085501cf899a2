"""CSA analogue: combined block-skip × N:M matmul as a Pallas TPU kernel.

Paper mapping (Section III-D): the CSA pairs ``csa_inc_indvar`` (lookahead
block skipping = our scalar-prefetched non-zero tile list) with
``csa_vcmac`` (variable-cycle MAC = our compressed-K inner tile).  The two
reductions compose multiplicatively: work ∝ (1 - x_block) · n/m of dense —
the paper's "dual-pruning capability ... allows the model to simultaneously
leverage each pruning method's distinct degrees of freedom".

Grid: ``(M/bm, N/bn, max_nnz)``; only the surviving K-tiles appear, and
each surviving tile is already n:m-compressed to ``bkc = bk·n/m`` rows.

  * ``x``    (M, K)  block (bm, bk), index ``(i, indices[j, t])`` —
             lookahead skip (HBM traffic ∝ surviving tiles).
  * ``vals`` (Nb, max_nnz, bkc, bn) block (1, 1, bkc, bn).
  * ``gidx`` (Nb, max_nnz, bkc) int32 block (1, max_nnz, bkc), index
             (j, 0, 0) — the strip's per-tile gather rows (VPU align
             stage), shared across its bn columns and fetched once per
             strip; step ``t`` reads row ``t``.

The gather is the one-hot selection matmul of :mod:`nm_spmm`
(``select_columns``): Mosaic lowers no lane gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import CombinedPack
from repro.kernels.nm_spmm import mxu_dtype, select_columns


def _kernel(idx_ref, cnt_ref, x_ref, v_ref, g_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < cnt_ref[j])
    def _mac():
        dt, prec = mxu_dtype(x_ref.dtype, v_ref.dtype)
        src = g_ref[0, pl.ds(t, 1), :]                  # (1, bkc)
        xg = select_columns(x_ref[...].astype(dt), src, x_ref.shape[1],
                            prec)                        # (bm, bkc)
        acc_ref[...] += jnp.dot(xg, v_ref[0, 0].astype(dt), precision=prec,
                                preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def csa_matmul(x: jax.Array, pack: CombinedPack, *, bm: int = 128,
               interpret: bool = False) -> jax.Array:
    """``x (M, K) @ pack (K, N) -> (M, N)``; block-skip × n:m compression."""
    M, K = x.shape
    if K != pack.K:
        raise ValueError(f"x K={K} != pack K={pack.K}")
    if M % bm:
        raise ValueError(f"M={M} not a multiple of bm={bm}")
    bk, bn, bkc = pack.bk, pack.bn, pack.bkc
    Nb, max_nnz = pack.indices.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // bm, Nb, max_nnz),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, t, idx, cnt: (i, idx[j, t])),
            pl.BlockSpec((1, 1, bkc, bn),
                         lambda i, j, t, idx, cnt: (j, t, 0, 0)),
            pl.BlockSpec((1, max_nnz, bkc),
                         lambda i, j, t, idx, cnt: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, idx, cnt: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, pack.N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        name="csa_matmul",
        interpret=interpret,
    )(pack.indices, pack.counts, x, pack.values, pack.gidx)
