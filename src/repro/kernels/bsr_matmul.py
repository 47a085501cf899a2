"""SSSA analogue: block-skip matmul as a Pallas TPU kernel.

Paper mapping (DESIGN.md §2): the FPGA design's ``sssa_inc_indvar`` reads a
lookahead counter embedded in the weights and bumps the inner-loop induction
variable past runs of all-zero blocks.  On a TPU the "induction variable" is
the grid index and the "blocks" are MXU-aligned (bk, bn) VMEM tiles, so the
skip becomes a *data-dependent grid*: per N-strip we scalar-prefetch the
list of non-zero K-tile indices (built offline from the same lookahead
metadata — ``LookaheadPack.to_block_sparse`` / ``pack_block_sparse``) and
the grid's reduction dimension runs only ``max_nnz`` steps instead of
``K/bk``.  Zero tiles are never fetched from HBM and never hit the MXU:
compute *and* memory scale with density, which is the paper's speedup
mechanism translated to the systolic world.

Grid: ``(M/bm, N/bn, max_nnz)`` with the reduction dim innermost
(ARBITRARY semantics — it carries the accumulator).

  * ``x``    (M, K)  block (bm, bk), index ``(i, indices[j, t])`` — the
             scalar-prefetched block list plays ``sssa_inc_indvar``.
  * ``vals`` (Nb, max_nnz, bk, bn) block (1, 1, bk, bn), index (j, t).
  * ``out``  (M, N)  block (bm, bn), f32 accumulator in VMEM scratch.

Padding slots (``t >= counts[j]``) are skipped with ``pl.when`` — they cost
a grid step but no FLOPs; strips are padded to the max strip density so the
waste is bounded by strip-density skew (measured in bench_resources).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import BlockSparsePack


def _kernel(idx_ref, cnt_ref, x_ref, v_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < cnt_ref[j])
    def _mac():
        x = x_ref[...].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot(x, v,
                                    preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def bsr_matmul(x: jax.Array, pack: BlockSparsePack, *, bm: int = 128,
               interpret: bool = False) -> jax.Array:
    """``x (M, K) @ pack (K, N) -> (M, N)``, skipping all-zero K-tiles."""
    M, K = x.shape
    if K != pack.K:
        raise ValueError(f"x K={K} != pack K={pack.K}")
    if M % bm:
        raise ValueError(f"M={M} must be a multiple of bm={bm}")
    bk, bn = pack.bk, pack.bn
    Nb, max_nnz = pack.indices.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // bm, Nb, max_nnz),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, t, idx, cnt: (i, idx[j, t])),
            pl.BlockSpec((1, 1, bk, bn),
                         lambda i, j, t, idx, cnt: (j, t, 0, 0)),
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
        interpret=interpret,
    )(pack.indices, pack.counts, x, pack.values)
