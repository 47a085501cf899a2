"""USSA analogue: N:M compressed-K matmul as a Pallas TPU kernel.

Paper mapping (DESIGN.md §2): the FPGA's variable-cycle MAC makes compute
proportional to non-zero weights by skipping zero multiplies *in time*.  A
systolic array has no per-element early-out, so the TPU-idiomatic way to
make compute ∝ nnz is to *shrink the contraction dimension*: keep ``n`` of
every ``m`` weights along K (positions shared across a ``g``-wide column
group so they are uniform inside a tile), store the kept values densely
``(Kc = K·n/m, N)`` plus small position metadata, and have the kernel
select the matching activation columns before a dense
``(bm, bkc) @ (bkc, g)`` MXU matmul.  Weight bytes read from HBM drop to
``n/m`` of dense — the same "only as many multiplications as non-zero
weights" property, expressed spatially instead of temporally.

Grid: ``(M/bm, N/bn, Kc/bkc)``, reduction innermost; ``bn`` is a multiple
of the group width ``g`` and each step serves ``bn/g`` groups.

  * ``x``    (M, K)  block (bm, bk_src) with ``bk_src = bkc·m/n`` — the
             source K-span covering compressed tile ``t``; index (i, t).
  * ``vals`` (Kc, N) block (bkc, bn), index (t, j).
  * ``idx``  (N/g, Kc) int32 block (bn/g, bkc), index (j, t) — position of
             each kept weight within its m-group (the USSA "case signal",
             precomputed offline instead of by comparators), one group per
             row so a group's positions lie along lanes.

The alignment multiplexer of the paper's Fig. 7 datapath is a one-hot
selection matrix ``sel[s, r] = (s == src[r])`` built on the VPU from the
metadata, with ``src[r] = (r // n)·m + idx[r]``; ``x_tile @ sel`` on the
MXU gathers the kept activation columns exactly (each output is one input
times 1.0).  Mosaic has no lane gather, and the selection costs
``bk_src/g`` of the main matmul's FLOPs while keeping every block
(8, 128)-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import NMPack


def mxu_dtype(*dtypes):
    """Operand dtype and precision for the in-kernel matmuls: bf16 stays
    bf16 (one MXU pass, exact products, f32 accumulation); anything wider
    runs f32 at HIGHEST so the one-hot selection never rounds to bf16."""
    dt = jnp.result_type(*dtypes)
    if dt == jnp.bfloat16:
        return dt, None
    return jnp.float32, jax.lax.Precision.HIGHEST


def select_columns(x, src, span: int, precision):
    """``x[:, src]`` for ``src (1, w)`` local column ids in ``[0, span)``,
    as ``x @ one_hot`` — exact, and lowers on Mosaic where a lane gather
    does not."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (span, src.shape[1]), 0)
    sel = (rows == src).astype(x.dtype)
    return jnp.dot(x, sel, precision=precision,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _make_kernel(n: int, m: int, bkc: int, g: int, groups: int):
    bk_src = bkc * m // n

    def kernel(x_ref, v_ref, i_ref, o_ref, acc_ref):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dt, prec = mxu_dtype(x_ref.dtype, v_ref.dtype)
        x = x_ref[...].astype(dt)
        # local source row of compressed lane r: (r // n) * m + idx[r]
        base = (jax.lax.broadcasted_iota(jnp.int32, (1, bkc), 1) // n) * m
        for q in range(groups):                 # static: bn // g groups
            cols = slice(q * g, (q + 1) * g)
            xg = select_columns(x, base + i_ref[q:q + 1, :], bk_src, prec)
            acc_ref[:, cols] += jnp.dot(
                xg, v_ref[:, cols].astype(dt), precision=prec,
                preferred_element_type=jnp.float32)

        @pl.when(t == pl.num_programs(2) - 1)
        def _write():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return kernel


def default_bn(pack: NMPack, bkc: int, budget: int = 2 << 20) -> int:
    """Widest column tile the metadata layout allows whose values block
    fits ``budget`` bytes: the full N, else a multiple of ``8·g`` (the
    metadata block's sublane rule) dividing N, else one group."""
    item = jnp.dtype(pack.values.dtype).itemsize
    if bkc * pack.N * item <= budget:
        return pack.N
    step = 8 * pack.g
    fits = [bn for bn in range(step, pack.N, step)
            if pack.N % bn == 0 and bkc * bn * item <= budget]
    return max(fits) if fits else pack.g


@functools.partial(jax.jit, static_argnames=("bm", "bkc", "bn", "out_dtype",
                                             "interpret"))
def nm_spmm(x: jax.Array, pack: NMPack, *, bm: int = 128, bkc: int = 128,
            bn: int | None = None, out_dtype=None,
            interpret: bool = False) -> jax.Array:
    """``x (M, K) @ pack (K, N) -> (M, N)`` with K compressed by n/m;
    the result is ``out_dtype`` (default ``x.dtype``)."""
    M, K = x.shape
    if K != pack.K:
        raise ValueError(f"x K={K} != pack K={pack.K}")
    n, m, g = pack.n, pack.m, pack.g
    Kc = pack.Kc
    bn = bn or default_bn(pack, bkc)
    if M % bm or Kc % bkc or pack.N % bn or bn % g:
        raise ValueError(f"shapes (M={M}, Kc={Kc}, N={pack.N}) not divisible "
                         f"by tiles (bm={bm}, bkc={bkc}, bn={bn}, g={g})")
    if bkc % n:
        raise ValueError(f"bkc={bkc} must be a multiple of n={n}")
    bk_src = bkc * m // n
    groups = bn // g

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(M // bm, pack.N // bn, Kc // bkc),
        in_specs=[
            pl.BlockSpec((bm, bk_src), lambda i, j, t: (i, t)),
            pl.BlockSpec((bkc, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((groups, bkc), lambda i, j, t: (j, t)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _make_kernel(n, m, bkc, g, groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, pack.N), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        name="nm_spmm",
        interpret=interpret,
    )(x, pack.values, pack.idx)
