"""Faithful lookahead-encoded matmul: in-kernel LSB decode (Algorithm 2⁻¹).

This kernel keeps the paper's headline property intact on TPU: the sparsity
metadata costs *zero extra bytes* because it rides in the LSBs of the INT7
weights (``LookaheadPack``).  The encoded int8 tile is DMA'd HBM→VMEM and
decoded on the VPU with the exact bit manipulation the FPGA does in LUTs —
isolate sign, shift the magnitude down, sign-extend 7 bits — then fed to
the MXU after per-column dequantization.

This is the (a)-variant of DESIGN.md §2 row 2: faithful, storage-optimal,
but *not* compute-skipping (the static grid touches every tile).  The
(b)-variant — ``bsr_matmul`` driven by ``LookaheadPack.to_block_sparse`` —
trades a small SMEM index list for tile skipping.  Benchmarks compare both,
which is precisely the paper's FPGA-vs-TPU design-point discussion
(bench_resources).

Grid: ``(M/bm, N/bn, K/bk)`` — a standard tiled matmul; the decode is fused
into the contraction so encoded weights never exist in decoded form in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import LookaheadPack


def _decode_int7(enc_i32: jax.Array) -> jax.Array:
    """[sign, b5..b0, skip] byte -> int7 value, in int32 lanes (VPU ops)."""
    e = enc_i32 & 0xFF
    sign = (e >> 7) & 0x1
    u = ((e >> 1) & 0x3F) | (sign << 6)
    return jnp.where(u >= 64, u - 128, u)


def _kernel(x_ref, e_ref, s_ref, o_ref, acc_ref):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_int7(e_ref[...].astype(jnp.int32)).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.float32), w,
                                preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _write():
        # per-output-column dequant scale applied once at the end
        o_ref[...] = (acc_ref[...] * s_ref[0, :][None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "interpret"))
def lookahead_matmul(x: jax.Array, pack: LookaheadPack, *, bm: int = 128,
                     bk: int = 128, bn: int = 128,
                     interpret: bool = False) -> jax.Array:
    """``x (M, K) @ decode(pack) (K, N) -> (M, N)`` with fused LSB decode."""
    M, K = x.shape
    if K != pack.K:
        raise ValueError(f"x K={K} != pack K={pack.K}")
    if M % bm or K % bk or pack.N % bn:
        raise ValueError(f"(M={M}, K={K}, N={pack.N}) not divisible by "
                         f"(bm={bm}, bk={bk}, bn={bn})")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(M // bm, pack.N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
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
    )(x, pack.enc, pack.scale)
