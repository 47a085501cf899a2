"""Paged-attention Pallas TPU kernel — decode against a paged KV cache.

The serving engine's paged KV cache (PR 3) stores K/V in a shared page
pool and keeps a per-slot page table; this kernel is the compute-side
twin: the grid walks ``(B, Hk, pages)`` and the *page table rides in as a
scalar-prefetch operand*, so the KV BlockSpec index map dereferences
``ptab[b, j]`` and the kernel only ever pulls the pages that belong to
sequence ``b`` — no gathered ``(B, max_pages*ps, ...)`` view is ever
materialized.  This is the same metadata-driven-skipping move as the
paper's functional units (a few bits of indirection metadata steer the
unit past work that doesn't matter), applied to cache reads instead of
weight blocks.

Per-sequence valid lengths (``lens``) mask rows inside the last page;
the decode query sits at position ``lens - 1``, so the length mask
subsumes causality.  Online softmax with running (max, denom, acc) VMEM
scratch, pages innermost (the accumulator carries across them).

``kernels/ref.py::paged_attention_ref`` is the semantic oracle (and the
CPU production path via ``dispatch.paged_attention``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


class PagedKV(NamedTuple):
    """A paged KV view: the operand bundle ``dispatch`` selects on.

    ``k/v (P, ps, Hk, D)`` page pools, ``ptab (B, max_pages) int32``
    per-sequence page tables, ``lens (B,) int32`` valid KV rows.
    """
    k: jax.Array
    v: jax.Array
    ptab: jax.Array
    lens: jax.Array

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    @property
    def max_pages(self) -> int:
        return self.ptab.shape[1]

    @property
    def head_dim(self) -> int:
        return self.k.shape[3]


def _make_kernel(ps: int, g: int, nq: int, n_pages: int, scale: float):
    def kernel(ptab_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref):
        b = pl.program_id(0)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qv = q_ref[0, 0].astype(jnp.float32)            # (nq*g, D)
        kv = k_ref[0].astype(jnp.float32)               # (ps, D)
        s = jax.lax.dot_general(
            qv, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (nq*g, ps)
        kpos = j * ps + jax.lax.broadcasted_iota(
            jnp.int32, (nq * g, ps), 1)
        # decode block: query row r (= qi*g + gi) sits at position
        # lens - nq + qi, so its causal reach is kpos < lens - (nq-1-qi)
        qi = jax.lax.broadcasted_iota(jnp.int32, (nq * g, ps), 0) // g
        valid = kpos < lens_ref[b] - (nq - 1) + qi
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(j == n_pages - 1)
        def _write():
            l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
            o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    ptab: jax.Array, lens: jax.Array, *,
                    scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """``q (B, H, D) × pools (P, ps, Hk, D) × ptab (B, np) → (B, H, D)``.

    One query per sequence (decode shape), or ``q (B, Q, H, D)`` — the
    *decode-shaped block* of the speculative verify step: Q queries per
    sequence at positions ``lens - Q .. lens - 1``, each with its own
    causal length mask (query rows fold next to the GQA head groups in
    the q/out blocks, so the grid stays page-shaped).  ``H`` a multiple
    of ``Hk`` (GQA — no materialized repeat).  The grid is
    ``(B, Hk, np)`` with one pool page per innermost step, fetched
    through the prefetched page table.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, nq, H, D = q.shape
    P, ps, Hk, Dk = k_pool.shape
    if D != Dk:
        raise ValueError(f"head_dim mismatch: q {D} vs pool {Dk}")
    if H % Hk:
        raise ValueError(f"H={H} not a multiple of Hk={Hk}")
    g = H // Hk
    n_pages = ptab.shape[1]
    s = scale if scale is not None else D ** -0.5

    # (B, nq, Hk, g, D) → (B, Hk, nq*g, D): query rows sit qi-major next
    # to the head group so one q block serves the whole (b, h) cell
    qf = q.reshape(B, nq, Hk, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hk, nq * g, D)
    # (head, page)-addressable pools: page ptab[b, j] of head h lives at
    # flat row h * P + ptab[b, j]
    kf = k_pool.transpose(2, 0, 1, 3).reshape(Hk * P, ps, D)
    vf = v_pool.transpose(2, 0, 1, 3).reshape(Hk * P, ps, D)

    def kv_map(b, h, j, ptab_ref, lens_ref):
        # grid indices first, scalar-prefetch refs last: dereference the
        # page table to fetch only the pages sequence b actually owns
        return (h * P + ptab_ref[b, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # ptab, lens
        grid=(B, Hk, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, nq * g, D),
                         lambda b, h, j, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, ps, D), kv_map),
            pl.BlockSpec((1, ps, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, nq * g, D),
                               lambda b, h, j, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq * g, 1), jnp.float32),    # running max
            pltpu.VMEM((nq * g, 1), jnp.float32),    # running denom
            pltpu.VMEM((nq * g, D), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        _make_kernel(ps, g, nq, n_pages, s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, nq * g, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
    )(ptab, lens, qf, kf, vf)
    out = out.reshape(B, Hk, nq, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, nq, H, D)
    return out[:, 0] if squeeze else out
