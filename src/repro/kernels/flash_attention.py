"""Fused attention (flash-style) Pallas TPU kernel.

Not a paper contribution — the perf-critical compute layer of the LM
framework the paper's technique is integrated into.  Supports the features
the assigned architectures need: causal masking, sliding windows
(gemma2/gemma3 local layers), logit soft-capping (gemma2), GQA (kv-head
groups folded into the index map, no materialized repeat), and
prefix-decode (Lq queries attending to the last Lq of Lk keys).

Online-softmax over KV blocks with running (max, denom, acc) VMEM scratch;
fully-masked KV blocks are skipped via ``pl.when`` on block-level bounds —
for causal or windowed layers the skipped fraction approaches 1/2 resp.
(1 - window/L), which is the attention-side mirror of the paper's
"skip whole zero blocks" principle (here the zeros are mask-structural
rather than weight-structural).

Grid: ``(B·H, Lq/bq, Lk/bk)`` — KV innermost (carries the accumulator).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _make_kernel(bq: int, bk: int, q_off: int, scale: float,
                 causal: bool, window: int | None, softcap: float | None):
    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        iq = pl.program_id(1)
        ik = pl.program_id(2)

        @pl.when(ik == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qpos = iq * bq + jax.lax.iota(jnp.int32, bq) + q_off   # abs positions
        kpos = ik * bk + jax.lax.iota(jnp.int32, bk)

        # block-level reachability: skip fully-masked KV blocks
        lo = ik * bk                       # first kpos in block
        hi = ik * bk + bk - 1              # last kpos in block
        q_lo = iq * bq + q_off
        q_hi = iq * bq + bq - 1 + q_off
        reach = jnp.bool_(True)
        if causal:
            reach &= lo <= q_hi            # some key not in the future
        if window is not None:
            reach &= hi > q_lo - window    # some key inside the window

        @pl.when(reach)
        def _block():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (bq, bk)
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
                p, v_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(ik == pl.num_programs(2) - 1)
        def _write():
            l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None,
                    scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """``q (B, H, Lq, D), k/v (B, Hk, Lk, D) -> (B, H, Lq, D)``.

    ``H`` must be a multiple of ``Hk`` (GQA); queries are the last ``Lq``
    positions of the key sequence.
    """
    B, H, Lq, D = q.shape
    _, Hk, Lk, _ = k.shape
    if H % Hk:
        raise ValueError(f"H={H} not a multiple of Hk={Hk}")
    group = H // Hk
    bq = min(bq, Lq)
    bk = min(bk, Lk)
    if Lq % bq or Lk % bk:
        raise ValueError(f"Lq={Lq}, Lk={Lk} not divisible by ({bq}, {bk})")
    s = scale if scale is not None else D ** -0.5

    qf = q.reshape(B * H, Lq, D)
    kf = k.reshape(B * Hk, Lk, D)
    vf = v.reshape(B * Hk, Lk, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B * H, Lq // bq, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, D), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        _make_kernel(bq, bk, Lk - Lq, s, causal, window, softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Lq, D)
