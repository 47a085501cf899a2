"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel sibling is tested
against (``tests/test_kernels_*`` sweep shapes/dtypes and assert_allclose).
They are also the *CPU execution path* of ``core.sparse_linear`` — the
multi-pod dry-run lowers these (they carry the same compressed FLOP/byte
structure as the kernels, so roofline terms reflect the paper's technique
without needing a TPU to compile Pallas).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import encoding
from repro.core.sparsity import (BlockSparsePack, CombinedPack, LookaheadPack,
                                 NMPack)

Array = jax.Array


def dense_matmul_ref(x: Array, w: Array) -> Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# SSSA analogue — block-skip matmul
# ---------------------------------------------------------------------------

def bsr_matmul_ref(x: Array, pack: BlockSparsePack) -> Array:
    """``x (M, K) @ densify(pack) (K, N)`` computed over packed tiles only.

    Gathers the x K-tiles named by ``pack.indices`` and contracts them with
    the packed values — the same arithmetic the Pallas grid performs, so
    compute/bytes scale with non-zero tiles (padding slots are masked).
    """
    M, K = x.shape
    bk, bn, = pack.bk, pack.bn
    Nb, max_nnz = pack.indices.shape
    xt = x.reshape(M, K // bk, bk)
    # (Nb, max_nnz, M, bk): x tiles addressed by the per-strip index lists
    xg = xt[:, pack.indices, :].transpose(1, 2, 0, 3)
    valid = (jnp.arange(max_nnz)[None, :] < pack.counts[:, None])
    vals = jnp.where(valid[:, :, None, None], pack.values, 0)
    # contract per strip: sum_t (M, bk) @ (bk, bn) -> (Nb, M, bn)
    out = jnp.einsum("jtmk,jtkn->jmn", xg.astype(jnp.float32),
                     vals.astype(jnp.float32))
    return out.transpose(1, 0, 2).reshape(M, pack.N).astype(x.dtype)


# ---------------------------------------------------------------------------
# USSA analogue — N:M compressed-K matmul
# ---------------------------------------------------------------------------

def nm_spmm_ref(x: Array, pack: NMPack) -> Array:
    """``x (M, K) @ densify(pack)`` via activation gather + short-K matmul.

    For each column group the kept source rows of x are gathered
    (``(M, Kc)``) and contracted with the compressed values — K shrinks by
    ``n/m`` exactly as in the kernel.
    """
    M, K = x.shape
    Ng, g = pack.N // pack.g, pack.g
    src = pack.src_rows()                              # (Ng, Kc)
    xg = x[:, src]                                     # (M, Ng, Kc)
    vals = pack.values.reshape(pack.Kc, Ng, g)
    out = jnp.einsum("mjk,kjg->mjg", xg.astype(jnp.float32),
                     vals.astype(jnp.float32))
    return out.reshape(M, pack.N).astype(x.dtype)


# ---------------------------------------------------------------------------
# CSA analogue — block-skip × N:M
# ---------------------------------------------------------------------------

def csa_matmul_ref(x: Array, pack: CombinedPack) -> Array:
    M, K = x.shape
    bk, bn, bkc = pack.bk, pack.bn, pack.bkc
    Nb, max_nnz = pack.indices.shape
    xt = x.reshape(M, K // bk, bk)
    xg = xt[:, pack.indices, :]                        # (M, Nb, max_nnz, bk)
    # gather the n:m-kept rows inside each tile: gidx (Nb, max_nnz, bkc)
    xs = jnp.take_along_axis(
        xg, pack.gidx[None, :, :, :], axis=3
    )                                                  # (M, Nb, max_nnz, bkc)
    valid = (jnp.arange(max_nnz)[None, :] < pack.counts[:, None])
    vals = jnp.where(valid[:, :, None, None], pack.values, 0)
    out = jnp.einsum("mjtk,jtkn->mjn", xs.astype(jnp.float32),
                     vals.astype(jnp.float32))
    return out.reshape(M, pack.N).astype(x.dtype)


# ---------------------------------------------------------------------------
# Faithful lookahead-encoded matmul (decode in the consumer)
# ---------------------------------------------------------------------------

def lookahead_matmul_ref(x: Array, pack: LookaheadPack) -> Array:
    """Decode INT7 values + per-column scales, then matmul.

    Oracle for ``kernels/lookahead_decode.py`` which performs the identical
    bit manipulation on VPU registers inside the Pallas kernel.
    """
    vals = encoding.decode_values(pack.enc).astype(jnp.float32)
    w = vals * pack.scale
    return jnp.dot(x.astype(jnp.float32), w).astype(x.dtype)


# ---------------------------------------------------------------------------
# Paged-attention oracle (for kernels/paged_attention.py)
# ---------------------------------------------------------------------------

def paged_attention_ref(q: Array, k_pool: Array, v_pool: Array,
                        ptab: Array, lens: Array,
                        scale: float | None = None) -> Array:
    """Decode attention against a paged KV cache.

    ``q`` — ``(B, H, D)``: one query per sequence (the token being
    decoded), or ``(B, Q, H, D)``: a *decode-shaped block* of Q queries
    (the speculative-verify posture — query ``i`` sits at position
    ``lens - Q + i``, so each query gets its own causal length mask);
    ``k_pool/v_pool (P, ps, Hk, D)`` — the shared page pools;
    ``ptab (B, max_pages) int32`` — logical page ``j`` of sequence ``b``
    lives in pool page ``ptab[b, j]``;
    ``lens (B,) int32`` — valid KV rows per sequence *including* the
    block (the last query sits at position ``lens - 1``).

    Gathers each sequence's pages into a ``(max_pages*ps)`` logical view
    and runs masked softmax attention — the semantic ground truth the
    Pallas kernel (which never materializes the gather) is tested
    against, and the CPU production path.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]                               # (B, 1, H, D)
    B, Q, H, D = q.shape
    ps, Hk = k_pool.shape[1], k_pool.shape[2]
    k = k_pool[ptab]                                 # (B, np, ps, Hk, D)
    v = v_pool[ptab]
    L = k.shape[1] * ps
    k = k.reshape(B, L, Hk, D).transpose(0, 2, 1, 3)  # (B, Hk, L, D)
    v = v.reshape(B, L, Hk, D).transpose(0, 2, 1, 3)
    if H != Hk:
        k = jnp.repeat(k, H // Hk, axis=1)
        v = jnp.repeat(v, H // Hk, axis=1)
    s = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bqhd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    # per-query valid length: query i may attend rows < lens - (Q-1-i)
    qlens = lens[:, None] - (Q - 1 - jnp.arange(Q))[None, :]   # (B, Q)
    mask = jnp.arange(L)[None, None, :] < qlens[:, :, None]    # (B, Q, L)
    mask = mask[:, None]                                       # (B, 1, Q, L)
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (dead slots, lens == 0): emit zeros, not NaN
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bqhd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Attention oracle (for kernels/flash_attention.py)
# ---------------------------------------------------------------------------

def mha_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
            window: int | None = None, softcap: float | None = None,
            scale: float | None = None) -> Array:
    """(B, H, Lq, D), (B, H, Lk, D), (B, H, Lk, D) -> (B, H, Lq, D).

    Supports causal masking, sliding windows (gemma-style local attention),
    logit soft-capping (gemma2) and GQA (H a multiple of Hk; kv heads are
    repeated).  Assumes Lq queries are the *last* Lq positions of the Lk
    keys (prefill: Lq == Lk; decode: Lq == 1).
    """
    *_, Lq, D = q.shape
    Lk = k.shape[-2]
    H, Hk = q.shape[1], k.shape[1]
    if H != Hk:
        if H % Hk:
            raise ValueError(f"H={H} not a multiple of Hk={Hk}")
        k = jnp.repeat(k, H // Hk, axis=1)
        v = jnp.repeat(v, H // Hk, axis=1)
    s = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * s
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(Lq) + (Lk - Lq)
    kpos = jnp.arange(Lk)
    mask = jnp.ones((Lq, Lk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
