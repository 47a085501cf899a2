"""GQA attention with KV cache — every attention variant the assigned
architectures need, behind one init/apply pair.

Variants (config-driven): grouped-query (any H/Hk ratio incl. MQA), qk-norm
(qwen3), sliding windows (gemma local layers), logit soft-capping (gemma2),
M-RoPE (qwen2-vl), cross-attention (seamless decoder).  The inner product
uses the inline chunked-flash jnp path below (SPMD-partitionable, cache-
aware; ``kernels.dispatch.attention`` provides the Pallas flash kernel for
standalone prefill shapes); projections dispatch through ``apply_linear``
→ ``kernels.dispatch`` so the paper's sparse formats apply to q/k/v/o like
any other matmul.

KV cache layouts (per layer):
  * monolithic — ``{"k": (B, S, Hk, D), "v": (B, S, Hk, D)}``,
    sequence-major so decode updates are one ``dynamic_update_slice`` and
    the "kv_seq" axis can be sharded for long contexts (DESIGN.md §6).
  * paged — ``{"kp": (P, ps, Hk, D), "vp": (P, ps, Hk, D),
    "ptab": (B, max_pages) int32}``: a shared page *pool* plus a per-slot
    page table mapping logical page ``j`` of slot ``b`` (rows
    ``[j*ps, (j+1)*ps)``) to a pool page.  Page 0 is the reserved null
    page: unallocated table entries point at it, writes from dead slots
    land in it, and the kv-length mask keeps reads from ever attending to
    it.  This is the memory-side analogue of the paper's metadata-driven
    skipping — the page table is the few bits of indirection metadata
    that let cache memory and attention work scale with *actual* sequence
    lengths instead of the padded maximum.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_linear import DENSE, SparsityConfig, apply_linear, \
    init_linear
from repro.models import layers as L
from repro.models.config import ModelConfig

Array = jax.Array
Params = Dict[str, Any]


def init_attention(rng: Array, cfg: ModelConfig, d_in: Optional[int] = None,
                   dtype=jnp.bfloat16) -> Params:
    d = d_in if d_in is not None else cfg.d_model
    ks = jax.random.split(rng, 4)
    p = {
        "wq": init_linear(ks[0], d, cfg.q_dim, dtype),
        "wk": init_linear(ks[1], d, cfg.kv_dim, dtype),
        "wv": init_linear(ks[2], d, cfg.kv_dim, dtype),
        "wo": init_linear(ks[3], cfg.q_dim, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(cfg.head_dim)
        p["k_norm"] = L.init_rmsnorm(cfg.head_dim)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None, dtype=jnp.bfloat16) -> Params:
    """Stacked-over-layers cache (leading L axis matches the layer scan)."""
    nl = n_layers if n_layers is not None else cfg.n_layers
    shape = (nl, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_max_pages(max_len: int, page_size: int) -> int:
    """Logical pages per slot covering a ``max_len`` sequence."""
    return -(-max_len // page_size)


def init_paged_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                        page_size: int, num_pages: int = 0,
                        n_layers: Optional[int] = None,
                        dtype=jnp.bfloat16) -> Params:
    """Paged cache: shared page pool + per-slot page table.

    ``num_pages`` counts *allocatable* pages; one extra null page (pool
    index 0) is always added, so the pool leaf is ``num_pages + 1`` pages
    deep.  ``num_pages=0`` sizes the pool at full capacity
    (``batch * max_pages`` — no memory win, but bit-identical serving for
    parity tests).  The page table starts all-null.
    """
    nl = n_layers if n_layers is not None else cfg.n_layers
    mp = paged_max_pages(max_len, page_size)
    if num_pages <= 0:
        num_pages = batch * mp
    pool = (nl, num_pages + 1, page_size, cfg.n_kv_heads, cfg.head_dim)
    return {"kp": jnp.zeros(pool, dtype), "vp": jnp.zeros(pool, dtype),
            "ptab": jnp.zeros((nl, batch, mp), jnp.int32)}


def _project_qkv(params: Params, cfg: ModelConfig, x: Array,
                 kv_src: Optional[Array] = None,
                 sparsity: SparsityConfig = DENSE):
    """x (B, L, d) → q (B, L, H, D), k/v (B, Lk, Hk, D)."""
    B, Lq, _ = x.shape
    src = x if kv_src is None else kv_src
    Lk = src.shape[1]
    q = apply_linear(x, params["wq"], sparsity, name="wq")
    k = apply_linear(src, params["wk"], sparsity, name="wk")
    v = apply_linear(src, params["wv"], sparsity, name="wv")
    q = q.reshape(B, Lq, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, Lk, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Lk, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _mask(cfg: ModelConfig, qpos: Array, kpos: Array, *, causal: bool,
          window: Optional[int], is_local, kv_len) -> Array:
    """(..., Lq, Lk) bool reachability mask.  ``is_local`` may be a *traced*
    bool (scanned heterogeneous local/global stacks select the window mask
    at run time — both masks are elementwise-cheap).

    ``qpos`` is (Lq,) or (B, Lq) and ``kv_len`` None / scalar / (B,) —
    the batched forms carry per-slot decode positions (continuous
    batching), broadcasting a leading batch axis onto the mask.
    """
    q = qpos[..., :, None]                       # (..., Lq, 1)
    mask = jnp.ones(q.shape[:-1] + (kpos.shape[0],), bool)
    if causal:
        mask &= kpos <= q
    if window is not None:
        wmask = kpos > q - window
        if isinstance(is_local, bool):
            if is_local:
                mask &= wmask
        else:
            mask &= wmask | ~is_local
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        if kvl.ndim == 1:                        # per-slot valid lengths
            kvl = kvl[:, None, None]
        mask &= kpos < kvl
    return mask


def _expand_mask(mask: Array) -> Array:
    """Broadcast a (Lq, Lk) or (B, Lq, Lk) mask onto (B, Hk, g, Lq, Lk)."""
    if mask.ndim == 2:
        return mask[None, None, None]
    return mask[:, None, None]


def _sdpa(cfg: ModelConfig, q: Array, k: Array, v: Array, *,
          causal: bool, window: Optional[int], is_local=True,
          kv_len: Optional[Array] = None) -> Array:
    """(B, Lq, H, D) × (B, Lk, Hk, D) → (B, Lq, H, D).

    jnp path (XLA SPMD-partitionable; what the dry-run lowers).  ``kv_len``
    masks cache positions ≥ the valid length during decode.  For long keys
    the computation is chunked over Lk (flash-style online softmax in a
    ``lax.scan``) so the (Lq, Lk) logits are never materialized whole.
    """
    B, Lq, H, D = q.shape
    Lk, Hk = k.shape[1], k.shape[2]
    g = H // Hk
    qh = q.transpose(0, 2, 1, 3).reshape(B, Hk, g, Lq, D).astype(jnp.float32)
    kh = k.transpose(0, 2, 1, 3).astype(jnp.float32)   # (B, Hk, Lk, D)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        # abs position of queries; (Lq,) for scalar kv_len, (B, Lq) when
        # kv_len is per-slot (vector cache_pos decode)
        qpos = (kvl[:, None] if kvl.ndim == 1 else kvl) - Lq + jnp.arange(Lq)
    else:
        qpos = jnp.arange(Lq) + (Lk - Lq)
    scale = D ** -0.5

    chunk = _KV_CHUNK
    if Lk <= chunk or Lk % chunk:
        kpos = jnp.arange(Lk)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qh, kh) * scale
        if cfg.attn_softcap is not None:
            logits = jnp.tanh(logits / cfg.attn_softcap) * cfg.attn_softcap
        mask = _expand_mask(_mask(cfg, qpos, kpos, causal=causal,
                                  window=window, is_local=is_local,
                                  kv_len=kv_len))
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vh)
    else:
        # double-chunked flash: outer map over Q chunks, inner online-
        # softmax scan over KV chunks — peak logits buffer is
        # (B, Hk, g, qc, chunk) regardless of sequence length, which is
        # what lets the 32k-prefill and 500k-decode cells fit HBM.
        qc = Lq if (Lq <= _Q_CHUNK or Lq % _Q_CHUNK) else _Q_CHUNK
        nq = Lq // qc
        nc = Lk // chunk
        kc_ = kh.reshape(B, Hk, nc, chunk, D).transpose(2, 0, 1, 3, 4)
        vc_ = vh.reshape(B, Hk, nc, chunk, D).transpose(2, 0, 1, 3, 4)
        qcs = qh.reshape(B, Hk, g, nq, qc, D).transpose(3, 0, 1, 2, 4, 5)
        if qpos.ndim == 2:          # per-slot positions: (B, Lq) → (nq, B, qc)
            qpos_c = qpos.reshape(B, nq, qc).transpose(1, 0, 2)
        else:
            qpos_c = qpos.reshape(nq, qc)

        def q_block(args):
            qb, qp = args                       # (B,Hk,g,qc,D), (qc,)

            def step(carry, xs):
                m_run, l_run, acc = carry
                kb, vb, ci = xs
                kpos = ci * chunk + jnp.arange(chunk)
                s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb) * scale
                if cfg.attn_softcap is not None:
                    s = jnp.tanh(s / cfg.attn_softcap) * cfg.attn_softcap
                mask = _expand_mask(
                    _mask(cfg, qp, kpos, causal=causal, window=window,
                          is_local=is_local, kv_len=kv_len))
                s = jnp.where(mask, s, -1e30)
                m_new = jnp.maximum(m_run,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                p = jnp.where(mask, p, 0.0)
                alpha = jnp.exp(m_run - m_new)
                l_new = l_run * alpha + jnp.sum(p, -1, keepdims=True)
                acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)
                return (m_new, l_new, acc), None

            init = (jnp.full((B, Hk, g, qc, 1), -1e30, jnp.float32),
                    jnp.zeros((B, Hk, g, qc, 1), jnp.float32),
                    jnp.zeros((B, Hk, g, qc, D), jnp.float32))
            (m_run, l_run, acc), _ = jax.lax.scan(
                step, init, (kc_, vc_, jnp.arange(nc)))
            return acc / jnp.where(l_run == 0.0, 1.0, l_run)

        if nq == 1:
            out = q_block((qcs[0], qpos_c[0]))                  # (B,Hk,g,Lq,D)
        else:
            out = jax.lax.map(q_block, (qcs, qpos_c))           # (nq,B,Hk,g,qc,D)
            out = out.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hk, g, Lq, D)
    return out.reshape(B, H, Lq, D).transpose(0, 2, 1, 3).astype(q.dtype)


# chunk sizes for the lax.scan flash path; full-logit path below KV chunk.
_KV_CHUNK = 2048
_Q_CHUNK = 2048


def paged_write_read(cache: Params, k: Array, v: Array, pos: Array,
                     layer: Optional[Array] = None
                     ) -> Tuple[Params, Array, Array]:
    """Scatter ``k``/``v`` (B, Lq, Hk, D), written at per-slot positions
    ``pos`` (B,), into the pool pages the slots' page table names, then
    gather the table back as a (B, max_pages*ps, Hk, D) logical view.

    Row index == logical position, so the downstream mask/qpos math is
    the monolithic one; rows past kv_len read whatever the mapped page
    holds (null-page garbage included) and are masked exactly like
    monolithic garbage rows.  Prefix sharing may map ONE page into
    SEVERAL table rows: safe by the same math — gathers tolerate
    duplicate page ids, and each slot's scatter lands at its own
    positions (≥ its prompt rows), which always resolve to slot-private
    pages, so a shared page is only ever read.

    ``layer=None``: the pools are this layer's own (P, ps, Hk, D).  A
    traced ``layer`` index addresses stacked (nl, P, ps, Hk, D) pools —
    the layer scan carries them and this scatter updates them in place,
    so a step touches only its own rows and the viewed pages, never a
    whole layer's pool.  Returns ``(new_cache, k_view, v_view)``.
    """
    pt = cache["ptab"]                              # (B, max_pages)
    kp, vp = cache["kp"], cache["vp"]
    ps = kp.shape[-3]
    B, Lq, Hk, D = k.shape
    posn = pos[:, None] + jnp.arange(Lq)[None, :]   # (B, Lq)
    pages = jnp.take_along_axis(
        pt, jnp.clip(posn // ps, 0, pt.shape[1] - 1), axis=1)
    at = (pages, posn % ps) if layer is None else (layer, pages, posn % ps)
    kp = kp.at[at].set(k.astype(kp.dtype))
    vp = vp.at[at].set(v.astype(vp.dtype))
    view = pt if layer is None else (layer, pt)
    return ({"kp": kp, "vp": vp, "ptab": pt},
            kp[view].reshape(B, -1, Hk, D), vp[view].reshape(B, -1, Hk, D))


def attention(params: Params, cfg: ModelConfig, x: Array, positions: Array,
              *, is_local=False,
              cache: Optional[Params] = None,
              cache_pos: Optional[Array] = None,
              cross_src: Optional[Array] = None,
              causal: Optional[bool] = None,
              sparsity: SparsityConfig = DENSE,
              layer: Optional[Array] = None
              ) -> Tuple[Array, Optional[Params]]:
    """Full attention layer: project → rope → (cache update) → sdpa → out.

    Modes:
      * prefill / training: ``cache=None`` → self-attention over ``x``.
      * decode: ``cache`` holds (B, S, Hk, D) k/v for THIS layer and
        ``cache_pos`` (scalar) the write position; returns updated cache.
        A paged layer cache (``{"kp", "vp", "ptab"}``, see module
        docstring) is detected by its ``ptab`` key and routed through the
        page-table scatter/gather instead (:func:`paged_write_read`);
        with a traced ``layer`` its pools are the stacked ones and the
        returned cache holds them updated in place.
      * cross-attention: ``cross_src`` is the encoder output (no rope on kv,
        no causal mask).
      * ``causal=False`` with ``cross_src=None``: bidirectional
        self-attention (encoder stacks).

    ``is_local`` may be a traced bool (scanned local/global stacks).
    """
    window = cfg.window_size
    if causal is None:
        causal = cross_src is None
    q, k, v = _project_qkv(params, cfg, x, cross_src, sparsity)

    if cross_src is None:
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        kv_pos = positions
        k = L.apply_rope(k, kv_pos, cfg.rope_theta, cfg.mrope_sections)

    # Attention head/sequence layout (TP mode): head-parallel flash when
    # the KV heads divide the model axis (zero intra-attention
    # collectives); for MQA (Hk ≤ 2, e.g. gemma3) replicate the — tiny by
    # design — KV and sequence-shard the queries.  Without this, GSPMD
    # partitions the QK^T contraction over head_dim and emits per-chunk
    # partial-sum all-reduces (measured 200 GB/step on gemma3
    # prefill_32k, §Perf cell C).
    from repro.distributed.annotate import (MODEL, axis_size, batch_axes,
                                            constrain, seq_axis)
    ext = axis_size(MODEL)
    if seq_axis() is not None and ext > 1:
        if cfg.n_kv_heads % ext == 0:
            q = constrain(q, batch_axes(), None, MODEL, None)
            k = constrain(k, batch_axes(), None, MODEL, None)
            v = constrain(v, batch_axes(), None, MODEL, None)
        elif cfg.n_kv_heads <= 2:
            q = constrain(q, batch_axes(), MODEL, None, None)
            k = constrain(k, batch_axes(), None, None, None)
            v = constrain(v, batch_axes(), None, None, None)

    new_cache = None
    kv_len = None
    if cache is not None and "ptab" in cache:
        cp = jnp.asarray(cache_pos)
        cpb = cp if cp.ndim == 1 else jnp.broadcast_to(cp, (x.shape[0],))
        with jax.named_scope("kv_cache"):
            new_cache, k, v = paged_write_read(cache, k, v, cpb, layer)
        Hk = k.shape[-2]
        if ext > 1 and Hk % ext == 0:
            # head-parallel pool (cache_specs "heads"): keep the gathered
            # view sharded on its head axis so the page gather stays
            # shard-local and attention runs collective-free per head
            k = constrain(k, batch_axes(), None, MODEL, None)
            v = constrain(v, batch_axes(), None, MODEL, None)
        kv_len = cpb + x.shape[1]
    elif cache is not None:
        # write the new k/v at cache_pos, attend over the whole cache.
        # cache_pos may be a scalar (shared write offset: prefill, wave
        # decode) or a (B,) vector of per-slot positions (continuous
        # batching: each slot advances independently).
        cp = jnp.asarray(cache_pos)
        if cp.ndim == 1:
            def _upd(c, n, p):          # (S, Hk, D), (Lq, Hk, D), ()
                return jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
            ck = jax.vmap(_upd)(cache["k"], k.astype(cache["k"].dtype), cp)
            cv = jax.vmap(_upd)(cache["v"], v.astype(cache["v"].dtype), cp)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        kv_len = cp + x.shape[1]

    out = _sdpa(cfg, q, k, v, causal=causal, window=window,
                is_local=is_local, kv_len=kv_len)
    B, Lq = x.shape[0], x.shape[1]
    out = out.reshape(B, Lq, cfg.q_dim)
    out = apply_linear(out, params["wo"], sparsity, name="wo")
    return out, new_cache
