"""Decoder-only LM assembly: one scan-over-layers body for every assigned
dense / MoE / VLM architecture (qwen3, gemma2/3, stablelm, qwen2-moe, dbrx,
qwen2-vl; zamba2/mamba2 live in hybrid.py/ssm assembly, seamless in
encdec.py).

Parameters are *stacked over layers* (every leaf gains a leading
``n_layers`` axis) so the layer loop is a single ``lax.scan`` — compile
time stays O(1) in depth, which keeps the 80-layer dry-run cells fast.
Heterogeneous local/global attention (gemma 5:1) is a per-layer scanned
int flag selecting the window mask at run time; MoE-vs-dense MLP is
homogeneous per arch and resolved statically.

The one entry point is :func:`lm_apply`:

  * training / no-cache forward:  ``lm_apply(p, cfg, tokens)`` → logits
  * prefill: pass a fresh ``init_kv_cache`` and ``cache_pos=0``
  * decode:  pass the running cache and the current position

Sparsity (the paper's technique) applies per layer family through
``cfg.attn_sparsity`` / ``cfg.mlp_sparsity`` / ``cfg.expert_sparsity`` —
projections dispatch through ``apply_linear`` which routes packed weights
to the sparse kernels.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models.attention import attention, init_attention
from repro.models.config import LayerKind, ModelConfig

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_layer(rng: Array, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    p: Params = {
        "ln_attn": L.init_rmsnorm(cfg.d_model),
        "ln_mlp": L.init_rmsnorm(cfg.d_model),
        "attn": init_attention(ks[0], cfg, dtype=dtype),
    }
    if cfg.post_norm:
        p["ln_attn_post"] = L.init_rmsnorm(cfg.d_model)
        p["ln_mlp_post"] = L.init_rmsnorm(cfg.d_model)
    if cfg.n_experts:
        p["moe"] = M.init_moe(ks[1], cfg, dtype=dtype)
    else:
        p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff,
                              gated=cfg.mlp_gated, dtype=dtype)
    return p


def init_lm(rng: Array, cfg: ModelConfig) -> Params:
    """Stacked-layer LM params (embed / layers / final norm)."""
    dtype = L._dtype(cfg.dtype)
    k_embed, k_layers, k_unembed = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys)
    p: Params = {
        "embed": L.init_embedding(k_embed, cfg.vocab_padded, cfg.d_model,
                                  dtype),
        "layers": layers,
        "ln_final": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = L.init_embedding(k_unembed, cfg.vocab_padded,
                                        cfg.d_model, dtype)
    return p


# ---------------------------------------------------------------------------
# One transformer block (the scanned body)
# ---------------------------------------------------------------------------

def block(p: Params, cfg: ModelConfig, x: Array, positions: Array,
          kind: Array, cache: Optional[Params], cache_pos,
          layer: Optional[Array] = None
          ) -> Tuple[Array, Optional[Params], Array]:
    """Pre-norm block: x + attn(norm(x)); x + mlp(norm(x)).

    ``kind`` is a traced int32 (LayerKind); returns (x, new_cache, aux).
    ``layer`` (traced) indexes stacked paged pools in ``cache``.

    The residual stream is sequence-sharded over the model axis
    (Megatron-SP): the per-layer remat stack shrinks |model|×, and the
    TP boundary collectives become bf16 all-gather / reduce-scatter
    pairs instead of f32 all-reduces.  ``constrain`` is a no-op off-mesh
    and on shapes that don't divide (decode's Lq=1).
    """
    from repro.distributed.annotate import batch_axes, constrain, seq_axis
    x = constrain(x, batch_axes(), seq_axis(), None)
    is_local = kind == int(LayerKind.ATTN_LOCAL)
    with jax.named_scope("attention"):
        h = L.rmsnorm(p["ln_attn"], x, cfg.norm_eps)
        attn_out, new_cache = attention(
            p["attn"], cfg, h, positions, is_local=is_local,
            cache=cache, cache_pos=cache_pos, sparsity=cfg.attn_sparsity,
            layer=layer)
        # constrain sub-block outputs back to the SP layout while still
        # bf16: the row-parallel partial sums then reduce-scatter in bf16
        # instead of all-reducing the f32 upcast the residual add would
        # otherwise hoist
        attn_out = constrain(attn_out, batch_axes(), seq_axis(), None)
        if cfg.post_norm:
            attn_out = L.rmsnorm(p["ln_attn_post"], attn_out, cfg.norm_eps)
    x = x + attn_out

    with jax.named_scope("mlp"):
        h = L.rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
        if cfg.n_experts:
            if cfg.moe_impl == "grouped":
                mlp_out, aux = M.moe_grouped(
                    p["moe"], cfg, h, sparsity=cfg.expert_sparsity,
                    capacity_factor=cfg.capacity_factor,
                    group_size=cfg.moe_group)
            elif cfg.moe_impl == "sorted":
                mlp_out, aux = M.moe_sorted(
                    p["moe"], cfg, h, sparsity=cfg.expert_sparsity,
                    capacity_factor=cfg.capacity_factor,
                    group_size=cfg.moe_group)
            else:
                mlp_out, aux = M.moe(p["moe"], cfg, h,
                                     sparsity=cfg.expert_sparsity)
        else:
            mlp_out = L.mlp(p["mlp"], h, gated=cfg.mlp_gated,
                            sparsity=cfg.mlp_sparsity)
            aux = jnp.zeros((), jnp.float32)
        mlp_out = constrain(mlp_out, batch_axes(), seq_axis(), None)
        if cfg.post_norm:
            mlp_out = L.rmsnorm(p["ln_mlp_post"], mlp_out, cfg.norm_eps)
    return x + mlp_out, new_cache, aux


# ---------------------------------------------------------------------------
# Whole-model apply
# ---------------------------------------------------------------------------

def lm_hidden(params: Params, cfg: ModelConfig, inputs: Array,
              positions: Optional[Array] = None,
              cache: Optional[Params] = None,
              cache_pos=None) -> Tuple[Array, Optional[Params], Array]:
    """Inputs → final (normed) hidden states — the shared trunk of
    ``lm_apply`` (logits) and ``lm_loss`` (chunked CE, no logits tensor).

    ``inputs``: (B, L) int tokens, or (B, L, d) float embeds when
    ``cfg.input_mode == 'embeds'`` (audio/VLM frontend stubs).
    ``positions``: (B, L) int32, or (B, L, 3) for M-RoPE; defaults to
    ``cache_pos + arange(L)``.
    ``cache``: stacked (n_layers, B, S, Hk, D) k/v dict from
    ``init_kv_cache``, or the paged dict from ``init_paged_kv_cache``;
    ``cache_pos`` the scalar or (B,) per-slot write offset.
    """
    if cfg.input_mode == "embeds" and inputs.ndim == 3:
        x = inputs.astype(L._dtype(cfg.dtype))
        B, Lq = inputs.shape[:2]
    else:
        B, Lq = inputs.shape
        x = L.embed(params["embed"], inputs, scale=cfg.embed_scale)
    if positions is None:
        base = jnp.arange(Lq)
        if cache_pos is not None:
            cp = jnp.asarray(cache_pos)
            # scalar offset (shared) or (B,) per-slot decode positions
            base = base[None, :] + (cp[:, None] if cp.ndim == 1 else cp)
        positions = jnp.broadcast_to(base, (B, Lq))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[..., None], (B, Lq, 3))

    kinds = jnp.asarray(cfg.layer_kinds, jnp.int32)
    # A paged cache's pools ride in the carry, stacked over layers, and
    # each layer scatters into / gathers from them at its own index;
    # only the small per-layer page tables are sliced.  As scanned
    # xs/ys they would be sliced out and written back whole every layer
    # (and copied again by the enclosing decode loop's carry).
    layer_cache, pools, layer_ids = cache, None, None
    if cache is not None and "ptab" in cache:
        pools = {"kp": cache["kp"], "vp": cache["vp"]}
        layer_cache, layer_ids = cache["ptab"], jnp.arange(kinds.shape[0])

    def body(carry, xs):
        x, aux, pools = carry
        p_layer, kind, cache_layer, layer = xs
        if pools is not None:
            cache_layer = {**pools, "ptab": cache_layer}
        x, new_cache, aux_l = block(p_layer, cfg, x, positions, kind,
                                    cache_layer, cache_pos, layer)
        if pools is not None:
            pools = {"kp": new_cache["kp"], "vp": new_cache["vp"]}
            new_cache = None
        return (x, aux + aux_l, pools), new_cache

    body_fn = body
    if cfg.remat and cache is None:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body_fn = jax.checkpoint(body, policy=policy)
    with jax.named_scope("layers"):
        (x, aux, pools), new_cache = jax.lax.scan(
            body_fn, (x, jnp.zeros((), jnp.float32), pools),
            (params["layers"], kinds, layer_cache, layer_ids))
    if pools is not None:
        new_cache = {**pools, "ptab": cache["ptab"]}

    x = L.rmsnorm(params["ln_final"], x, cfg.norm_eps)
    return x, new_cache, aux


def lm_apply(params: Params, cfg: ModelConfig, inputs: Array,
             positions: Optional[Array] = None,
             cache: Optional[Params] = None,
             cache_pos=None, last_only: bool = False
             ) -> Tuple[Array, Optional[Params], Array]:
    """Inputs → logits f32 (B, L|1, vocab_padded).

    ``last_only`` unembeds just the final position — the prefill path,
    where materializing (B, 32768, V) logits would be hundreds of GB.
    """
    x, new_cache, aux = lm_hidden(params, cfg, inputs, positions, cache,
                                  cache_pos)
    if last_only:
        x = x[:, -1:]
    table = params.get("unembed", params["embed"])
    with jax.named_scope("lm_head"):
        logits = L.unembed(table, x, softcap=cfg.final_softcap)
    return logits, new_cache, aux


def lm_logits(params: Params, cfg: ModelConfig, inputs: Array,
              positions: Optional[Array] = None) -> Array:
    """Training-mode forward (no cache)."""
    logits, _, _ = lm_apply(params, cfg, inputs, positions)
    return logits


# ---------------------------------------------------------------------------
# Loss (shared by trainers) — vocab-chunked cross-entropy
# ---------------------------------------------------------------------------

def chunked_ce(x: Array, table: Array, labels: Array, cfg: ModelConfig,
               chunk: int = 16384) -> Array:
    """Mean next-token CE from hidden states without a (B, L, V) tensor.

    Streams the unembedding in vocab chunks with an online logsumexp —
    the classic memory-efficient CE: peak extra memory is (B, L, chunk)
    instead of (B, L, V) (a 10–30× cut at 150k–260k vocabs; what lets the
    train_4k cells fit).  Exactly equals log_softmax+gather (tested).
    """
    from repro.distributed.annotate import batch_axes, constrain, seq_axis

    BATCH = batch_axes()
    MODEL = seq_axis()          # vocab chunks shard over model iff TP mode

    B, Lq, d = x.shape
    V = table.shape[0]
    if V % chunk:
        # pick the divisor of V closest below the target chunk (every
        # vocab_padded is a multiple of 512, so a good divisor exists)
        nc_min = max(1, -(-V // chunk))          # ceil
        chunk = next((V // nc for nc in range(nc_min, V + 1) if V % nc == 0),
                     V)
    nc = V // chunk
    tc = table.reshape(nc, chunk, d)
    # vocab-parallel CE (Megatron-style): each model shard scores its own
    # vocab slice; the only collectives are (B, L)-sized logsumexp psums
    tc = constrain(tc, None, MODEL, None)
    x32 = constrain(x.astype(jnp.float32), BATCH, None, None)

    def step(carry, xs):
        m_run, l_run, lab_logit = carry
        tb, ci = xs
        lo = ci * chunk
        s = jnp.einsum("bld,vd->blv", x32, tb.astype(jnp.float32))
        s = constrain(s, BATCH, None, MODEL)
        if cfg.final_softcap is not None:
            s = jnp.tanh(s / cfg.final_softcap) * cfg.final_softcap
        # mask vocab padding inside the chunk
        valid = (lo + jnp.arange(chunk)) < cfg.vocab_size
        s = jnp.where(valid[None, None, :], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        l_new = l_run * jnp.exp(m_run - m_new) + jnp.sum(
            jnp.exp(s - m_new[..., None]), axis=-1)
        # label logit if the label falls in this chunk
        in_chunk = (labels >= lo) & (labels < lo + chunk)
        idx = jnp.clip(labels - lo, 0, chunk - 1)
        got = jnp.take_along_axis(s, idx[..., None], axis=-1)[..., 0]
        lab_logit = jnp.where(in_chunk, got, lab_logit)
        return (m_new, l_new, lab_logit), None

    init = (jnp.full((B, Lq), -1e30, jnp.float32),
            jnp.zeros((B, Lq), jnp.float32),
            jnp.full((B, Lq), -1e30, jnp.float32))
    # checkpoint the chunk step: without it backward saves every chunk's
    # (B, L, chunk) logits — at 150k+ vocabs that is the single biggest
    # training buffer (≫ all activations combined)
    (m, l, lab), _ = jax.lax.scan(jax.checkpoint(step), init,
                                  (tc, jnp.arange(nc)))
    nll = (m + jnp.log(l)) - lab
    return jnp.mean(nll)


def lm_loss(params: Params, cfg: ModelConfig, tokens: Array, labels: Array,
            aux_weight: float = 0.01) -> Array:
    """Mean next-token cross-entropy (+ MoE aux), vocab padding masked."""
    x, _, aux = lm_hidden(params, cfg, tokens)
    table = params.get("unembed", params["embed"])
    return chunked_ce(x, table, labels, cfg) + aux_weight * aux


def mask_vocab_padding(logits: Array, cfg: ModelConfig) -> Array:
    if cfg.vocab_padded == cfg.vocab_size:
        return logits
    pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
    return jnp.where(pad, -1e30, logits)


# ---------------------------------------------------------------------------
# Serving steps (prefill / decode) — lowered by the dry-run's serve cells
# ---------------------------------------------------------------------------

def lm_prefill(params: Params, cfg: ModelConfig, inputs: Array,
               cache: Params) -> Tuple[Array, Params]:
    """Fill the cache with the prompt; return last-position logits."""
    logits, new_cache, _ = lm_apply(params, cfg, inputs, cache=cache,
                                    cache_pos=jnp.zeros((), jnp.int32),
                                    last_only=True)
    return logits[:, -1], new_cache


def lm_decode_step(params: Params, cfg: ModelConfig, token: Array,
                   cache: Params, pos: Array) -> Tuple[Array, Params]:
    """One decode step: ``token (B,)`` + cache at ``pos`` → next logits."""
    logits, new_cache, _ = lm_apply(params, cfg, token[:, None],
                                    cache=cache, cache_pos=pos)
    return logits[:, 0], new_cache


def lm_decode_block(params: Params, cfg: ModelConfig, tokens: Array,
                    cache: Params, pos: Array) -> Tuple[Array, Params]:
    """Multi-token decode-shaped forward (the speculative verify step):
    ``tokens (B, T)`` written at per-slot positions ``pos (B,)``, causal
    within the block — one batched forward instead of T decode steps.
    Returns logits for every block position ``(B, T, vocab_padded)``."""
    logits, new_cache, _ = lm_apply(params, cfg, tokens,
                                    cache=cache, cache_pos=pos)
    return logits, new_cache
