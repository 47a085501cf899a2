"""Encoder-decoder backbone (seamless-m4t-large-v2 text/audio backbone).

Per the assignment spec the modality frontend is a STUB: the encoder
consumes precomputed frame embeddings ``(B, L_src, d_model)`` (what the
conformer audio frontend would emit); ``input_specs`` provides them as
ShapeDtypeStructs for the dry-run and the data pipeline synthesizes them
for smoke tests.

Structure (standard transformer enc-dec, pre-norm):
  * encoder: n_encoder_layers × [bidirectional self-attn + MLP], scanned.
  * decoder: n_layers × [causal self-attn + cross-attn(enc_out) + MLP],
    scanned.

Serving: ``encdec_prefill`` encodes the source once, *precomputes the
cross-attention K/V per decoder layer* (they are decode-invariant) and
prefills the decoder self-attention cache; ``encdec_decode_step`` then
touches only cached tensors.  Sparsity applies to every projection via
``apply_linear``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import (_sdpa, attention, init_attention)
from repro.models.config import ModelConfig

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_enc_layer(rng: Array, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 2)
    return {
        "ln_attn": L.init_rmsnorm(cfg.d_model),
        "attn": init_attention(ks[0], cfg, dtype=dtype),
        "ln_mlp": L.init_rmsnorm(cfg.d_model),
        "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff,
                          gated=cfg.mlp_gated, dtype=dtype),
    }


def _init_dec_layer(rng: Array, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    return {
        "ln_self": L.init_rmsnorm(cfg.d_model),
        "self_attn": init_attention(ks[0], cfg, dtype=dtype),
        "ln_cross": L.init_rmsnorm(cfg.d_model),
        "cross_attn": init_attention(ks[1], cfg, dtype=dtype),
        "ln_mlp": L.init_rmsnorm(cfg.d_model),
        "mlp": L.init_mlp(ks[2], cfg.d_model, cfg.d_ff,
                          gated=cfg.mlp_gated, dtype=dtype),
    }


def init_encdec(rng: Array, cfg: ModelConfig) -> Params:
    dtype = L._dtype(cfg.dtype)
    k_embed, k_enc, k_dec = jax.random.split(rng, 3)
    enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    return {
        "embed": L.init_embedding(k_embed, cfg.vocab_padded, cfg.d_model,
                                  dtype),
        "encoder": jax.vmap(lambda k: _init_enc_layer(k, cfg, dtype))(
            enc_keys),
        "decoder": jax.vmap(lambda k: _init_dec_layer(k, cfg, dtype))(
            dec_keys),
        "ln_enc_final": L.init_rmsnorm(cfg.d_model),
        "ln_dec_final": L.init_rmsnorm(cfg.d_model),
    }


def init_encdec_cache(cfg: ModelConfig, batch: int, max_len: int,
                      src_len: int, dtype=jnp.bfloat16,
                      page_size: int = 0, num_pages: int = 0) -> Params:
    """Self KV (n_layers, B, max_len, Hk, D) + decode-invariant cross KV
    (n_layers, B, src_len, Hk, D), filled by ``encdec_prefill``.

    ``page_size > 0`` pages the decoder *self* cache (the only part that
    grows with decode length); cross K/V is written once per request at a
    fixed per-slot ``src_len``, so paging it buys nothing.
    """
    from repro.models.attention import init_paged_kv_cache
    if page_size:
        self_cache = init_paged_kv_cache(cfg, batch, max_len, page_size,
                                         num_pages, dtype=dtype)
    else:
        self_shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                      cfg.head_dim)
        self_cache = {"k": jnp.zeros(self_shape, dtype),
                      "v": jnp.zeros(self_shape, dtype)}
    cross_shape = (cfg.n_layers, batch, src_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "self": self_cache,
        "cross": {"k": jnp.zeros(cross_shape, dtype),
                  "v": jnp.zeros(cross_shape, dtype)},
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(params: Params, cfg: ModelConfig, src: Array) -> Array:
    """``src`` (B, L_src, d_model) frame embeddings → encoder output."""
    B, Ls, _ = src.shape
    positions = jnp.broadcast_to(jnp.arange(Ls), (B, Ls))

    def body(x, p_layer):
        h = L.rmsnorm(p_layer["ln_attn"], x, cfg.norm_eps)
        out, _ = attention(p_layer["attn"], cfg, h, positions, causal=False,
                           sparsity=cfg.attn_sparsity)
        x = x + out
        h = L.rmsnorm(p_layer["ln_mlp"], x, cfg.norm_eps)
        return x + L.mlp(p_layer["mlp"], h, gated=cfg.mlp_gated,
                         sparsity=cfg.mlp_sparsity), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, src.astype(L._dtype(cfg.dtype)),
                        params["encoder"])
    return L.rmsnorm(params["ln_enc_final"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Decoder (training / teacher-forcing path: recomputes cross K/V in-layer)
# ---------------------------------------------------------------------------

def decode_hidden(params: Params, cfg: ModelConfig, tokens: Array,
                  enc_out: Array) -> Array:
    """Teacher-forcing decoder trunk → final (normed) hidden states."""
    B, Lt = tokens.shape
    x = L.embed(params["embed"], tokens, scale=cfg.embed_scale)
    positions = jnp.broadcast_to(jnp.arange(Lt), (B, Lt))

    def body(x, p_layer):
        h = L.rmsnorm(p_layer["ln_self"], x, cfg.norm_eps)
        out, _ = attention(p_layer["self_attn"], cfg, h, positions,
                           sparsity=cfg.attn_sparsity)
        x = x + out
        h = L.rmsnorm(p_layer["ln_cross"], x, cfg.norm_eps)
        out, _ = attention(p_layer["cross_attn"], cfg, h, positions,
                           cross_src=enc_out, sparsity=cfg.attn_sparsity)
        x = x + out
        h = L.rmsnorm(p_layer["ln_mlp"], x, cfg.norm_eps)
        return x + L.mlp(p_layer["mlp"], h, gated=cfg.mlp_gated,
                         sparsity=cfg.mlp_sparsity), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["decoder"])
    return L.rmsnorm(params["ln_dec_final"], x, cfg.norm_eps)


def decode_train(params: Params, cfg: ModelConfig, tokens: Array,
                 enc_out: Array) -> Array:
    x = decode_hidden(params, cfg, tokens, enc_out)
    return L.unembed(params["embed"], x, softcap=cfg.final_softcap)


def encdec_apply(params: Params, cfg: ModelConfig, src: Array,
                 tokens: Array) -> Array:
    """Teacher-forcing forward: (frames, target tokens) → logits."""
    return decode_train(params, cfg, tokens, encode(params, cfg, src))


def encdec_loss(params: Params, cfg: ModelConfig, src: Array, tokens: Array,
                labels: Array) -> Array:
    from repro.models.transformer import chunked_ce
    x = decode_hidden(params, cfg, tokens, encode(params, cfg, src))
    return chunked_ce(x, params["embed"], labels, cfg)


# ---------------------------------------------------------------------------
# Serving: prefill (encode + cache cross K/V + decoder prompt) and decode
# ---------------------------------------------------------------------------

def _cross_kv(p_layer: Params, cfg: ModelConfig, enc_out: Array):
    """Project encoder output to one decoder layer's cross K/V."""
    from repro.core.sparse_linear import apply_linear
    B, Ls, _ = enc_out.shape
    k = apply_linear(enc_out, p_layer["cross_attn"]["wk"], cfg.attn_sparsity,
                     name="wk")
    v = apply_linear(enc_out, p_layer["cross_attn"]["wv"], cfg.attn_sparsity,
                     name="wv")
    k = k.reshape(B, Ls, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Ls, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = L.rmsnorm(p_layer["cross_attn"]["k_norm"], k, cfg.norm_eps)
    return k, v


def _dec_step_body(p_layer, cfg: ModelConfig, x: Array, positions: Array,
                   self_cache: Params, cross_k: Array, cross_v: Array,
                   cache_pos) -> Tuple[Array, Params]:
    """One decoder layer against cached self/cross K/V."""
    from repro.core.sparse_linear import apply_linear
    B, Lq, _ = x.shape
    h = L.rmsnorm(p_layer["ln_self"], x, cfg.norm_eps)
    out, new_self = attention(p_layer["self_attn"], cfg, h, positions,
                              cache=self_cache, cache_pos=cache_pos,
                              sparsity=cfg.attn_sparsity)
    x = x + out

    h = L.rmsnorm(p_layer["ln_cross"], x, cfg.norm_eps)
    q = apply_linear(h, p_layer["cross_attn"]["wq"], cfg.attn_sparsity,
                     name="wq")
    q = q.reshape(B, Lq, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rmsnorm(p_layer["cross_attn"]["q_norm"], q, cfg.norm_eps)
    out = _sdpa(cfg, q, cross_k, cross_v, causal=False, window=None)
    out = out.reshape(B, Lq, cfg.q_dim)
    out = apply_linear(out, p_layer["cross_attn"]["wo"], cfg.attn_sparsity,
                       name="wo")
    x = x + out

    h = L.rmsnorm(p_layer["ln_mlp"], x, cfg.norm_eps)
    x = x + L.mlp(p_layer["mlp"], h, gated=cfg.mlp_gated,
                  sparsity=cfg.mlp_sparsity)
    return x, new_self


def _dec_cached(params: Params, cfg: ModelConfig, tokens: Array,
                cache: Params, cache_pos,
                last_only: bool = False) -> Tuple[Array, Params]:
    B, Lt = tokens.shape
    x = L.embed(params["embed"], tokens, scale=cfg.embed_scale)
    cp = jnp.asarray(cache_pos)
    base = jnp.arange(Lt)[None, :] + (cp[:, None] if cp.ndim == 1 else cp)
    positions = jnp.broadcast_to(base, (B, Lt))

    def body(x, xs):
        p_layer, self_c, ck, cv = xs
        x, new_self = _dec_step_body(p_layer, cfg, x, positions, self_c,
                                     ck, cv, cache_pos)
        return x, new_self

    x, new_self = jax.lax.scan(
        body, x, (params["decoder"], cache["self"],
                  cache["cross"]["k"], cache["cross"]["v"]))
    x = L.rmsnorm(params["ln_dec_final"], x, cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    logits = L.unembed(params["embed"], x, softcap=cfg.final_softcap)
    return logits, {"self": new_self, "cross": cache["cross"]}


def encdec_prefill(params: Params, cfg: ModelConfig, src: Array,
                   prompt: Array, cache: Params) -> Tuple[Array, Params]:
    """Encode source, fill cross K/V, prefill decoder self cache with the
    prompt; returns last-position logits + the serving cache."""
    enc_out = encode(params, cfg, src)

    def kv_layer(p_layer):
        return _cross_kv(p_layer, cfg, enc_out)

    ck, cv = jax.vmap(kv_layer)(params["decoder"])     # (nl, B, Ls, Hk, D)
    cache = {"self": cache["self"],
             "cross": {"k": ck.astype(cache["cross"]["k"].dtype),
                       "v": cv.astype(cache["cross"]["v"].dtype)}}
    logits, cache = _dec_cached(params, cfg, prompt, cache,
                                jnp.zeros((), jnp.int32), last_only=True)
    return logits[:, -1], cache


def encdec_decode_step(params: Params, cfg: ModelConfig, token: Array,
                       cache: Params, pos: Array) -> Tuple[Array, Params]:
    logits, cache = _dec_cached(params, cfg, token[:, None], cache, pos)
    return logits[:, 0], cache


def encdec_decode_block(params: Params, cfg: ModelConfig, tokens: Array,
                        cache: Params, pos: Array) -> Tuple[Array, Params]:
    """Multi-token decode-shaped forward (the speculative verify step):
    ``tokens (B, T)`` against the cached self/cross K/V at per-slot
    positions ``pos (B,)`` — logits for every block position."""
    return _dec_cached(params, cfg, tokens, cache, pos)
