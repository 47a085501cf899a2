"""Shared neural building blocks (pure-JAX pytrees, no framework).

Every projection goes through ``core.sparse_linear.apply_linear`` so the
paper's sparsity formats are available to *all* model families via config.
Params are nested dicts of arrays; init functions mirror apply functions.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_linear import (DENSE, SparsityConfig, apply_linear,
                                      init_linear)

Array = jax.Array
Params = Dict[str, Any]


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}      # (1 + scale) convention


def rmsnorm(params: Params, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: Params, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * params["scale"].astype(jnp.float32) \
        + params["bias"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(rng: Array, vocab_padded: int, d: int,
                   dtype=jnp.bfloat16) -> Array:
    e = jax.random.normal(rng, (vocab_padded, d), jnp.float32)
    return (e / math.sqrt(d)).astype(dtype)


def embed(table: Array, tokens: Array, scale: bool = False) -> Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(math.sqrt(table.shape[-1]), x.dtype)
    return x


def unembed(table: Array, x: Array, softcap: Optional[float] = None) -> Array:
    logits = jnp.einsum("...d,vd->...v", x, table).astype(jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10_000.0,
               mrope_sections: Optional[Tuple[int, int, int]] = None) -> Array:
    """Rotate ``x (..., L, H, D)`` by ``positions``.

    ``positions``: (..., L) int32 for standard RoPE, or (..., L, 3) for
    M-RoPE (qwen2-vl: temporal/height/width position triples; the head dim's
    frequency slots are partitioned into ``mrope_sections`` groups, each
    rotated by its own position component).  Text-only inputs pass identical
    triples, which reduces exactly to standard RoPE.
    """
    D = x.shape[-1]
    inv = rope_freqs(D, theta)                        # (D/2,)
    if mrope_sections is None:
        if positions.ndim == x.ndim - 2:              # (..., L)
            ang = positions[..., None].astype(jnp.float32) * inv  # (...,L,D/2)
        else:
            raise ValueError("standard RoPE expects (..., L) positions")
    else:
        if positions.shape[-1] != 3:
            raise ValueError("M-RoPE expects (..., L, 3) positions")
        s0, s1, s2 = mrope_sections
        if (s0 + s1 + s2) != D // 2:
            raise ValueError(f"mrope sections {mrope_sections} != D/2={D//2}")
        sect = jnp.concatenate([jnp.zeros((s0,), jnp.int32),
                                jnp.ones((s1,), jnp.int32),
                                2 * jnp.ones((s2,), jnp.int32)])
        # per-frequency-slot position component: (..., L, D/2)
        pos = positions.astype(jnp.float32)[..., sect]
        ang = pos * inv                               # (..., L, D/2)
    sin = jnp.sin(ang)[..., None, :]                  # (..., L, 1, D/2)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated / plain), sparse-format aware
# ---------------------------------------------------------------------------

def init_mlp(rng: Array, d: int, ff: int, gated: bool = True,
             dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(rng, 3)
    p = {"w_in": init_linear(ks[0], d, ff, dtype),
         "w_out": init_linear(ks[1], ff, d, dtype)}
    if gated:
        p["w_gate"] = init_linear(ks[2], d, ff, dtype)
    return p


def mlp(params: Params, x: Array, gated: bool = True,
        sparsity: SparsityConfig = DENSE) -> Array:
    h = apply_linear(x, params["w_in"], sparsity, name="w_in")
    if gated:
        g = apply_linear(x, params["w_gate"], sparsity, name="w_gate")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return apply_linear(h, params["w_out"], sparsity, name="w_out")


def init_dense(rng: Array, K: int, N: int, dtype=jnp.bfloat16) -> Array:
    return init_linear(rng, K, N, dtype)
