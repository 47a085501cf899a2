"""Mamba-2 (SSD — state-space duality) block, chunked, pure JAX.

Implements the SSD algorithm of arXiv:2405.21060 (the minimal discrete
formulation): within chunks of length Q the recurrence is materialized as a
(Q, Q) lower-triangular attention-like matmul (MXU-friendly); across chunks
a linear recurrence over per-chunk states runs as an O(L/Q) scan.  Decode
is the O(1) recurrent update.  The block's big matmuls — ``in_proj`` and
``out_proj``, ≈85% of parameters — dispatch through ``apply_linear`` so the
paper's sparse formats apply (DESIGN.md §Arch-applicability: the SSD state
update itself is elementwise/scan, no weight matmul to sparsify).

Shapes: d_inner = expand·d_model, H heads of dim P = d_inner/H, state N,
B/C shared across G groups (we materialize per-head for clarity; G=1 for
both assigned SSM archs).
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


def init_mamba(rng: Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, di = cfg.d_model, cfg.d_inner
    G, N, H = cfg.ssm_groups, cfg.ssm_state, cfg.n_ssm_heads
    K = cfg.ssm_conv
    conv_dim = di + 2 * G * N
    ks = jax.random.split(rng, 4)
    return {
        "in_proj": init_linear(ks[0], d, 2 * di + 2 * G * N + H, dtype),
        "out_proj": init_linear(ks[1], di, d, dtype),
        "conv_w": (jax.random.normal(ks[2], (K, conv_dim), jnp.float32)
                   / jnp.sqrt(K)).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm": L.init_rmsnorm(di),
    }


def init_ssm_cache(cfg: ModelConfig, batch: int,
                   n_layers: Optional[int] = None, dtype=jnp.float32) -> Params:
    """Stacked per-layer recurrent state: O(1) in sequence length."""
    nl = n_layers if n_layers is not None else cfg.n_layers
    di = cfg.d_inner
    G, N, H, P = cfg.ssm_groups, cfg.ssm_state, cfg.n_ssm_heads, \
        cfg.ssm_head_dim
    conv_dim = di + 2 * G * N
    return {
        "conv": jnp.zeros((nl, batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((nl, batch, H, P, N), dtype),
    }


def _segsum(a: Array) -> Array:
    """(..., Q) → (..., Q, Q): S[i, j] = Σ_{k=j+1..i} a[k] (−inf above diag)."""
    c = jnp.cumsum(a, axis=-1)
    S = c[..., :, None] - c[..., None, :]
    Q = a.shape[-1]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(tri, S, -jnp.inf)


def ssd_chunked(x: Array, dt: Array, A: Array, B: Array, C: Array,
                chunk: int, init_state: Optional[Array] = None,
                return_states: bool = False):
    """SSD scan.  x (b,l,h,p), dt (b,l,h), A (h,), B/C (b,l,h,n) →
    (y (b,l,h,p), final_state (b,h,p,n)).

    ``return_states=True`` forces ``chunk=1`` (the inter-chunk recurrence
    then runs per position) and additionally returns the recurrent state
    *after every position* as (b, l, h, p, n) — what speculative decoding
    needs to roll the state back to an arbitrary accepted prefix.
    """
    if return_states:
        chunk = 1
    b, l, h, p = x.shape
    n = B.shape[-1]
    nc = l // chunk
    assert l % chunk == 0, f"L={l} not divisible by chunk={chunk}"

    xd = (x * dt[..., None]).astype(jnp.float32)          # discretized input
    a = (dt * A).astype(jnp.float32)                      # (b, l, h) decay logs

    # → chunk layout
    xd = xd.reshape(b, nc, chunk, h, p)
    Bc = B.reshape(b, nc, chunk, h, n).astype(jnp.float32)
    Cc = C.reshape(b, nc, chunk, h, n).astype(jnp.float32)
    ac = a.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2)  # (b, h, nc, Q)
    a_cum = jnp.cumsum(ac, axis=-1)                        # (b, h, nc, Q)

    # 1. intra-chunk (diagonal blocks): quadratic in Q, MXU-shaped
    Lmat = jnp.exp(_segsum(ac))                            # (b, h, nc, Q, Q)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp",
                        Cc, Bc, Lmat, xd)

    # 2. per-chunk final states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)        # (b, h, nc, Q)
    states = jnp.einsum("bcshn,bhcs,bcshp->bchpn", Bc, decay_states, xd)

    # 3. inter-chunk recurrence (includes the initial state slot)
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)
    states = jnp.concatenate([init_state[:, None].astype(jnp.float32),
                              states], axis=1)             # (b, nc+1, h, p, n)
    chunk_sum = a_cum[..., -1]                             # (b, h, nc)
    padded = jnp.pad(chunk_sum, ((0, 0), (0, 0), (1, 0)))  # (b, h, nc+1)
    decay_chunk = jnp.exp(_segsum(padded))                 # (b,h,nc+1,nc+1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    states_in, final = new_states[:, :-1], new_states[:, -1]

    # 4. inter-chunk contribution to outputs
    out_decay = jnp.exp(a_cum)                             # (b, h, nc, Q)
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, states_in, out_decay)

    y = (y_diag + y_off).reshape(b, l, h, p)
    if return_states:
        # state AFTER position t = state before position t+1; the last
        # one is the final state (chunk == 1 → one position per chunk)
        after = jnp.concatenate([states_in[:, 1:], final[:, None]], axis=1)
        return y, final, after
    return y, final


def _causal_conv(xBC: Array, w: Array, bias: Array,
                 state: Optional[Array] = None) -> Tuple[Array, Array]:
    """Depthwise causal conv along L.  xBC (b, l, c), w (K, c) →
    (out (b, l, c), new_state (b, K-1, c))."""
    K = w.shape[0]
    pad = state if state is not None else \
        jnp.zeros((xBC.shape[0], K - 1, xBC.shape[2]), xBC.dtype)
    xp = jnp.concatenate([pad.astype(xBC.dtype), xBC], axis=1)
    out = sum(xp[:, k:k + xBC.shape[1], :] * w[k][None, None, :]
              for k in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else pad
    return out + bias[None, None, :], new_state


def _pick_chunk(l: int, target: int) -> int:
    """Largest divisor of ``l`` that is ≤ target (SSD chunk length)."""
    c = min(target, l)
    while l % c:
        c -= 1
    return c


def mamba_block(params: Params, cfg: ModelConfig, x: Array, *,
                cache: Optional[Params] = None,
                sparsity: SparsityConfig = DENSE,
                collect_states: bool = False
                ) -> Tuple[Array, Optional[Params]]:
    """One Mamba-2 mixer.  ``cache`` (decode): {"conv": (b,K-1,c),
    "ssm": (b,h,p,n)} → returns updated cache; None → chunked scan.

    ``collect_states=True`` (multi-token verify path, needs ``cache`` and
    ``l > 1``): the returned cache additionally carries per-position
    snapshots — ``"conv_seq"`` (b, l, K-1, c) and ``"ssm_seq"``
    (b, l, h, p, n), the recurrent state *after* each of the l positions —
    so a speculative-decode caller can truncate the recurrence to any
    accepted prefix (KV caches roll back by masking; recurrent state
    rolls back by selecting the snapshot).
    """
    b, l, d = x.shape
    di, G, N, H = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.n_ssm_heads
    P = cfg.ssm_head_dim

    zxbcdt = apply_linear(x, params["in_proj"], sparsity, name="in_proj")
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])   # (b, l, H)

    conv_state = cache["conv"] if cache is not None else None
    xBC_raw = xBC                       # pre-conv inputs (conv-state domain)
    xBC, new_conv = _causal_conv(xBC, params["conv_w"], params["conv_b"],
                                 conv_state)
    if conv_state is not None:
        # keep the cache's dtype: the serving decode loop carries the
        # cache through a lax.scan, whose carry type must be stable
        new_conv = new_conv.astype(conv_state.dtype)
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    xs, B, C = jnp.split(xBC, [di, di + G * N], axis=-1)
    xs = xs.reshape(b, l, H, P)
    # expand B/C groups to heads
    rep = H // G
    B = jnp.repeat(B.reshape(b, l, G, N), rep, axis=2)
    C = jnp.repeat(C.reshape(b, l, G, N), rep, axis=2)
    A = -jnp.exp(params["A_log"])                              # (H,)

    if cache is None:
        y, _ = ssd_chunked(xs, dt, A, B, C, _pick_chunk(l, cfg.ssm_chunk))
        new_cache = None
    elif l > 1:
        # prefill with cache: chunked scan seeded from the cached state
        if collect_states:
            y, final, ssm_seq = ssd_chunked(
                xs, dt, A, B, C, 1,
                init_state=cache["ssm"].astype(jnp.float32),
                return_states=True)
            # conv state after position t = the last K-1 pre-conv inputs
            # of the prefix ending at t: sliding windows over the padded
            # input buffer (this branch requires a cache, so conv_state
            # is always set)
            Kc = params["conv_w"].shape[0]
            xp = jnp.concatenate(
                [conv_state.astype(xBC_raw.dtype), xBC_raw], 1)
            win = (jnp.arange(l)[:, None] + 1 + jnp.arange(Kc - 1)[None, :])
            conv_seq = xp[:, win].astype(new_conv.dtype)  # (b, l, K-1, c)
            new_cache = {"conv": new_conv, "ssm": final,
                         "conv_seq": conv_seq, "ssm_seq": ssm_seq}
        else:
            y, final = ssd_chunked(
                xs, dt, A, B, C, _pick_chunk(l, cfg.ssm_chunk),
                init_state=cache["ssm"].astype(jnp.float32))
            new_cache = {"conv": new_conv, "ssm": final}
    else:
        # O(1) recurrent update (l == 1)
        s = cache["ssm"].astype(jnp.float32)                   # (b, h, p, n)
        dt1 = dt[:, 0]                                         # (b, h)
        dA = jnp.exp(dt1 * A[None, :])                         # (b, h)
        xd = xs[:, 0].astype(jnp.float32) * dt1[..., None]     # (b, h, p)
        s = s * dA[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xd, B[:, 0].astype(jnp.float32))
        y = jnp.einsum("bhn,bhpn->bhp", C[:, 0].astype(jnp.float32), s)
        y = y[:, None]                                         # (b, 1, h, p)
        new_cache = {"conv": new_conv, "ssm": s}

    y = y + xs.astype(jnp.float32) * params["D"][None, None, :, None]
    y = y.reshape(b, l, di).astype(x.dtype)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = L.rmsnorm(params["norm"], y, cfg.norm_eps)
    out = apply_linear(y, params["out_proj"], sparsity,
                       name="out_proj")
    return out, new_cache
