"""Plain Qwen3 decoder: the benchmark's own weights and its float32
forward pass, written from the published description (the Hugging Face
``Qwen3ForCausalLM`` modelling code and ``config.json``), importing
nothing of the program under test.

Weights.  ``make_weights(cfg, seed, mesh)`` draws every tensor in one
jitted call, in the layout ``x @ W`` (rows = input features), made in
place on ``mesh``: each tensor split over its ``model`` axis along the
axis ``SPLIT`` names (replicated where that axis does not divide), so
that no device ever holds more than its share.  The values do not depend
on the mesh (JAX's partitionable threefry).  It prunes each projection
the configuration's ``sparsity`` recipe lists:

  * ``nm``: keep ``n`` of every ``m`` consecutive input rows, the kept
    positions shared by a group of ``group`` output columns, ranked by
    the group's summed magnitude (float32);
  * ``combined``: first keep ``1 - block_sparsity`` of the
    ``block x block`` tiles of every column strip (the strip's largest
    tiles by summed magnitude, the same count in every strip), then
    ``n:m`` as above inside the kept tiles.

The pruned weights are served in ``torch_dtype`` (bfloat16); norm
weights stay float32.  A projection whose shape the recipe's tiles do not
divide is left dense.

Forward.  ``logits_at(weights, cfg, tokens, positions)`` runs one
sequence through the model in float32 with every matmul at ``highest``
precision and returns the logits at ``positions``.  The weights stay in
their served dtype and each layer's are upcast to float32 inside the
layer scan; called under ``jax.set_mesh``, the work is placed where the
weights lie.  It follows the
published equations: pre-norm blocks, RMSNorm ``x / sqrt(mean(x^2) +
eps) * w``, per-head RMSNorm on q and k before rotary embeddings
(rotate-half form, ``theta = rope_theta``), grouped-query attention
(query head ``h`` reads key/value head ``h // (H / Hk)``), causal
softmax at scale ``head_dim ** -0.5``, SwiGLU MLP ``down(silu(gate(x)) *
up(x))``, final RMSNorm, and the tied embedding as the LM head.
Departures: none in the mathematics; the embedding rows past
``vocab_size`` do not exist here.

Control.  ``control=True`` computes every projection and the LM head in
float8 e4m3, the precision step below the bfloat16 the configuration
states: both operands rounded to e4m3, the activations with one scale
per row and the weights with one per output column, products summed in
float32.  Attention's score and value products stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

ATTN = ("q", "k", "v", "o")
MLP = ("gate", "up", "down")
FP8_MAX = 448.0
# the axis of each weight that a mesh's ``model`` axis splits: output
# features of q, k, v, gate and up, input features of o and down, the
# vocabulary of the embedding, the features of every norm
SPLIT = {"q": -1, "k": -1, "v": -1, "gate": -1, "up": -1, "o": -2,
         "down": -2, "embed": 0, "attn_norm": -1, "mlp_norm": -1,
         "q_norm": -1, "k_norm": -1, "final_norm": -1}


def seed32(seed: int) -> int:
    """A 32-bit key seed from any whole number, however large."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def shapes(cfg: dict) -> dict:
    """(K, N) of every projection of one layer."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def recipe(cfg: dict, name: str):
    """The sparsity recipe that governs projection ``name`` (or None)."""
    sp = cfg.get("sparsity", {})
    fam = sp.get("attention") if name in ATTN else sp.get("mlp")
    if not fam or fam.get("format", "dense") == "dense":
        return None
    K, N = shapes(cfg)[name]
    if K % fam["m"] or N % fam["group"]:
        return None
    if fam["format"] == "combined" and (K % fam["block"] or N % fam["block"]):
        return None
    return fam


def nm_mask(w, n: int, m: int, group: int):
    """Keep ``n`` of every ``m`` rows, shared over ``group`` columns."""
    K, N = w.shape
    s = jnp.abs(w).reshape(K // m, m, N // group, group).sum(-1)
    rank = jnp.argsort(jnp.argsort(-s, axis=1), axis=1)
    keep = (rank < n).astype(w.dtype)                     # (K/m, m, N/g)
    return jnp.repeat(keep[..., None], group, -1).reshape(K, N)


def block_mask(w, sparsity: float, block: int):
    """Keep the largest ``1 - sparsity`` of each column strip's tiles."""
    K, N = w.shape
    Kb, Nb = K // block, N // block
    s = jnp.abs(w).reshape(Kb, block, Nb, block).sum((1, 3))   # (Kb, Nb)
    keep_n = int(round(Kb * (1.0 - sparsity)))
    rank = jnp.argsort(jnp.argsort(-s, axis=0), axis=0)
    keep = (rank < keep_n).astype(w.dtype)
    keep = jnp.repeat(jnp.repeat(keep, block, 0), block, 1)
    return keep


def prune(w, fam):
    if fam is None:
        return w
    mask = nm_mask(w, fam["n"], fam["m"], fam["group"])
    if fam["format"] == "combined":
        mask = mask * block_mask(w, fam["block_sparsity"], fam["block"])
    return w * mask


def _dtype(cfg):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[cfg.get("torch_dtype", "bfloat16")]


def _norm_weight(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def _make(cfg_items, key):
    cfg = dict(cfg_items)
    cfg["sparsity"] = {k: dict(v) for k, v in cfg["sparsity"]}
    dt = _dtype(cfg)
    d, hd, V = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    shp = shapes(cfg)
    k_embed, k_final, k_layers = jax.random.split(key, 3)

    def layer(lkey):
        ks = jax.random.split(lkey, len(shp) + 4)
        out = {}
        for i, (name, (K, N)) in enumerate(shp.items()):
            w = jax.random.normal(ks[i], (K, N), jnp.float32) / np.sqrt(K)
            out[name] = prune(w, recipe(cfg, name)).astype(dt)
        out["attn_norm"] = _norm_weight(ks[-4], (d,))
        out["mlp_norm"] = _norm_weight(ks[-3], (d,))
        out["q_norm"] = _norm_weight(ks[-2], (hd,))
        out["k_norm"] = _norm_weight(ks[-1], (hd,))
        return out

    layers = jax.lax.map(layer, jax.random.split(k_layers,
                                                 cfg["num_hidden_layers"]))
    embed = (jax.random.normal(k_embed, (V, d), jnp.float32)
             / np.sqrt(d)).astype(dt)
    return {"embed": embed, "layers": layers,
            "final_norm": _norm_weight(k_final, (d,))}


def _frozen(cfg: dict):
    """Hashable copy of the numbers the weights depend on."""
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "torch_dtype")
    sp = tuple(sorted((k, tuple(sorted(v.items())))
                      for k, v in cfg.get("sparsity", {}).items()))
    return tuple((k, cfg[k]) for k in keep if k in cfg) + (("sparsity", sp),)


def shardings(abstract, mesh) -> dict:
    """Each weight split over ``mesh``'s ``model`` axis along its
    ``SPLIT`` axis, or replicated where the extent does not divide it."""
    ext = mesh.shape["model"]

    def one(path, a):
        spec = [None] * a.ndim
        axis = SPLIT[path[-1].key] % a.ndim
        if a.shape[axis] % ext == 0:
            spec[axis] = "model"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree_util.tree_map_with_path(one, abstract)


@functools.lru_cache(maxsize=None)
def _maker(frozen: tuple, mesh):
    make = functools.partial(_make, frozen)
    abstract = jax.eval_shape(make, jax.random.key(0))
    return jax.jit(make, out_shardings=shardings(abstract, mesh))


def make_weights(cfg: dict, seed: int, mesh) -> dict:
    """The model's weights from ``seed``: one jitted call, made in place
    on ``mesh``."""
    return _maker(_frozen(cfg), mesh)(jax.random.key(seed32(seed)))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; ``x (L, H, D)``, ``pos (L,)``."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def fp8(w, axis: int):
    """float8 e4m3 with one scale per slice along ``axis`` (the reduction
    axis), returned in float32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x, w, control: bool):
    """``x @ w`` in float32, or with both operands in float8 e4m3 (scaled
    per row of ``x`` and per column of ``w``) for the control."""
    if control:
        return fp8(x, -1) @ fp8(w, 0)
    return x @ w.astype(jnp.float32)


def _hidden(weights, cfg_items, tokens, control):
    """Final-normed hidden states ``(L, d)`` of one sequence."""
    cfg = dict(cfg_items)
    H, Hk, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    L = tokens.shape[0]
    pos = jnp.arange(L)
    x = weights["embed"].astype(jnp.float32)[tokens]
    causal = pos[None, :] <= pos[:, None]

    def block(x, lw):
        mm = lambda a, n: _mm(a, lw[n], control)            # noqa: E731
        h = _rms(x, lw["attn_norm"], eps)
        q = mm(h, "q").reshape(L, H, D)
        k = mm(h, "k").reshape(L, Hk, D)
        v = mm(h, "v").reshape(L, Hk, D)
        q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, lw["k_norm"], eps), pos, theta)
        kh = jnp.repeat(k, H // Hk, axis=1)                   # (L, H, D)
        vh = jnp.repeat(v, H // Hk, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, kh) * (D ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, vh).reshape(L, H * D)
        x = x + mm(a, "o")
        h = _rms(x, lw["mlp_norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, "gate")) * mm(h, "up"), "down")
        return x, None

    x, _ = jax.lax.scan(block, x, weights["layers"])
    return _rms(x, weights["final_norm"], eps)


def _head(weights, cfg_items, x, control):
    V = dict(cfg_items)["vocab_size"]
    return _mm(x, weights["embed"][:V].astype(jnp.float32).T, control)


def _logits(weights, cfg_items, tokens, positions, control):
    x = _hidden(weights, cfg_items, tokens, control)
    return _head(weights, cfg_items, x[positions], control)


_logits_at = jax.jit(_logits, static_argnums=(1, 4))


@functools.partial(jax.jit, static_argnums=(1, 5))
def _best_and_picked(weights, cfg_items, tokens, positions, pick, control):
    logits = _logits(weights, cfg_items, tokens, positions, control)
    rows = jnp.arange(pick.shape[0])[:, None]
    return logits.max(-1), logits.argmax(-1), logits[rows, pick]


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in ("num_attention_heads",
                                       "num_key_value_heads", "head_dim",
                                       "rms_norm_eps", "rope_theta",
                                       "vocab_size"))


def _padded(tokens, positions, pos_to_len: bool):
    """``tokens`` padded at its end to a multiple of 512, ``positions``
    to a multiple of 128 (or, with ``pos_to_len``, to the padded length),
    so that few programs serve many lengths; causal attention keeps the
    padding out of every position read."""
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    L = -(-len(tokens) // 512) * 512
    buf = np.zeros(L, np.int32)
    buf[:len(tokens)] = tokens
    n = len(positions)
    P = L if pos_to_len else -(-n // 128) * 128
    pos = np.full(P, positions[-1], np.int32)
    pos[:n] = positions
    return jnp.asarray(buf), jnp.asarray(pos), n, P


def logits_at(weights: dict, cfg: dict, tokens, positions,
              control: bool = False):
    """float32 logits ``(len(positions), vocab_size)`` of one sequence."""
    buf, pos, n, _ = _padded(tokens, positions, False)
    with jax.default_matmul_precision("highest"):
        return _logits_at(weights, _items(cfg), buf, pos, control)[:n]


def best_and_picked(weights: dict, cfg: dict, tokens, positions, pick,
                    control: bool = False):
    """At each of ``positions`` of one sequence: the largest logit, the
    token that has it, and the logits of the tokens ``pick`` names
    (``(len(positions), k)``), as numpy arrays.  Reduced on the device,
    with one program per 512 tokens of sequence length."""
    buf, pos, n, P = _padded(tokens, positions, True)
    pick = np.asarray(pick, np.int32).reshape(n, -1)
    pk = np.zeros((P, pick.shape[1]), np.int32)
    pk[:n] = pick
    with jax.default_matmul_precision("highest"):
        out = _best_and_picked(weights, _items(cfg), buf, pos,
                               jnp.asarray(pk), control)
    return tuple(np.asarray(a)[:n] for a in out)
