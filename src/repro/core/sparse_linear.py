"""SparseLinear — the paper's technique as a first-class framework feature.

A linear layer whose weight can live in any of the sparsity formats
(DESIGN.md §4).  Configs declare a :class:`SparsityConfig` per layer family;
models build projections through :func:`init_linear` / :func:`apply_linear`
and never branch on format themselves.  The lifecycle mirrors the paper's
co-design flow (Fig. 2):

  1. train / load dense weights;
  2. ``prune_params`` — offline pruning pass (Section IV-C);
  3. ``pack_params`` — offline packing into the configured format
     (Algorithm 1+2 for ``lookahead``; tile/N:M packing for the TPU forms);
  4. forward dispatches through ``kernels.dispatch.sparse_matmul`` (kernel
     registry + CPU fallback + autotuned block sizes).

For the multi-pod dry-run (no real weights), :func:`abstract_params`
produces the same pytree out of ``ShapeDtypeStruct`` leaves with a nominal
density, so `jit(...).lower()` sees exactly the structures the packed model
would run with.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import pruning, sparsity
from repro.core.sparsity import (BlockSparsePack, CombinedPack, LookaheadPack,
                                 NMPack)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """Per-layer-family sparsity declaration (config-file level).

    ``format``: ``dense | lookahead | block | nm | combined``
    ``sparsity``: target block sparsity for block/combined (paper's x_ss)
    ``n, m``: N:M pattern for nm/combined (paper's unstructured x_us ≈ 1-n/m)
    ``block_k, block_n``: skip-tile geometry (TPU analogue of the paper's 4)
    ``impl``: ``auto | kernel | ref | interpret | compiled`` execution-mode
    request forwarded to ``kernels.dispatch`` (``auto`` = compiled on TPU,
    pure-jnp ref elsewhere)
    """
    format: str = "dense"
    sparsity: float = 0.5
    n: int = 2
    m: int = 4
    block_k: int = 128
    block_n: int = 128
    impl: str = "auto"

    def __post_init__(self):
        if self.format not in ("dense", "lookahead", "block", "nm", "combined"):
            raise ValueError(f"unknown sparsity format {self.format!r}")


DENSE = SparsityConfig(format="dense")


# ---------------------------------------------------------------------------
# Dense init + offline prune/pack passes
# ---------------------------------------------------------------------------

def init_linear(rng: jax.Array, K: int, N: int,
                dtype=jnp.bfloat16) -> Array:
    """Dense init (fan-in scaled); packing is a separate offline pass."""
    w = jax.random.normal(rng, (K, N), jnp.float32) / math.sqrt(K)
    return w.astype(dtype)


def prune_weight(w: Array, cfg: SparsityConfig) -> Tuple[Array, Array]:
    """Offline pruning matching the configured format's structure."""
    if cfg.format == "dense":
        return w, jnp.ones_like(w)
    if cfg.format == "lookahead":
        # the faithful path prunes at the paper's block-4 granularity
        return pruning.block_semi_structured(w, cfg.sparsity, block=4)
    if cfg.format == "block":
        return pruning.block_semi_structured(w, cfg.sparsity,
                                             block=cfg.block_k)
    if cfg.format == "nm":
        return pruning.n_m(w, cfg.n, cfg.m, group=cfg.block_n)
    if cfg.format == "combined":
        return pruning.combined_nm(w, cfg.sparsity, cfg.n, cfg.m,
                                   group=cfg.block_n, block=cfg.block_k)
    raise ValueError(cfg.format)


def pack_weight(w: Array, cfg: SparsityConfig, pad_to: Optional[int] = None):
    """Offline packing of a (pruned) dense weight into the configured
    format.  Returns the dense array unchanged for ``format='dense'``."""
    if cfg.format == "dense":
        return w
    if cfg.format == "lookahead":
        return LookaheadPack.from_float(w)
    if cfg.format == "block":
        return sparsity.pack_block_sparse(w, cfg.block_k, cfg.block_n,
                                          pad_to=pad_to)
    if cfg.format == "nm":
        return sparsity.pack_nm(w, cfg.n, cfg.m, g=cfg.block_n)
    if cfg.format == "combined":
        return sparsity.pack_combined(w, cfg.n, cfg.m, cfg.block_k,
                                      cfg.block_n, pad_to=pad_to)
    raise ValueError(cfg.format)


def sparsify_weight(w: Array, cfg: SparsityConfig):
    """prune + pack in one offline call."""
    pruned, _ = prune_weight(w, cfg)
    return pack_weight(pruned, cfg)


def _family_sparsity(names, cfg: Any) -> Optional[SparsityConfig]:
    """Name-based rule: which per-family SparsityConfig governs a weight.

    Shared by :func:`pack_params` (concrete offline packing) and
    :func:`sparsify_abstract` (dry-run abstract packs) so the two can
    never disagree about what gets packed.  ``cfg`` is the model config
    (duck-typed: ``mlp_sparsity`` / ``attn_sparsity`` /
    ``expert_sparsity``).
    """
    if any(n in ("w_in", "w_gate", "w_out") for n in names):
        moe = "moe" in names and "shared" not in names
        return cfg.expert_sparsity if moe else cfg.mlp_sparsity
    if any(n in ("in_proj", "out_proj") for n in names):
        return cfg.mlp_sparsity
    if any(n in ("wq", "wk", "wv", "wo") for n in names):
        return cfg.attn_sparsity
    return None


def _geometry_ok(K: int, N: int, scfg: SparsityConfig) -> bool:
    """Every dim the pack format assumes must divide."""
    if scfg.format in ("nm", "combined") and (K % scfg.m or
                                              N % scfg.block_n):
        return False
    if scfg.format in ("block", "combined") and K % scfg.block_k:
        return False
    return True


def _pack_stacked(w: Array, scfg: SparsityConfig):
    """prune + pack a weight with optional stacked leading axes.

    Layer-scan / expert stacks carry leading axes on every leaf; the pack
    is built per 2D slice and its array leaves re-stacked (static
    geometry describes the slice, matching how ``lax.scan`` slices it
    in-model).  block/combined packs are padded to a uniform ``max_nnz``
    across slices so the stack is rectangular.
    """
    lead = w.shape[:-2]
    if not lead:
        return sparsify_weight(w, scfg)
    flat = w.reshape((-1,) + w.shape[-2:])
    pruned = [prune_weight(s, scfg)[0] for s in flat]
    if scfg.format in ("block", "combined"):
        pad = max(pack_weight(p, scfg).max_nnz for p in pruned)
        packs = [pack_weight(p, scfg, pad_to=pad) for p in pruned]
    else:
        packs = [pack_weight(p, scfg) for p in pruned]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *packs)
    if len(lead) > 1:
        stacked = jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]),
                               stacked)
    return stacked


def pack_params(params: Any, cfg: Any) -> Any:
    """Offline prune+pack of a whole param pytree (lifecycle steps 2+3).

    ``cfg`` is the model config (duck-typed: only ``mlp_sparsity`` /
    ``attn_sparsity`` / ``expert_sparsity`` are read).  The same
    name-based rules as :func:`sparsify_abstract` pick the per-family
    :class:`SparsityConfig`; weights whose geometry doesn't divide the
    pack tiling stay dense.  The result serves directly: ``apply_linear``
    dispatches on the packed types, so a packed model runs the paper's
    sparse kernels with no model-code changes.
    """

    def rule(path, leaf):
        names = [str(p.key) for p in path if hasattr(p, "key")]
        scfg = _family_sparsity(names, cfg)
        if scfg is None or scfg.format == "dense" or leaf.ndim < 2:
            return leaf
        K, N = leaf.shape[-2:]
        if not _geometry_ok(K, N, scfg):
            return leaf
        return _pack_stacked(leaf, scfg)

    return jax.tree_util.tree_map_with_path(rule, params)


def make_draft_params(params: Any, cfg: Any) -> Any:
    """The sparse *drafter* half of speculative serving: prune+pack the
    verify params into the model config's per-family sparse formats.

    Memory contract: only weights a :class:`SparsityConfig` actually
    governs are re-materialized as packs — every other leaf (embeddings,
    norms, dense-format families, geometry misfits) is returned **by
    reference**, so carrying both draft and verify params through one
    ``ServeConfig`` costs the packed values (≈ ``1 - n/m`` of the packed
    weights), not a second model copy; the KV cache is shared outright
    (the verify block re-writes drafted rows, see ``serving.engine``).

    A config whose sparsity families are all ``dense`` yields the input
    pytree unchanged — spec_draft="pack" then degenerates to self-draft.
    """
    return pack_params(params, cfg)


# ---------------------------------------------------------------------------
# Abstract (ShapeDtypeStruct) packs for the dry-run
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_pack(K: int, N: int, cfg: SparsityConfig, dtype=jnp.bfloat16,
                  density: Optional[float] = None):
    """The pack pytree with ShapeDtypeStruct leaves — same structure the
    packed model would carry, sized at the configured nominal density."""
    if cfg.format == "dense":
        return _sds((K, N), dtype)
    if cfg.format == "lookahead":
        return LookaheadPack(enc=_sds((K, N), jnp.int8),
                             scale=_sds((1, N), jnp.float32), K=K, N=N)
    d = density if density is not None else (1.0 - cfg.sparsity)
    if cfg.format == "block":
        Kb, Nb = K // cfg.block_k, N // cfg.block_n
        max_nnz = max(1, math.ceil(Kb * d))
        return BlockSparsePack(
            values=_sds((Nb, max_nnz, cfg.block_k, cfg.block_n), dtype),
            indices=_sds((Nb, max_nnz), jnp.int32),
            counts=_sds((Nb,), jnp.int32),
            K=K, N=N, bk=cfg.block_k, bn=cfg.block_n, max_nnz=max_nnz)
    if cfg.format == "nm":
        Kc = K * cfg.n // cfg.m
        return NMPack(values=_sds((Kc, N), dtype),
                      idx=_sds((N // cfg.block_n, Kc), jnp.int32),
                      K=K, N=N, n=cfg.n, m=cfg.m, g=cfg.block_n)
    if cfg.format == "combined":
        Kb, Nb = K // cfg.block_k, N // cfg.block_n
        bkc = cfg.block_k * cfg.n // cfg.m
        max_nnz = max(1, math.ceil(Kb * d))
        return CombinedPack(
            values=_sds((Nb, max_nnz, bkc, cfg.block_n), dtype),
            gidx=_sds((Nb, max_nnz, bkc), jnp.int32),
            indices=_sds((Nb, max_nnz), jnp.int32),
            counts=_sds((Nb,), jnp.int32),
            K=K, N=N, n=cfg.n, m=cfg.m, bk=cfg.block_k, bn=cfg.block_n,
            max_nnz=max_nnz)
    raise ValueError(cfg.format)


def sparsify_abstract(abstract_params, cfg) -> Any:
    """Replace weight ShapeDtypeStruct leaves with abstract *packs* per the
    model config's per-family sparsity — what the dry-run lowers for the
    paper-faithful sparse cells (inference: packed weights, no grads).

    Stacked leading axes (layer scan, expert stacks) are preserved on the
    pack's array leaves; the pack's static geometry describes the 2D
    per-slice weight, matching how ``lax.scan`` slices it in-model.
    Leaves whose K/N don't divide the pack geometry stay dense (recorded
    by the caller via tree inspection).
    """
    import jax

    def rule(path, leaf):
        names = [str(p.key) for p in path if hasattr(p, "key")]
        scfg = _family_sparsity(names, cfg)
        if scfg is None or scfg.format == "dense" or leaf.ndim < 2:
            return leaf
        lead = leaf.shape[:-2]
        K, N = leaf.shape[-2:]
        if not _geometry_ok(K, N, scfg):
            return leaf
        try:
            pack = abstract_pack(K, N, scfg, dtype=leaf.dtype)
        except Exception:
            return leaf
        if lead:
            pack = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype),
                pack)
        return pack

    return jax.tree_util.tree_map_with_path(rule, abstract_params)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def apply_linear(x: Array, weight: Any, cfg: SparsityConfig = DENSE,
                 name: Optional[str] = None) -> Array:
    """``x (..., K) @ weight (K, N) -> (..., N)`` for any format.

    Leading dims are flattened to the kernel's M dimension and restored.
    Kernel choice, backend fallback and block sizes are the dispatcher's
    job (``kernels.dispatch``) — this layer only normalizes shapes.
    ``name`` is the weight's param name (``"wq"``, ``"w_out"``, ...): on
    a tensor-parallel mesh it tells dispatch how the packed weight is
    split (``distributed.sharding.shard_factors``).
    """
    from repro.kernels import dispatch  # local import: kernels pull in pallas

    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    if isinstance(weight, (BlockSparsePack, NMPack, CombinedPack,
                           LookaheadPack)):
        out = dispatch.sparse_matmul(x2, weight, impl=cfg.impl, name=name)
        N = weight.N
    else:
        out = jnp.dot(x2, weight)
        N = weight.shape[-1]
    return out.reshape(*lead, N)


def weight_out_features(weight: Any) -> int:
    if isinstance(weight, (BlockSparsePack, NMPack, CombinedPack,
                           LookaheadPack)):
        return weight.N
    return weight.shape[-1]


def format_stats(weight: Any) -> dict:
    """values/metadata bytes + density — feeds bench_resources (Table III
    analogue)."""
    if isinstance(weight, (BlockSparsePack, NMPack, CombinedPack,
                           LookaheadPack)):
        stats = {
            "values_bytes": sparsity.values_bytes(weight),
            "metadata_bytes": sparsity.metadata_bytes(weight),
        }
        if isinstance(weight, BlockSparsePack):
            stats["density"] = weight.density
        return stats
    return {"values_bytes": weight.size * weight.dtype.itemsize,
            "metadata_bytes": 0, "density": 1.0}
