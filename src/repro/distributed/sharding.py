"""Sharding rules: PartitionSpec templates for params / batches / caches.

The 1000-node posture (DESIGN.md §6):

  mesh axes   ("pod", "data", "model")   — multi-pod
              ("data", "model")          — single pod

  * ``data``  carries DP *and* FSDP (ZeRO-3): gradients reduce over it and
    parameters/optimizer state are sharded over it, so the 132B/72B cells
    fit 16 GB/chip.
  * ``model`` carries TP (attention heads / MLP hidden / expert-internal),
    EP (expert axis, when the expert count divides), and SP (KV sequence
    at long context).
  * ``pod``   is pure DP across pods: only gradient all-reduces cross the
    DCN, never layer-internal collectives.

Rules are *name-based over the param pytree* (tree_map_with_path), then
filtered by :func:`best_effort` which drops any axis that does not divide
the dimension — every assigned architecture compiles under one rule set,
and the §Perf loop tightens specs per cell from there.

``kv_mode`` picks the KV-cache sharding for serving:
  * ``"batch"`` — shard over batch (decode_32k, B ≥ data extent)
  * ``"heads"`` — shard KV heads over ``model`` (B too small, Hk divides)
  * ``"seq"``   — shard the cache sequence over ``model`` (long_500k:
    B=1 and Hk < model extent; the SP posture)
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def abstract_mesh(sizes: Tuple[int, ...], names: Tuple[str, ...]):
    """``AbstractMesh`` of the given axis sizes and names — the production
    meshes tests and the dry-run reason about without devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(sizes), tuple(names))


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _dp_axes(mesh: Mesh):
    """The data-parallel axes: ("pod","data") on multi-pod, else "data"."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def best_effort(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec axes that don't divide their dim (or don't exist)."""
    sizes = dict(mesh.shape)
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if all(a in sizes for a in axes):
            ext = int(np.prod([sizes[a] for a in axes]))
            out.append(ax if dim % ext == 0 else None)
        else:
            out.append(None)
    return P(*out)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
    return tuple(names)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

# weight-name → (spec for 2D leaf); stacked layers prepend None.
_COL_PARALLEL = {"wq", "wk", "wv", "w_in", "w_gate", "in_proj"}   # (d, wide)
_ROW_PARALLEL = {"wo", "w_out", "out_proj"}                        # (wide, d)


def _param_rule(names: Tuple[str, ...], shape: Tuple[int, ...],
                cfg: ModelConfig, mesh: Mesh) -> P:
    dp = _dp_axes(mesh)
    last = names[-1] if names else ""
    # shared experts are plain MLPs (no leading E axis) — exclude them
    moe = "moe" in names and "shared" not in names
    nd = len(shape)

    if last in ("embed", "unembed"):
        # vocab-parallel (Megatron-style): the CE/unembed matmul is then
        # collective-free except tiny (B, L) logsumexp psums; FSDP-sharding
        # d here makes XLA partition the CE einsum on its contraction dim
        # (multi-GB all-reduces per vocab chunk — measured, see
        # EXPERIMENTS.md §Perf prelude)
        return P("model", None)
    if moe and last == "router":
        return P(None, dp if nd == 2 else None) if nd == 2 else P()
    if moe and last in ("w_in", "w_gate", "w_out"):
        # expert-stacked (.., E, a, b); EP over model if E divides, else
        # TP.  Under EP the FSDP shard goes on the ff dim (NOT the d
        # contraction dim — d-sharded weights force partial-sum
        # all-reduces of every expert activation; §Perf cell B).
        ep_ok = (cfg.moe_sharding == "ep")
        if last == "w_out":          # (E, ff, d)
            inner = ("model", dp) if not ep_ok else (dp, None)
        else:                        # (E, d, ff)
            inner = (dp, "model") if not ep_ok else (None, dp)
        e_ax = "model" if ep_ok else None
        lead = (None,) * (nd - 3)
        return P(*lead, e_ax, *inner)
    if last == "conv_w":             # mamba depthwise conv (K, conv_dim)
        return P(*(None,) * (nd - 1), "model")
    # sparse-pack leaves: "values" inherits the parent weight's rule, and
    # the index metadata shards ALIGNED with it so the gather paths stay
    # shard-local (misaligned metadata forces GSPMD to rematerialize the
    # full pack — visible as "[spmd] Involuntary full rematerialization").
    parent_col = any(n in _COL_PARALLEL for n in names)
    parent_row = any(n in _ROW_PARALLEL for n in names)
    if last == "values" and nd >= 2:
        if nd >= 4:
            # BSR/combined strips (.., Nb, max_nnz, bk, bn): Nb indexes
            # output-feature strips — the TP split the paper's layout
            # argument calls for.  Row-parallel parents FSDP-shard the
            # strip axis instead (their TP split is the contraction dim,
            # which the irregular nnz axis cannot carry).
            lead = (None,) * (nd - 4)
            if parent_col:
                return P(*lead, "model", None, None, None)
            if parent_row:
                return P(*lead, dp, None, None, None)
            return P(*(None,) * nd)
        lead = (None,) * (nd - 2)   # N:M (.., Kc, N)
        if parent_col:
            return P(*lead, dp, "model")
        if parent_row:
            return P(*lead, "model", dp)
        return P(*(None,) * nd)
    if last == "idx" and nd >= 2:            # N:M (.., N//g, Kc)
        lead = (None,) * (nd - 2)
        if parent_col:
            return P(*lead, "model", None)
        if parent_row:
            return P(*lead, None, "model")
        return P(*(None,) * nd)
    if last == "indices" and nd >= 2:        # BSR (.., Nb, max_nnz)
        lead = (None,) * (nd - 2)
        if parent_col:
            return P(*lead, "model", None)
        if parent_row:
            return P(*lead, dp, None)
        return P(*(None,) * nd)
    if last == "counts" and nd >= 1:         # BSR (.., Nb)
        lead = (None,) * (nd - 1)
        if parent_col:
            return P(*lead, "model")
        if parent_row:
            return P(*lead, dp)
        return P(*(None,) * nd)
    if last == "gidx" and nd >= 3:           # combined (.., Nb, nnz, bn//g)
        lead = (None,) * (nd - 3)
        if parent_col:
            return P(*lead, "model", None, None)
        if parent_row:
            return P(*lead, dp, None, None)
        return P(*(None,) * nd)
    if last in ("scale", "enc"):
        return P(*(None,) * nd)
    if last in _COL_PARALLEL:
        lead = (None,) * (nd - 2)
        return P(*lead, dp, "model")
    if last in _ROW_PARALLEL:
        lead = (None,) * (nd - 2)
        return P(*lead, "model", dp)
    if last == "w" and nd >= 2:      # CNN / plain fc
        return P(*(None,) * nd)
    # norms, biases, scalars: replicate
    return P(*(None,) * nd)


def param_specs(abstract_params: Any, cfg: ModelConfig, mesh: Mesh,
                profile: str = "tp") -> Any:
    """PartitionSpec pytree matching ``jax.eval_shape(init_model, ...)``.

    ``profile``:
      * ``"tp"`` — the default rules above (TP/EP over ``model`` + FSDP
        over ``data``); required for models whose state exceeds one chip.
      * ``"dp"`` — pure data parallelism: parameters replicated over
        ``model``, FSDP over ``data``; the batch then shards over BOTH
        axes (``batch_specs(..., extra_dp=True)``).  The right posture
        for small models where per-layer TP all-reduces dwarf compute
        (§Perf cell A: a 0.6B model on TP-16 moves 50× its parameter
        bytes per step in activation collectives).
    """

    def rule(path, leaf):
        if profile == "dp":
            names = _path_names(path)
            spec = _param_rule(names, leaf.shape, cfg, mesh)
            # keep FSDP ("data") placements, drop "model" (replicate)
            cleaned = []
            for ax in tuple(spec):
                axes = ax if isinstance(ax, tuple) else (ax,)
                if ax is not None and "model" not in axes:
                    cleaned.append(ax)
                else:
                    cleaned.append(None)
            spec = P(*cleaned)
        else:
            spec = _param_rule(_path_names(path), leaf.shape, cfg, mesh)
        return best_effort(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, abstract_params)


def shard_factors(names: Tuple[str, ...], mesh: Mesh) -> Tuple[int, int]:
    """(K-split, N-split) of a weight's matmul geometry on ``mesh``.

    Used by ``kernels.dispatch`` to key autotune plans on the SHARD-LOCAL
    problem size: a column-parallel weight computes N/ext output features
    per shard, a row-parallel one contracts K/ext.  Callers only apply a
    factor when it divides (``dispatch.select`` checks), mirroring
    :func:`best_effort`.
    """
    ext = int(dict(mesh.shape).get("model", 1))
    if ext <= 1:
        return (1, 1)
    if any(n in _COL_PARALLEL for n in names):
        return (1, ext)
    if any(n in _ROW_PARALLEL for n in names):
        return (ext, 1)
    return (1, 1)


# ---------------------------------------------------------------------------
# Optimizer-state specs: mirror the param spec for each moment buffer
# ---------------------------------------------------------------------------

def opt_state_specs(param_spec_tree: Any) -> Dict[str, Any]:
    """AdamW state {"mu", "nu", "step"} sharded like the params."""
    return {"mu": param_spec_tree, "nu": param_spec_tree, "step": P()}


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------

def batch_specs(batch_shapes: Dict[str, Any], mesh: Mesh,
                seq_shard: bool = False, extra_dp: bool = False
                ) -> Dict[str, P]:
    """Specs for a training/serving batch dict (tokens/labels/embeds/src).

    Batch dim over the DP axes (plus ``model`` when ``extra_dp`` — the
    pure-DP profile); optionally the sequence dim over ``model``
    (sequence parallelism for very long prefill).
    """
    dp = _dp_axes(mesh)
    if extra_dp:
        dp = (dp if isinstance(dp, tuple) else (dp,)) + ("model",)
    out = {}
    for k, v in batch_shapes.items():
        shape = v.shape if hasattr(v, "shape") else v
        nd = len(shape)
        seq_ax = "model" if seq_shard else None
        if nd == 1:
            spec = P(dp)
        elif nd == 2:
            spec = P(dp, seq_ax)
        else:                      # (B, L, d) embeds / (B, L, 3) mrope
            spec = P(dp, seq_ax, *(None,) * (nd - 2))
        out[k] = best_effort(spec, shape, mesh)
    return out


def shard_batch(batch: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """device_put a concrete host batch onto the mesh per batch_specs."""
    specs = batch_specs(batch, mesh)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in batch.items()}


# ---------------------------------------------------------------------------
# Cache specs (serving)
# ---------------------------------------------------------------------------

def cache_specs(abstract_cache: Any, cfg: ModelConfig, mesh: Mesh,
                kv_mode: str = "auto") -> Any:
    """Specs for the serving cache pytree.

    KV leaves are (n_layers, B, S, Hk, D); SSM leaves are
    conv (L, B, K-1, C) / ssm (L, B, H, P, N).

    ``kv_mode``:
      * "auto"  — batch over DP axes plus, over ``model``, KV heads when
        they divide the axis, else the cache sequence (SP posture; the
        only option at MQA/batch-1 long context).  This is the default:
        a 72B decode_32k cache is ~1.4 TB — batch-sharding alone leaves
        86 GB/chip, batch×model sharding gives 5.4 GB/chip.
      * "batch" | "heads" | "seq" — force one model-axis placement.
    """
    if kv_mode not in ("auto", "batch", "heads", "seq"):
        raise ValueError(f"kv_mode {kv_mode!r}")
    dp = _dp_axes(mesh)
    model_ext = dict(mesh.shape).get("model", 1)

    def rule(path, leaf):
        names = _path_names(path)
        last = names[-1]
        nd = len(leaf.shape)
        if last in ("kp", "vp") and nd == 5:
            # paged KV pool (nl, P, ps, Hk, D): pages are a global pool
            # addressed by every slot's table, so they replicate over the
            # DP axes; KV heads shard over model when they divide (the
            # "heads" posture).  Page tables ("ptab", int32) replicate
            # via the default rule below.
            Hk = leaf.shape[3]
            head_ax = "model" if Hk % model_ext == 0 else None
            spec = P(None, None, None, head_ax, None)
        elif last in ("k", "v") and nd == 5:
            mode = kv_mode
            if mode == "auto":
                Hk = leaf.shape[3]
                mode = "heads" if Hk % model_ext == 0 else "seq"
            if mode == "batch":
                spec = P(None, dp, None, None, None)
            elif mode == "heads":
                spec = P(None, dp, None, "model", None)
            else:
                spec = P(None, dp, "model", None, None)
        elif last == "conv":          # (L, B, K-1, C)
            spec = P(None, dp, None, "model")
        elif last == "ssm":           # (L, B, H, P, N)
            spec = P(None, dp, "model", None, None)
        else:
            spec = P(*(None,) * nd)
        return best_effort(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, abstract_cache)


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

def validate_specs(tree: Any, specs: Any, mesh: Mesh) -> list[str]:
    """Check every spec divides its leaf; returns human-readable problems
    (empty == valid).  Used by tests and the dry-run preflight."""
    problems = []
    sizes = dict(mesh.shape)

    def check(path, leaf, spec):
        shape = leaf.shape if hasattr(leaf, "shape") else leaf
        for d, ax in zip(shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            ext = int(np.prod([sizes.get(a, 1) for a in axes]))
            if d % ext:
                problems.append(
                    f"{'/'.join(_path_names(path))}: dim {d} % {ax}={ext}")

    jax.tree_util.tree_map_with_path(check, tree, specs)
    return problems


def named(mesh: Mesh, spec_tree: Any) -> Any:
    """PartitionSpec pytree → NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
