"""Packed sparse weight formats — the TPU-side carriers of the paper's idea.

The paper's metadata lives *inside* the weights (LSB lookahead bits).  On a
TPU the unit of skippable work is an MXU-aligned tile and the metadata that
drives skipping must live in SMEM as scalar-prefetch operands of a Pallas
grid.  This module packs pruned weights into three formats, one per paper
design, plus the faithful LSB-encoded form:

  * :class:`BlockSparsePack` — SSSA analogue.  Weight ``(K, N)`` cut into
    ``(bk, bn)`` tiles; per N-strip we store the list of *non-zero* K-tile
    indices (the compiled form of the lookahead walk) and gather their
    values into a dense ``(Nb, max_nnz, bk, bn)`` array.  The kernel grid
    iterates ``max_nnz`` — compute and HBM traffic scale with the number of
    non-zero tiles, exactly the paper's "skip whole blocks" effect.
  * :class:`NMPack` — USSA analogue.  ``n`` of every ``m`` weights kept
    along K, positions shared across groups of ``g`` output columns so the
    activation gather is one ``jnp.take`` per tile followed by a dense MXU
    matmul on a K-axis shrunk by ``n/m`` — compute ∝ non-zeros, the
    variable-cycle MAC's systolic equivalent.
  * :class:`CombinedPack` — CSA analogue: block-skip outer structure whose
    surviving K-tiles are N:M-compressed inside.
  * :class:`LookaheadPack` — the *faithful* container: INT7-clamped int8
    weights with Algorithm 1+2 LSB metadata and a per-column dequant scale.
    ``to_block_sparse`` is the bridge: a host-side scalar pass reads the
    embedded skip bits and emits the SMEM index lists the Pallas kernels
    prefetch (the role ``sssa_inc_indvar`` plays on the FPGA).

All classes are registered dataclass pytrees (arrays = leaves, geometry =
static aux data) so they pass through ``jax.jit``/``pjit`` and can be
sharded like any other parameter.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding
from repro.core.encoding import SKIP_CAP

Array = jax.Array


def _register(cls):
    fields = [f.name for f in dataclasses.fields(cls)]
    data = [f for f in fields if f not in cls._static]
    return jax.tree_util.register_dataclass(
        cls, data_fields=data, meta_fields=list(cls._static))


# ---------------------------------------------------------------------------
# Block-sparse (SSSA analogue)
# ---------------------------------------------------------------------------

@functools.partial(_register)
@dataclasses.dataclass(frozen=True)
class BlockSparsePack:
    """Per-N-strip packed non-zero K-tiles of a ``(K, N)`` weight."""
    values: Array      # (Nb, max_nnz, bk, bn) — packed non-zero tiles
    indices: Array     # (Nb, max_nnz) int32   — K-tile index of each slot
    counts: Array      # (Nb,) int32           — valid slots per strip
    K: int
    N: int
    bk: int
    bn: int
    max_nnz: int
    _static = ("K", "N", "bk", "bn", "max_nnz")

    @property
    def density(self) -> float:
        return float(np.asarray(self.counts).sum()) / max(
            (self.K // self.bk) * (self.N // self.bn), 1)

    def densify(self) -> Array:
        """Reconstruct the dense ``(K, N)`` weight (test oracle)."""
        Kb, Nb = self.K // self.bk, self.N // self.bn
        slot = jnp.arange(self.max_nnz)
        valid = slot[None, :] < self.counts[:, None]            # (Nb, max_nnz)
        vals = jnp.where(valid[:, :, None, None], self.values, 0)
        dense = jnp.zeros((Nb, Kb, self.bk, self.bn), self.values.dtype)
        strip = jnp.arange(Nb)[:, None].repeat(self.max_nnz, 1)
        # clip padded indices into range; their values are zeroed above
        idx = jnp.clip(self.indices, 0, Kb - 1)
        dense = dense.at[strip, idx].add(vals)
        return dense.transpose(1, 2, 0, 3).reshape(self.K, self.N)


def pack_block_sparse(w: Array, bk: int, bn: int,
                      pad_to: Optional[int] = None) -> BlockSparsePack:
    """Pack a (pruned) dense ``(K, N)`` weight; runs eagerly (offline)."""
    K, N = w.shape
    if K % bk or N % bn:
        raise ValueError(f"{w.shape} not divisible by tile ({bk},{bn})")
    Kb, Nb = K // bk, N // bn
    wt = np.asarray(w).reshape(Kb, bk, Nb, bn)
    nz = ~np.all(wt == 0, axis=(1, 3))                  # (Kb, Nb)
    counts = nz.sum(axis=0).astype(np.int32)            # (Nb,)
    max_nnz = int(pad_to if pad_to is not None else max(int(counts.max(initial=0)), 1))
    if counts.max(initial=0) > max_nnz:
        raise ValueError(f"pad_to={pad_to} < max strip nnz {counts.max()}")
    indices = np.zeros((Nb, max_nnz), np.int32)
    values = np.zeros((Nb, max_nnz, bk, bn), np.asarray(w).dtype)
    for j in range(Nb):
        ks = np.nonzero(nz[:, j])[0]
        indices[j, :len(ks)] = ks
        values[j, :len(ks)] = wt[ks, :, j, :]
    return BlockSparsePack(values=jnp.asarray(values),
                           indices=jnp.asarray(indices),
                           counts=jnp.asarray(counts),
                           K=K, N=N, bk=bk, bn=bn, max_nnz=max_nnz)


# ---------------------------------------------------------------------------
# N:M compressed (USSA analogue)
# ---------------------------------------------------------------------------

@functools.partial(_register)
@dataclasses.dataclass(frozen=True)
class NMPack:
    """``n``-of-``m`` compressed K axis; positions shared over ``g`` columns."""
    values: Array      # (Kc, N)  — kept weights, Kc = K*n//m
    idx: Array         # (N//g, Kc) int32 — position within each m-group [0, m)
    K: int
    N: int
    n: int
    m: int
    g: int
    _static = ("K", "N", "n", "m", "g")

    @property
    def Kc(self) -> int:
        return self.K * self.n // self.m

    def src_rows(self) -> Array:
        """Absolute source K-row of each compressed row, per column group:
        ``(N//g, Kc)``."""
        kc = jnp.arange(self.Kc)[None, :]
        return (kc // self.n) * self.m + self.idx

    def densify(self) -> Array:
        src = self.src_rows().T                                 # (Kc, Ng)
        dense = jnp.zeros((self.K, self.N), self.values.dtype)
        vals = self.values.reshape(self.Kc, self.N // self.g, self.g)
        col0 = jnp.arange(self.N // self.g) * self.g
        for off in range(self.g):   # g is small & static (tile width)
            dense = dense.at[src, col0[None, :] + off].set(vals[:, :, off])
        return dense


def pack_nm(w: Array, n: int, m: int, g: int = 1) -> NMPack:
    """Pack a weight already pruned to (group-shared) n:m along K.

    If ``w`` is not exactly n:m it is *projected*: the top-n magnitude rows
    per (m-group × column-group) are kept — so ``pack_nm(prune.n_m(w)…)``
    round-trips exactly, and packing an unstructured-pruned weight gives
    the best n:m approximation (the lossy step is explicit, never silent:
    ``densify()`` shows what the kernel actually computes).
    """
    K, N = w.shape
    if K % m or N % g:
        raise ValueError(f"{w.shape} incompatible with m={m}, g={g}")
    Kg, Ng = K // m, N // g
    wg = np.asarray(w).reshape(Kg, m, Ng, g)
    score = np.abs(wg).sum(axis=3)                      # (Kg, m, Ng)
    order = np.argsort(-score, axis=1)[:, :n, :]        # top-n positions
    pos = np.sort(order, axis=1)                        # keep K-order
    # gather values: (Kg, n, Ng, g)
    vals = np.take_along_axis(wg, pos[:, :, :, None], axis=1)
    Kc = Kg * n
    values = vals.transpose(0, 1, 2, 3).reshape(Kc, Ng, g)[...].reshape(Kc, N)
    # group-major so one group's positions lie along lanes (the kernel
    # reads a (groups, bkc) metadata tile per step)
    idx = pos.reshape(Kc, Ng).T.astype(np.int32)
    return NMPack(values=jnp.asarray(values), idx=jnp.asarray(idx),
                  K=K, N=N, n=n, m=m, g=g)


# ---------------------------------------------------------------------------
# Combined (CSA analogue)
# ---------------------------------------------------------------------------

@functools.partial(_register)
@dataclasses.dataclass(frozen=True)
class CombinedPack:
    """Block-skip outer grid over K-tiles; surviving tiles n:m-compressed.

    ``values[j, t]`` is the compressed ``(bkc, bn)`` tile of the ``t``-th
    non-zero K-tile of strip ``j``; ``gidx[j, t]`` are its ``bkc`` local
    gather rows (shared across the strip's ``bn`` columns)."""
    values: Array      # (Nb, max_nnz, bkc, bn)
    gidx: Array        # (Nb, max_nnz, bkc) int32 — local row within the K-tile
    indices: Array     # (Nb, max_nnz) int32 — K-tile index
    counts: Array      # (Nb,) int32
    K: int
    N: int
    n: int
    m: int
    bk: int
    bn: int
    max_nnz: int
    _static = ("K", "N", "n", "m", "bk", "bn", "max_nnz")

    @property
    def bkc(self) -> int:
        return self.bk * self.n // self.m

    def densify(self) -> Array:
        Kb, Nb = self.K // self.bk, self.N // self.bn
        out = np.zeros((self.K, self.N), dtype=np.asarray(self.values).dtype)
        vals = np.asarray(self.values)
        gidx = np.asarray(self.gidx)
        idxs = np.asarray(self.indices)
        cnts = np.asarray(self.counts)
        for j in range(Nb):
            for t in range(int(cnts[j])):
                kb = int(idxs[j, t])
                rows = kb * self.bk + gidx[j, t]
                out[rows, j * self.bn:(j + 1) * self.bn] += vals[j, t]
        return jnp.asarray(out)


def pack_combined(w: Array, n: int, m: int, bk: int, bn: int,
                  pad_to: Optional[int] = None) -> CombinedPack:
    """Pack a weight pruned with ``pruning.combined_nm`` (block × n:m)."""
    if bk % m:
        raise ValueError(f"bk={bk} must be a multiple of m={m}")
    bsp = pack_block_sparse(w, bk, bn, pad_to=pad_to)
    Nb, max_nnz = bsp.indices.shape
    bkc = bk * n // m
    vals_np = np.asarray(bsp.values)                    # (Nb, max_nnz, bk, bn)
    out_vals = np.zeros((Nb, max_nnz, bkc, bn), vals_np.dtype)
    out_gidx = np.zeros((Nb, max_nnz, bkc), np.int32)
    for j in range(Nb):
        for t in range(int(np.asarray(bsp.counts)[j])):
            tile = vals_np[j, t]                        # (bk, bn)
            sub = pack_nm(jnp.asarray(tile), n, m, g=bn)
            out_vals[j, t] = np.asarray(sub.values)
            out_gidx[j, t] = np.asarray(sub.src_rows()[0])
    return CombinedPack(values=jnp.asarray(out_vals),
                        gidx=jnp.asarray(out_gidx),
                        indices=bsp.indices, counts=bsp.counts,
                        K=bsp.K, N=bsp.N, n=n, m=m, bk=bk, bn=bn,
                        max_nnz=max_nnz)


# ---------------------------------------------------------------------------
# Faithful LSB-encoded container + the bridge to tile metadata
# ---------------------------------------------------------------------------

@functools.partial(_register)
@dataclasses.dataclass(frozen=True)
class LookaheadPack:
    """INT7 weights with Algorithm 1+2 metadata in their LSBs.

    The *entire* sparsity description rides inside the int8 tensor — zero
    extra bytes, the paper's headline property.  ``scale`` dequantizes
    (per output column).
    """
    enc: Array         # (K, N) int8 — encoded: [sign, b5..b0, skip_bit]
    scale: Array       # (1, N) f32
    K: int
    N: int
    _static = ("K", "N")

    @classmethod
    def from_float(cls, w: Array, cap: int = SKIP_CAP) -> "LookaheadPack":
        q, scale = encoding.quantize_int7(w, axis=0)
        enc = encoding.encode_weight_matrix(q, cap=cap)
        return cls(enc=enc, scale=scale.astype(jnp.float32),
                   K=w.shape[0], N=w.shape[1])

    def decode(self) -> Array:
        """Dense float weight the encoded tensor represents."""
        vals, _ = encoding.decode_weight_matrix(self.enc)
        return vals.astype(jnp.float32) * self.scale

    def decode_int(self) -> Array:
        return encoding.decode_values(self.enc)

    def to_block_sparse(self, bk: int, bn: int) -> BlockSparsePack:
        """The FPGA→TPU bridge: read the embedded lookahead bits, walk each
        column stream exactly as ``sssa_inc_indvar`` would, and emit the
        non-zero tile index lists a Pallas scalar-prefetch grid consumes."""
        vals = self.decode_int().astype(jnp.float32) * self.scale
        return pack_block_sparse(vals, bk, bn)


def skip_lists_from_encoded(enc: np.ndarray) -> list[list[int]]:
    """Walk every column of an encoded ``(K, N)`` int8 matrix via the
    embedded skip bits (Listing 2 semantics); returns visited block indices
    per column.  Host-side scalar pass — numpy."""
    enc = np.asarray(enc)
    return [encoding.simulate_walk(enc[:, j]) for j in range(enc.shape[1])]


# ---------------------------------------------------------------------------
# Format metadata overhead (Table III analogue, see bench_resources)
# ---------------------------------------------------------------------------

def metadata_bytes(pack) -> int:
    """Bytes of sparsity metadata a format carries beyond its values."""
    if isinstance(pack, LookaheadPack):
        return 0                      # metadata lives in the weights' LSBs
    if isinstance(pack, BlockSparsePack):
        return pack.indices.size * 4 + pack.counts.size * 4
    if isinstance(pack, NMPack):
        return pack.idx.size * 4
    if isinstance(pack, CombinedPack):
        return (pack.indices.size + pack.counts.size + pack.gidx.size) * 4
    raise TypeError(type(pack))


def values_bytes(pack) -> int:
    v = pack.enc if isinstance(pack, LookaheadPack) else pack.values
    return v.size * v.dtype.itemsize
