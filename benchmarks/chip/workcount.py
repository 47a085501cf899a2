"""Operations and bytes the served model requires, from its shapes.

They count the work a layer requires, whatever implements it:

  * a sparse projection ``x (M, K) @ W (K, N)``: ``2 * M * kept``
    operations, where ``kept`` is the number of weight values the pack
    keeps (``n/m`` of ``K * N``; for the combined pack the kept tiles
    times ``n/m``); bytes are the kept values plus ``x`` plus ``y``.
    Index metadata, and the operations of the kernels' one-hot gather,
    are not counted;
  * a decoded token: the kept-weight operations of every projection, the
    dense tied LM head, and attention over the live context
    (``4 * heads * head_dim`` per attended row: scores and the weighted
    sum).  Norms, rotary embeddings and softmax are not counted.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BF16 = 2


def load_peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind`` from ``peaks.json``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def _shapes(cfg: dict) -> dict:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def _family(cfg: dict, name: str):
    fam = cfg.get("sparsity", {}).get(
        "attention" if name in ("q", "k", "v", "o") else "mlp")
    if not fam or fam.get("format", "dense") == "dense":
        return None
    return fam


def kernel_of(cfg: dict, name: str) -> str:
    """The sparse kernel the program runs for projection ``name``."""
    fam = _family(cfg, name)
    if fam is None:
        return "dense"
    return {"nm": "nm_spmm", "combined": "csa_matmul"}[fam["format"]]


def kept(cfg: dict, name: str) -> float:
    """Weight values the pack of projection ``name`` keeps."""
    K, N = _shapes(cfg)[name]
    fam = _family(cfg, name)
    if fam is None:
        return float(K * N)
    frac = fam["n"] / fam["m"]
    if fam["format"] == "combined":
        Kb = K // fam["block"]
        frac *= round(Kb * (1.0 - fam["block_sparsity"])) / Kb
    return K * N * frac


def projection(cfg: dict, name: str, M: int) -> tuple:
    """(operations, bytes) of one call of projection ``name`` at M rows."""
    K, N = _shapes(cfg)[name]
    w = kept(cfg, name)
    return 2.0 * M * w, BF16 * (w + M * K + M * N)


def roofline_s(ops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def kernel_ideal_s(cfg: dict, kernel: str, M: int, peaks: dict) -> float:
    """Roofline time of every call of ``kernel`` in one model step at M
    rows (all layers)."""
    t = sum(roofline_s(*projection(cfg, n, M), peaks)
            for n in _shapes(cfg) if kernel_of(cfg, n) == kernel)
    return t * cfg["num_hidden_layers"]


def _weight_ops_per_row(cfg: dict) -> float:
    return 2.0 * cfg["num_hidden_layers"] * sum(kept(cfg, n)
                                                for n in _shapes(cfg))


def _attn_ops_per_pair(cfg: dict) -> float:
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])


def _head_ops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_token_ops(cfg: dict, context: int) -> float:
    """Operations to decode one token that attends to ``context`` rows."""
    return (_weight_ops_per_row(cfg) + _head_ops(cfg)
            + _attn_ops_per_pair(cfg) * context)
