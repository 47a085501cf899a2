"""Mesh-agnostic sharding annotations for model code.

``constrain(x, *axes)`` is ``with_sharding_constraint`` that (a) no-ops
outside a ``jax.set_mesh`` context (smoke tests, single-host examples), (b) drops
axes missing from the ambient mesh, and (c) drops axes that don't divide
the dimension — so model code can state its *intended* layout once and
run everywhere.  The named axes follow DESIGN.md §6: "data" (+"pod") for
batch, "model" for TP/EP/SP.

This module deliberately imports nothing from repro (models import it).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

BATCH = ("pod", "data")      # data-parallel axes (present subset is used)
MODEL = "model"

# Activation-sharding mode, set by the step builders before tracing:
#   "tp" — batch over (pod, data); sequence/vocab dims over "model"
#          (Megatron-SP residual stream).
#   "dp" — batch over (pod, data, model); "model" carries no tensor
#          parallelism (small-model posture, §Perf cell A).
_MODE = "tp"


def set_sharding_mode(mode: str) -> None:
    global _MODE
    if mode not in ("tp", "dp"):
        raise ValueError(mode)
    _MODE = mode


def batch_axes() -> Tuple[str, ...]:
    return BATCH + (MODEL,) if _MODE == "dp" else BATCH


def seq_axis() -> Optional[str]:
    return None if _MODE == "dp" else MODEL


def axis_size(name: str) -> int:
    sizes = _ambient_sizes()
    return sizes.get(name, 1) if sizes else 1


def _ambient_sizes() -> Optional[dict]:
    """Axis sizes of the mesh entered with ``jax.set_mesh`` (None off-mesh)."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else dict(am.shape)


def constrain(x: jax.Array, *axes: Axis) -> jax.Array:
    """Best-effort ``with_sharding_constraint(x, P(*axes))``."""
    sizes = _ambient_sizes()
    if sizes is None:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes + (None,) * (x.ndim - len(axes))):
        if ax is None:
            spec.append(None)
            continue
        t = ax if isinstance(ax, tuple) else (ax,)
        t = tuple(a for a in t if a in sizes)
        ext = math.prod(sizes[a] for a in t) if t else 1
        spec.append((t if len(t) > 1 else t[0])
                    if t and dim % ext == 0 else None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
