"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16×16 = 256 chips per pod; the multi-pod variant adds a
leading pod axis (2 pods = 512 chips).  The pod axis carries pure data
parallelism (only gradient all-reduces cross the DCN); ``data`` carries
DP+FSDP; ``model`` carries TP/EP/SP (DESIGN.md §6).

``make_elastic_mesh`` is the resize-aware variant the relaunch path uses:
given whatever devices exist, it keeps the model axis fixed (the model
must still fit) and grows/shrinks ``data`` — checkpoints reshard on
restore (checkpoint/store.py), so elastic scaling is a relaunch, not a
code change.

Every mesh is built by :func:`make_mesh`: ``jax.make_mesh`` (devices in
topology order) with ``Auto`` axis types.  JAX defaults to ``Explicit``
axes, which ``with_sharding_constraint`` on bare ``PartitionSpec``s (the
model's ``constrain`` annotations) refuses.  Enter a mesh with
``jax.set_mesh(mesh)``; that is what the annotations and the sharded
kernel calls (``kernels.dispatch``) read.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

log = logging.getLogger(__name__)


def make_mesh(shape: Sequence[int] = (1, 1),
              axes: Sequence[str] = ("data", "model"),
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types (see module doc)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(model_parallel: int = 16,
                      devices: Optional[list] = None) -> Mesh:
    """Whatever-fits mesh: ``model`` fixed, ``data`` = n_devices / model.

    Raises when the requested TP exceeds the device count (the model was
    sized for that shard width — silently serving it on 1 device OOMs or
    lies about the measured posture).  When ``model_parallel`` merely
    fails to divide ``n``, the largest divisor ≤ request is used and the
    chosen shape is logged.
    """
    devs = devices if devices is not None else jax.devices()
    n = len(devs)
    if model_parallel < 1:
        raise ValueError(f"model_parallel must be >= 1, got {model_parallel}")
    if model_parallel > n:
        raise ValueError(
            f"model_parallel={model_parallel} exceeds the {n} available "
            f"device(s); pass --devices/--model-parallel that fit (e.g. "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={model_parallel}"
            " on CPU)")
    mp = model_parallel
    while n % mp:
        mp -= 1
    if mp != model_parallel:
        log.warning("make_elastic_mesh: model_parallel=%d does not divide "
                    "%d devices; using mesh shape data=%d x model=%d",
                    model_parallel, n, n // mp, mp)
    return make_mesh((n // mp, mp), devices=devs[: (n // mp) * mp])


def make_host_mesh() -> Mesh:
    """1×1 mesh over the real local device (smoke tests, examples)."""
    return make_mesh(devices=jax.devices()[:1])


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size
