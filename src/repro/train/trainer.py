"""Distributed trainer: sharded train step + fault-tolerant fit loop.

``build_train_step`` assembles the jitted SPMD step the dry-run lowers and
the real launcher runs:

  * loss = ``models.model_loss`` (family-dispatched),
  * microbatch gradient accumulation (``lax.scan`` over a leading
    microbatch axis — the scheduling substrate pipeline parallelism would
    plug into),
  * optional int8 gradient quantization with error feedback before the
    update (``compress_grads`` — the cross-pod DCN traffic shrinks 4×;
    byte-level effect verified in the §Perf collective parse),
  * masked AdamW update (pruned weights stay pruned),
  * in/out shardings from ``distributed.sharding`` with donated
    params/opt-state (no double-buffer HBM spike).

``Trainer.fit`` adds the 1000-node operational posture in host code:
restart-from-latest, periodic async checkpoints, per-step retry on
transient failure, and a straggler watchdog (wall-time EMA; steps slower
than ``straggler_factor``× the EMA are flagged — the hook where a fleet
controller would re-slice).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import models as MZ
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, make_batch
from repro.distributed import sharding as SH
from repro.models.config import ModelConfig
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         cosine_warmup)
from repro.optim.compression import compress_int8, decompress_int8

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    microbatches: int = 1           # grad-accumulation factor
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 3
    log_every: int = 10
    # optimizer
    lr: float = 3e-4
    warmup: int = 20
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    # distribution / resilience
    compress_grads: bool = False
    max_retries: int = 2            # per-step transient-failure retries
    straggler_factor: float = 3.0   # step > factor·EMA ⇒ flagged
    seed: int = 0


# ---------------------------------------------------------------------------
# Step construction
# ---------------------------------------------------------------------------

def _accumulate_grads(loss_fn, params, batch, n_micro: int):
    """Mean loss/grads over ``n_micro`` microbatches via lax.scan."""
    if n_micro == 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, grads

    def reshape(x):
        b = x.shape[0]
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    micro = jax.tree.map(reshape, batch)

    def body(carry, mb):
        loss_acc, grads_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        return (loss_acc + loss,
                jax.tree.map(jnp.add, grads_acc, grads)), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), micro)
    inv = 1.0 / n_micro
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def build_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                     abstract_params: Any,
                     batch_shapes: Dict[str, Any],
                     masks: Optional[Any] = None,
                     donate: bool = True,
                     profile: str = "tp") -> Tuple[Callable, Any, Any]:
    """→ (jitted step, param_specs, opt_specs).

    step(params, opt_state, batch) → (params, opt_state, metrics).
    ``profile``: "tp" (TP/EP over model) or "dp" (params replicated over
    model, batch sharded over it — small-model posture, §Perf cell A).
    """
    from repro.distributed.annotate import set_sharding_mode
    set_sharding_mode(profile)      # read at trace time by constrain()

    opt_cfg = AdamWConfig(lr=tcfg.lr, weight_decay=tcfg.weight_decay,
                          grad_clip=tcfg.grad_clip,
                          schedule=cosine_warmup(tcfg.warmup, tcfg.steps))
    pspecs = SH.param_specs(abstract_params, cfg, mesh, profile=profile)
    ospecs = SH.opt_state_specs(pspecs)
    bspecs = SH.batch_specs(batch_shapes, mesh,
                            extra_dp=(profile == "dp"))

    def loss_fn(params, batch):
        return MZ.model_loss(params, cfg, batch)

    def step(params, opt_state, batch):
        loss, grads = _accumulate_grads(loss_fn, params, batch,
                                        tcfg.microbatches)
        if tcfg.compress_grads:
            # int8 quantize/dequantize with error feedback carried in the
            # optimizer state; the quantized representation is what the
            # cross-pod reduce moves (see optim.compression docstring).
            err = opt_state.get("ef")
            if err is not None:
                grads = jax.tree.map(
                    lambda g, e: g.astype(jnp.float32) + e, grads, err)
            qs = jax.tree.map(compress_int8, grads)
            approx = jax.tree.map(
                lambda t: decompress_int8(t[0], t[1]),
                qs, is_leaf=lambda x: isinstance(x, tuple))
            new_err = jax.tree.map(
                lambda g, a: g.astype(jnp.float32) - a, grads, approx)
            grads = approx
        new_params, new_opt, metrics = adamw_update(
            opt_cfg, params, grads,
            {k: opt_state[k] for k in ("mu", "nu", "step")}, masks=masks)
        if tcfg.compress_grads:
            new_opt["ef"] = new_err
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    in_shardings = (SH.named(mesh, pspecs),
                    SH.named(mesh, _opt_shard_tree(ospecs, tcfg, pspecs,
                                                   mesh)),
                    SH.named(mesh, bspecs))
    out_shardings = (in_shardings[0], in_shardings[1], None)
    jit_step = jax.jit(
        step,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        donate_argnums=(0, 1) if donate else ())
    return jit_step, pspecs, ospecs


def _opt_shard_tree(ospecs, tcfg: TrainConfig, pspecs, mesh):
    tree = dict(ospecs)
    if tcfg.compress_grads:
        tree["ef"] = pspecs
    return tree


def init_opt_state(params: Any, tcfg: TrainConfig) -> dict:
    state = adamw_init(params)
    if tcfg.compress_grads:
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


# ---------------------------------------------------------------------------
# Fit loop (host-side resilience)
# ---------------------------------------------------------------------------

class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                 dcfg: DataConfig, masks: Optional[Any] = None):
        self.cfg, self.tcfg, self.mesh, self.dcfg = cfg, tcfg, mesh, dcfg
        self.masks = masks
        self.manager = (CheckpointManager(tcfg.checkpoint_dir,
                                          keep=tcfg.keep_checkpoints)
                        if tcfg.checkpoint_dir else None)
        self.history: list = []
        self.straggler_flags: list = []

    # -- initialization / restore ---------------------------------------

    def init_state(self) -> Tuple[Any, dict, int]:
        """Fresh or restored (params, opt_state, start_step)."""
        rng = jax.random.key(self.tcfg.seed)
        abstract = jax.eval_shape(lambda: MZ.init_model(rng, self.cfg))
        pspecs = SH.param_specs(abstract, self.cfg, self.mesh)
        pshard = SH.named(self.mesh, pspecs)

        if self.manager is not None:
            abstract_opt = jax.eval_shape(
                lambda: init_opt_state(
                    MZ.init_model(rng, self.cfg), self.tcfg))
            tmpl = {"params": abstract, "opt": abstract_opt}
            oshard = SH.named(
                self.mesh, _opt_shard_tree(SH.opt_state_specs(pspecs),
                                           self.tcfg, pspecs, self.mesh))
            restored = self.manager.restore_latest(
                tmpl, {"params": pshard, "opt": oshard})
            if restored is not None:
                tree, step = restored
                return tree["params"], tree["opt"], step

        with jax.set_mesh(self.mesh):
            params = jax.jit(
                lambda r: MZ.init_model(r, self.cfg),
                out_shardings=pshard)(rng)
            opt_state = jax.jit(
                lambda p: init_opt_state(p, self.tcfg),
                out_shardings=SH.named(
                    self.mesh, _opt_shard_tree(SH.opt_state_specs(pspecs),
                                               self.tcfg, pspecs,
                                               self.mesh)))(params)
        return params, opt_state, 0

    # -- main loop --------------------------------------------------------

    def fit(self, progress: Optional[Callable[[int, dict], None]] = None
            ) -> Tuple[Any, dict]:
        params, opt_state, start = self.init_state()
        shapes = {k: v for k, v in make_batch(
            self.cfg, self.dcfg, 0).items()}
        step_fn, _, _ = build_train_step(
            self.cfg, self.tcfg, self.mesh, jax.eval_shape(lambda: params),
            shapes, masks=self.masks)

        ema = None
        for step in range(start, self.tcfg.steps):
            batch = make_batch(self.cfg, self.dcfg, step)
            batch = SH.shard_batch(batch, self.mesh)

            for attempt in range(self.tcfg.max_retries + 1):
                t0 = time.perf_counter()
                try:
                    with jax.set_mesh(self.mesh):
                        params, opt_state, metrics = step_fn(
                            params, opt_state, batch)
                    jax.block_until_ready(metrics["loss"])
                    break
                except jax.errors.JaxRuntimeError:
                    # transient device failure: on a real fleet this is a
                    # preempted slice — recompile/retry, then restore
                    if attempt == self.tcfg.max_retries:
                        raise
            dt = time.perf_counter() - t0

            # straggler watchdog
            if ema is None:
                ema = dt
            if dt > self.tcfg.straggler_factor * ema and step > start + 2:
                self.straggler_flags.append(
                    {"step": step, "dt": dt, "ema": ema})
            ema = 0.9 * ema + 0.1 * dt

            m = {k: float(np.asarray(v)) for k, v in metrics.items()}
            m["step_time_s"] = dt
            self.history.append(m)
            if progress and step % self.tcfg.log_every == 0:
                progress(step, m)

            if (self.manager is not None and step + 1 > start
                    and (step + 1) % self.tcfg.checkpoint_every == 0):
                self.manager.save(step + 1,
                                  {"params": params, "opt": opt_state})

        if self.manager is not None:
            self.manager.save(self.tcfg.steps,
                              {"params": params, "opt": opt_state},
                              blocking=True)
        return params, opt_state
