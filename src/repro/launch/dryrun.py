import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell and extract the roofline terms (deliverables e + g).

The lines above MUST precede any other import (jax locks the platform
and device count on first init): the dry-run — and only the dry-run —
sees 512 placeholder CPU devices so ``make_production_mesh`` can build
the real 16×16 (single-pod) and 2×16×16 (multi-pod) meshes.  The CPU pin
is inherited by the per-cell child processes, so neither this process
nor its children ever claim an attached TPU.

Per cell this driver:
  1. builds the abstract params / optimizer / batch / cache pytrees
     (ShapeDtypeStruct — no allocation),
  2. resolves sharding specs (distributed.sharding) and preflights
     divisibility,
  3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
  4. records ``memory_analysis()`` (bytes/device — proves it fits),
     ``cost_analysis()`` (FLOPs/bytes — roofline numerators), and the
     collective operand bytes parsed from the compiled HLO,
  5. writes one JSON per cell under results/dryrun/.

Cost-analysis convention (verified): the compiled SPMD module is the
per-device program, so flops / bytes / collective sums are **per chip**;
roofline terms divide by per-chip peaks directly (v5e: 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI).

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --cell train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs 1]
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict

from repro.launch.analysis import (hlo_collective_bytes, memory_traffic,
                                   step_flops)

# v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link


def _tree_bytes(tree: Any) -> int:
    import numpy as np
    total = 0
    for leaf in __import__("jax").tree.leaves(tree):
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


def _tree_bytes_sharded(tree: Any, specs: Any, mesh) -> int:
    """Per-device bytes of a spec-sharded pytree."""
    import jax
    import numpy as np
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0
    flat_t = jax.tree.leaves(tree)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index") or
                             x is None or
                             type(x).__name__ == "PartitionSpec")
    for leaf, spec in zip(flat_t, flat_s):
        n = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        shards = 1
        if spec is not None:
            for ax in tuple(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                for a in axes:
                    shards *= sizes.get(a, 1)
        total += n // max(shards, 1)
    return total


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------

def build_cell(arch: str, cell_name: str, multi_pod: bool,
               profile: str = "tp", microbatches: int = 0,
               remat_policy: str = "", sparse: bool = False):
    """→ (jitted fn, abstract args tuple, meta dict).  Heavy imports are
    deferred so `--all` orchestration stays light."""
    import jax
    import jax.numpy as jnp

    from repro import configs as C
    from repro import models as MZ
    from repro.data import input_specs_for_batch
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_production_mesh, mesh_chips
    from repro.serving import ServeConfig, build_decode_step, \
        build_prefill_step
    from repro.train import TrainConfig, build_train_step
    from repro.train.trainer import init_opt_state

    cfg = C._module(arch).sparse() if sparse else C.get(arch)
    if remat_policy:
        import dataclasses
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    cell = C.CELLS[cell_name]
    if cell_name == "long_500k" and not cfg.sub_quadratic:
        raise ValueError(f"{arch} is full-attention; long_500k skipped "
                         "(DESIGN.md §5)")
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)

    rng = jax.random.key(0)
    abstract_params = jax.eval_shape(lambda: MZ.init_model(rng, cfg))
    if sparse:
        from repro.core.sparse_linear import sparsify_abstract
        abstract_params = sparsify_abstract(abstract_params, cfg)
    pspecs = SH.param_specs(abstract_params, cfg, mesh, profile=profile)
    problems = SH.validate_specs(abstract_params, pspecs, mesh)
    if problems:
        raise ValueError(f"param spec problems: {problems[:5]}")

    meta = {
        "arch": cfg.name, "cell": cell_name, "kind": cell.kind,
        "seq": cell.seq, "batch": cell.batch, "chips": chips,
        "mesh": dict(mesh.shape),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "param_bytes_global": _tree_bytes(abstract_params),
        "param_bytes_pd": _tree_bytes_sharded(abstract_params, pspecs, mesh),
        "cache_bytes_pd": 0, "opt_bytes_pd": 0, "microbatches": 1,
    }

    if cell.kind == "train":
        n_micro = microbatches or cell.microbatches
        tcfg = TrainConfig(steps=1000, microbatches=n_micro,
                           compress_grads=multi_pod)
        batch = input_specs_for_batch(cfg, cell.batch, cell.seq)
        abstract_opt = jax.eval_shape(
            lambda: init_opt_state(MZ.init_model(rng, cfg), tcfg))
        step, _, ospecs = build_train_step(cfg, tcfg, mesh, abstract_params,
                                           batch, donate=True,
                                           profile=profile)
        args = (abstract_params, abstract_opt, batch)
        meta["tokens_per_step"] = cell.batch * cell.seq
        meta["microbatches"] = n_micro
        meta["opt_bytes_pd"] = _tree_bytes_sharded(
            {k: abstract_opt[k] for k in ("mu", "nu")},
            {k: ospecs[k] for k in ("mu", "nu")}, mesh)
        return mesh, step, args, meta

    scfg = ServeConfig(slots=cell.batch, max_len=cell.seq,
                       prompt_pad=cell.seq, kv_mode="auto")
    src_len = cell.seq if cfg.is_encoder_decoder else None
    abstract_cache = jax.eval_shape(
        lambda: MZ.init_cache(cfg, cell.batch, cell.seq, src_len=src_len))
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode="auto")
    problems = SH.validate_specs(abstract_cache, cspecs, mesh)
    if problems:
        raise ValueError(f"cache spec problems: {problems[:5]}")
    meta["cache_bytes_global"] = _tree_bytes(abstract_cache)
    meta["cache_bytes_pd"] = _tree_bytes_sharded(abstract_cache, cspecs,
                                                 mesh)

    if cell.kind == "prefill":
        batch = input_specs_for_batch(cfg, cell.batch, cell.seq,
                                      src_len=src_len)
        batch.pop("labels", None)
        step = build_prefill_step(cfg, mesh, scfg, abstract_params,
                                  abstract_cache, batch)
        args = (abstract_params, batch, abstract_cache)
        meta["tokens_per_step"] = cell.batch * cell.seq
        return mesh, step, args, meta

    # decode: one new token against a seq_len cache
    step = build_decode_step(cfg, mesh, scfg, abstract_params,
                             abstract_cache)
    token = jax.ShapeDtypeStruct((cell.batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    args = (abstract_params, token, abstract_cache, pos)
    meta["tokens_per_step"] = cell.batch
    return mesh, step, args, meta


def run_cell(arch: str, cell_name: str, multi_pod: bool,
             profile: str = "tp", microbatches: int = 0,
             remat_policy: str = "", sparse: bool = False) -> Dict[str, Any]:
    t0 = time.time()
    mesh, step, args, meta = build_cell(arch, cell_name, multi_pod, profile,
                                        microbatches, remat_policy, sparse)
    meta["profile"] = profile
    meta["sparse"] = sparse
    with jax.set_mesh(mesh):
        lowered = step.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        # exact program FLOPs from the jaxpr (scan lengths are static
        # there; XLA cost analysis counts while bodies once — see
        # launch/analysis.py)
        flops_global = step_flops(step, *args)

    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    mem["total_per_device"] = (mem["argument_size_in_bytes"]
                               + mem["output_size_in_bytes"]
                               + mem["temp_size_in_bytes"])
    ca = compiled.cost_analysis() or {}
    coll = hlo_collective_bytes(compiled.as_text())

    chips = meta["chips"]
    flops_pd = flops_global / chips
    traffic_pd = memory_traffic(
        param_bytes_pd=meta["param_bytes_pd"],
        temp_bytes_pd=mem["temp_size_in_bytes"],
        cache_bytes_pd=meta["cache_bytes_pd"],
        opt_bytes_pd=meta["opt_bytes_pd"],
        microbatches=meta["microbatches"])
    t_comp = flops_pd / PEAK_FLOPS
    t_mem = traffic_pd / HBM_BW
    t_coll = coll["total_bytes"] / ICI_BW
    dominant = max((("compute", t_comp), ("memory", t_mem),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]

    # useful-compute ratio
    toks = meta["tokens_per_step"]
    n_active = meta["active_params"]
    model_flops = (6 if meta["kind"] == "train" else 2) * n_active * toks
    ratio = model_flops / flops_global if flops_global else 0.0

    rec = dict(meta)
    rec.update({
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "flops_per_device": flops_pd,
        "flops_global": flops_global,
        "hbm_traffic_pd": traffic_pd,
        "xla_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "roofline": {
            "t_compute_s": t_comp,
            "t_memory_s": t_mem,
            "t_collective_s": t_coll,
            "dominant": dominant,
            "model_flops": model_flops,
            "useful_flop_ratio": ratio,
            "bound_step_s": max(t_comp, t_mem, t_coll),
        },
    })
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _out_path(out_dir: str, arch: str, cell: str, multi_pod: bool) -> str:
    sub = "multipod" if multi_pod else "singlepod"
    d = os.path.join(out_dir, sub)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{cell}.json")


def run_all(out_dir: str, multi_pod: bool, timeout: int,
            archs=None, cells=None) -> int:
    """Spawn one subprocess per cell (isolates failures + XLA state)."""
    from repro import configs as C
    failures = 0
    arch_list = archs or C.list_archs()
    for arch in arch_list:
        cfg = C.get(arch)
        for cell in C.cells_for(cfg):
            if cells and cell.name not in cells:
                continue
            path = _out_path(out_dir, cfg.name, cell.name, multi_pod)
            if os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        print(f"[skip] {cfg.name} × {cell.name} (done)")
                        continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--cell", cell.name, "--out", out_dir]
            if multi_pod:
                cmd.append("--multi-pod")
            print(f"[run ] {cfg.name} × {cell.name} "
                  f"({'multi' if multi_pod else 'single'}-pod)", flush=True)
            try:
                r = subprocess.run(cmd, timeout=timeout,
                                   capture_output=True, text=True)
                if r.returncode != 0:
                    failures += 1
                    err = (r.stderr or "")[-2000:]
                    with open(path, "w") as f:
                        json.dump({"ok": False, "arch": arch,
                                   "cell": cell.name, "error": err}, f)
                    print(f"[FAIL] {cfg.name} × {cell.name}:\n{err[-500:]}")
                else:
                    with open(path) as f:
                        rec = json.load(f)
                    rl = rec["roofline"]
                    print(f"[ ok ] {cfg.name} × {cell.name}: "
                          f"compile={rec['compile_s']}s "
                          f"mem/dev={rec['memory']['total_per_device']/2**30:.2f}GiB "
                          f"dominant={rl['dominant']} "
                          f"step≥{rl['bound_step_s']*1e3:.2f}ms", flush=True)
            except subprocess.TimeoutExpired:
                failures += 1
                with open(path, "w") as f:
                    json.dump({"ok": False, "arch": arch, "cell": cell.name,
                               "error": f"timeout {timeout}s"}, f)
                print(f"[TIME] {cfg.name} × {cell.name}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--archs", nargs="*", default=None)
    ap.add_argument("--profile", default="tp", choices=["tp", "dp"])
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--remat-policy", default="")
    ap.add_argument("--sparse", action="store_true",
                    help="lower the paper-technique (packed sparse) config")
    args = ap.parse_args()

    if args.all:
        return 1 if run_all(args.out, args.multi_pod, args.timeout,
                            archs=args.archs, cells=args.cells) else 0

    if not args.arch or not args.cell:
        ap.error("--arch and --cell required (or --all)")
    try:
        rec = run_cell(args.arch, args.cell, args.multi_pod,
                       profile=args.profile,
                       microbatches=args.microbatches,
                       remat_policy=args.remat_policy,
                       sparse=args.sparse)
    except Exception:
        traceback.print_exc()
        return 1
    cell_tag = (args.cell if args.profile == "tp"
                else f"{args.cell}__{args.profile}")
    path = _out_path(args.out, rec["arch"], cell_tag, args.multi_pod)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({k: rec[k] for k in
                      ("arch", "cell", "chips", "compile_s")}))
    print(json.dumps(rec["roofline"], indent=1))
    print(f"memory/device: "
          f"{rec['memory']['total_per_device'] / 2**30:.2f} GiB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
