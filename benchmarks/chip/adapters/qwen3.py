"""Hands the benchmark's Qwen3 weights to the program under test.

``model_config`` maps the configuration file (Hugging Face keys plus the
sparsity recipe) onto the program's ``ModelConfig``; ``program_params``
renames the weights into the program's parameter tree, checks that tree
against the one ``models.init_model`` would build, and packs it with
``core.sparse_linear.pack_params`` (the program's own offline pass).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _sparsity(fam):
    from repro.core.sparse_linear import DENSE, SparsityConfig
    if not fam or fam.get("format", "dense") == "dense":
        return DENSE
    if fam["format"] == "nm":
        return SparsityConfig(format="nm", n=fam["n"], m=fam["m"],
                              block_n=fam["group"])
    if fam["format"] == "combined":
        return SparsityConfig(format="combined",
                              sparsity=fam["block_sparsity"], n=fam["n"],
                              m=fam["m"], block_k=fam["block"],
                              block_n=fam["group"])
    raise ValueError(f"unknown sparsity format {fam['format']!r}")


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    sp = cfg.get("sparsity", {})
    return ModelConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], vocab_size=cfg["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg.get("torch_dtype", "bfloat16"),
        attn_sparsity=_sparsity(sp.get("attention")),
        mlp_sparsity=_sparsity(sp.get("mlp")))


@jax.jit
def _rename(w):
    """Benchmark weights -> the program's dense parameter tree.  Norms
    use the program's ``(1 + scale)`` convention; the embedding is padded
    with zero rows to the program's padded vocabulary by the caller."""
    L = w["layers"]
    one = lambda a: {"scale": a - 1.0}                      # noqa: E731
    return {
        "embed": w["embed"],
        "layers": {
            "ln_attn": one(L["attn_norm"]), "ln_mlp": one(L["mlp_norm"]),
            "attn": {"wq": L["q"], "wk": L["k"], "wv": L["v"], "wo": L["o"],
                     "q_norm": one(L["q_norm"]), "k_norm": one(L["k_norm"])},
            "mlp": {"w_in": L["up"], "w_gate": L["gate"], "w_out": L["down"]},
        },
        "ln_final": one(w["final_norm"]),
    }


def program_params(weights: dict, mcfg):
    """Pack the weights as the program serves them."""
    from repro import models as MZ
    from repro.core.sparse_linear import pack_params
    dense = _rename(weights)
    pad = mcfg.vocab_padded - dense["embed"].shape[0]
    if pad:
        dense["embed"] = jnp.pad(dense["embed"], ((0, pad), (0, 0)))
    want = jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), mcfg))
    got = jax.eval_shape(lambda: dense)
    sig = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    if jax.tree.structure(want) != jax.tree.structure(got) \
            or sig(want) != sig(got):
        raise ValueError("the program's parameter tree no longer matches "
                         "the benchmark's Qwen3 weights")
    return pack_params(dense, mcfg)
