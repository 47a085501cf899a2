"""Sharding profiles + activation annotations (§Perf machinery)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs as C
from repro import models as MZ
from repro.core.sparse_linear import sparsify_abstract
from repro.core.sparsity import NMPack
from repro.distributed import annotate, sharding as SH
from repro.launch.mesh import make_mesh


@pytest.fixture(autouse=True)
def reset_mode():
    annotate.set_sharding_mode("tp")
    yield
    annotate.set_sharding_mode("tp")


class TestAnnotate:
    def test_constrain_noop_off_mesh(self):
        x = jnp.ones((4, 4))
        y = annotate.constrain(x, "data", "model")
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_mode_switch(self):
        assert annotate.batch_axes() == ("pod", "data")
        assert annotate.seq_axis() == "model"
        annotate.set_sharding_mode("dp")
        assert annotate.batch_axes() == ("pod", "data", "model")
        assert annotate.seq_axis() is None
        with pytest.raises(ValueError):
            annotate.set_sharding_mode("nope")

    def test_constrain_under_mesh_drops_nondividing(self):
        mesh = make_mesh()

        def f(x):
            return annotate.constrain(x, "data", "model") * 2

        with jax.set_mesh(mesh):
            out = jax.jit(f)(jnp.ones((3, 5)))
        assert out.shape == (3, 5)


class TestDpProfile:
    def test_params_replicated_over_model(self):
        cfg = C.get("qwen3-0.6b")
        mesh = SH.abstract_mesh((16, 16), ("data", "model"))
        ab = jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg))
        tp = SH.param_specs(ab, cfg, mesh, profile="tp")
        dp = SH.param_specs(ab, cfg, mesh, profile="dp")
        leaves_tp = jax.tree.leaves(tp, is_leaf=lambda x: isinstance(x, P))
        leaves_dp = jax.tree.leaves(dp, is_leaf=lambda x: isinstance(x, P))
        assert any("model" in str(s) for s in leaves_tp)
        assert not any("model" in str(s) for s in leaves_dp)
        # FSDP (data) placement is preserved
        assert any("data" in str(s) for s in leaves_dp)

    def test_batch_extra_dp(self):
        mesh = SH.abstract_mesh((16, 16), ("data", "model"))
        shapes = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
        specs = SH.batch_specs(shapes, mesh, extra_dp=True)
        assert specs["tokens"][0] == ("data", "model")


class TestSparsifyAbstract:
    def test_mlp_weights_become_packs(self):
        cfg = C._module("qwen3-0.6b").sparse()
        ab = jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg))
        sp = sparsify_abstract(ab, cfg)
        w = sp["layers"]["mlp"]["w_in"]
        assert isinstance(w, NMPack)
        # leading layer-stack axis preserved on array leaves
        assert w.values.shape[0] == cfg.n_layers
        # compressed K: d_model * n/m
        assert w.values.shape[1] == cfg.d_model * 2 // 4
        # norms untouched
        assert not isinstance(sp["layers"]["ln_attn"]["scale"], NMPack)

    def test_geometry_guard_leaves_dense(self):
        import dataclasses
        from repro.core.sparse_linear import SparsityConfig
        cfg = dataclasses.replace(
            C.get_reduced("qwen3-0.6b"),
            mlp_sparsity=SparsityConfig(format="nm", n=2, m=4,
                                        block_n=999))   # N % 999 != 0
        ab = jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg))
        sp = sparsify_abstract(ab, cfg)
        assert not isinstance(sp["layers"]["mlp"]["w_in"], NMPack)

    def test_sparse_specs_validate(self):
        cfg = C._module("qwen3-0.6b").sparse()
        mesh = SH.abstract_mesh((16, 16), ("data", "model"))
        ab = jax.eval_shape(lambda: MZ.init_model(jax.random.key(0), cfg))
        sp = sparsify_abstract(ab, cfg)
        specs = SH.param_specs(sp, cfg, mesh)
        assert SH.validate_specs(sp, specs, mesh) == []


class TestAttentionLayoutRule:
    """C1: the layout rule itself is pure logic over (Hk, ext)."""

    def test_rule_selection(self):
        # mirrors the condition in models/attention.py
        def path(hk, ext):
            if hk % ext == 0:
                return "heads"
            if hk <= 2:
                return "mqa"
            return "auto"
        assert path(16, 16) == "heads"    # gemma2
        assert path(32, 16) == "heads"    # zamba2
        assert path(1, 16) == "mqa"       # gemma3
        assert path(8, 16) == "auto"      # qwen3/dbrx (kv-replicate would
        #                                   cost more than it saves)
