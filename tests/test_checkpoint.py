"""Checkpoint store: atomicity, integrity, retention, resharding."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, load_checkpoint,
                              restore_latest, save_checkpoint)
from repro.launch.mesh import make_mesh


def tree(seed=0):
    k = jax.random.key(seed)
    return {"a": jax.random.normal(k, (8, 4)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32),
                  "d": jnp.ones((3,), jnp.bfloat16)},
            "step": jnp.asarray(seed)}


class TestBasic:
    def test_roundtrip_exact(self):
        with tempfile.TemporaryDirectory() as d:
            t = tree(3)
            save_checkpoint(d, 3, t)
            got, step = restore_latest(d, jax.eval_shape(lambda: t))
            assert step == 3
            for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                assert a.dtype == b.dtype      # bf16 survives npz

    def test_latest_pointer(self):
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, tree(1))
            save_checkpoint(d, 2, tree(2))
            with open(os.path.join(d, "LATEST")) as f:
                assert f.read() == "step_000000002"

    def test_missing_dir_returns_none(self):
        assert restore_latest("/nonexistent/dir", tree()) is None


class TestIntegrity:
    def test_digest_detects_corruption(self):
        with tempfile.TemporaryDirectory() as d:
            path = save_checkpoint(d, 1, tree(1))
            # corrupt the npz
            npz = os.path.join(path, "arrays.npz")
            data = open(npz, "rb").read()
            with open(npz, "wb") as f:
                f.write(data[:-20] + b"\x00" * 20)
            with pytest.raises(Exception):
                load_checkpoint(path)

    def test_fallback_to_previous_step(self):
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, tree(1))
            path2 = save_checkpoint(d, 2, tree(2))
            os.remove(os.path.join(path2, "arrays.npz"))
            got, step = restore_latest(d, jax.eval_shape(lambda: tree(0)))
            assert step == 1


class TestManager:
    def test_async_save_and_retention(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2)
            for s in (1, 2, 3, 4):
                mgr.save(s, tree(s))
            mgr.wait()
            steps = sorted(x for x in os.listdir(d)
                           if x.startswith("step_"))
            assert steps == ["step_000000003", "step_000000004"]

    def test_async_error_surfaces(self):
        mgr = CheckpointManager("/proc/definitely/not/writable", keep=1)
        mgr.save(1, tree(1))
        with pytest.raises(BaseException):
            mgr.wait()


class TestResharding:
    def test_restore_onto_different_mesh(self):
        """Elasticity: save under one sharding, restore under another."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh_a = make_mesh()
        mesh_b = make_mesh((1,), ("data",))
        t = tree(7)
        with tempfile.TemporaryDirectory() as d:
            t_dev = jax.device_put(
                t, NamedSharding(mesh_a, P()))
            save_checkpoint(d, 5, t_dev)
            shardings = jax.tree.map(
                lambda _: NamedSharding(mesh_b, P()), t)
            got, step = restore_latest(d, jax.eval_shape(lambda: t),
                                       shardings=shardings)
            assert step == 5
            for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
