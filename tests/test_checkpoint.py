"""Checkpoint store: roundtrip, bf16, atomicity, async overlap, GC, elasticity."""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint, save_checkpoint
from repro.launch.mesh import make_mesh


def _tree(key):
    return {
        "params": {
            "w": jax.random.normal(key, (8, 4)),
            "emb": (jax.random.normal(key, (16, 4)) * 0.1).astype(jnp.bfloat16),
        },
        "step": jnp.int32(7),
    }


def test_roundtrip_including_bf16(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 3, t)
    like = jax.eval_shape(lambda: t)
    r = restore_checkpoint(str(tmp_path), 3, like)
    for a, b in zip(jax.tree_util.tree_leaves(r), jax.tree_util.tree_leaves(t)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_latest_step_ignores_tmp(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 1, t)
    save_checkpoint(str(tmp_path), 5, t)
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert latest_step(str(tmp_path)) == 5
    assert latest_step(str(tmp_path / "missing")) is None


def test_restore_validates_shapes(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 1, t)
    bad = jax.eval_shape(lambda: {**t, "params": {**t["params"], "w": jnp.zeros((9, 4))}})
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_checkpoint(str(tmp_path), 1, bad)


def test_restore_missing_leaf(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 1, t)
    bigger = jax.eval_shape(lambda: {**t, "extra": jnp.zeros((2,))})
    with pytest.raises(KeyError):
        restore_checkpoint(str(tmp_path), 1, bigger)


def test_async_checkpointer_and_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    t = _tree(jax.random.PRNGKey(0))
    for s in (1, 2, 3, 4):
        ck.save(s, t)
    ck.wait()
    steps = sorted(
        int(n.split("_")[1]) for n in os.listdir(tmp_path) if n.startswith("step_") and "." not in n
    )
    assert steps == [3, 4]


def test_async_snapshot_isolated_from_mutation(tmp_path):
    """The snapshot must capture values at save() time even if buffers change."""
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    t = {"w": jnp.ones((4,))}
    ck.save(1, t)
    ck.wait()
    r = restore_checkpoint(str(tmp_path), 1, jax.eval_shape(lambda: t))
    np.testing.assert_array_equal(np.asarray(r["w"]), np.ones((4,)))


def test_elastic_restore_onto_mesh(tmp_path):
    """Restore with explicit shardings (any-mesh restart)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 2, t)
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    r = restore_checkpoint(str(tmp_path), 2, jax.eval_shape(lambda: t), shardings=sh)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t["w"]))
    assert r["w"].sharding == sh["w"]
