"""Multi-device SPMD tests — run in subprocesses so the 8 fake host devices never
leak into the main test process (jax locks device count at first init)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.subprocess  # every test here shells out to a fresh mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 900) -> str:
    script = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=timeout, env=env
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_sketch_solve_matches_local_average():
    _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed, sketches as sk, solve, averaging
        from repro.utils import prng

        key = jax.random.PRNGKey(0)
        n, d, m = 2048, 16, 128
        A = jax.random.normal(key, (n, d))
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        spec = sk.SketchSpec("gaussian", m)
        xbar = distributed.distributed_sketch_solve(mesh, spec, key, A, b)
        # reference: same worker keys, computed locally
        xs = jnp.stack([
            solve.sketch_and_solve(spec, prng.worker_key(key, w, 0), A, b) for w in range(8)
        ])
        np.testing.assert_allclose(np.asarray(xbar), np.asarray(xs.mean(0)), rtol=1e-4, atol=1e-4)

        # straggler mask: drop workers 0-3 -> average of 4-7 only
        mask = jnp.array([0., 0., 0., 0., 1., 1., 1., 1.])
        xbar_m = distributed.distributed_sketch_solve(mesh, spec, key, A, b, straggler_mask=mask)
        np.testing.assert_allclose(np.asarray(xbar_m), np.asarray(xs[4:].mean(0)), rtol=1e-4, atol=1e-4)

        # master-sketch mode (batched apply, one pass over A) == worker-sketch mode
        xbar_ms = distributed.distributed_sketch_solve_master(mesh, spec, key, A, b)
        np.testing.assert_allclose(np.asarray(xbar_ms), np.asarray(xs.mean(0)), rtol=1e-4, atol=1e-4)
        print("DIST_OK")
        """
    )


def test_distributed_least_norm_and_multiround():
    _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed, sketches as sk, solve
        key = jax.random.PRNGKey(0)
        n, d = 16, 256
        A = jax.random.normal(key, (n, d))
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        spec = sk.SketchSpec("gaussian", 4 * n)
        xbar = distributed.distributed_sketch_least_norm(mesh, spec, key, A, b)
        x_star = solve.least_norm(A, b)
        e1 = float(jnp.linalg.norm(xbar - x_star) / jnp.linalg.norm(x_star))
        assert e1 < 1.0, e1
        x2 = distributed.distributed_sketch_solve_multiround(
            mesh, sk.SketchSpec("gaussian", 128),
            key, jax.random.normal(key, (2048, 16)), jax.random.normal(key, (2048,)), rounds=3)
        assert np.isfinite(np.asarray(x2)).all()
        print("LN_OK")
        """
    )


def test_sketch_dp_training_step_runs():
    _run(
        """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.core import gradcomp
        from repro.data import lm_batch
        from repro.optim import AdamWConfig
        from repro.train.state import init_train_state
        from repro.train.sketch_dp import make_sketch_dp_step

        cfg = dataclasses.replace(get_config('granite-3-8b').reduced(),
                                  num_layers=2, d_model=32, d_ff=64, num_heads=2,
                                  num_kv_heads=1, head_dim=16, vocab_size=97)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        comp = gradcomp.GradCompressionConfig(enabled=True, ratio=0.1, kind='countsketch')
        step = make_sketch_dp_step(cfg, AdamWConfig(lr=1e-3), mesh, comp=comp)
        state = init_train_state(cfg, AdamWConfig(lr=1e-3), jax.random.PRNGKey(0))
        batch = lm_batch(0, 0, batch=8, seq=32, vocab=cfg.vocab_size)
        mask = jnp.array([1.,1.,1.,0.,1.,1.,1.,1.])  # one straggler dropped
        with mesh:
            state, metrics = step(state, batch, jax.random.PRNGKey(1), mask)
        assert np.isfinite(float(metrics['loss']))
        assert int(state['step']) == 1
        # uncompressed + full mask variant
        step2 = make_sketch_dp_step(cfg, AdamWConfig(lr=1e-3), mesh, comp=None)
        with mesh:
            state2, m2 = step2(state, batch, jax.random.PRNGKey(2), jnp.ones((8,)))
        assert np.isfinite(float(m2['loss']))
        print("SKETCH_DP_OK")
        """
    )


def test_sharded_train_step_compiles_on_mini_mesh():
    """The production train step (GSPMD path) on a 2x2x2 pod/data/model mini-mesh."""
    _run(
        """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_config
        from repro.data import lm_batch
        from repro.data.specs import batch_pspecs, input_specs
        from repro.distributed.sharding import ShardingRules
        from repro.optim import AdamWConfig
        from repro.train.state import init_train_state, train_state_pspecs
        from repro.train.step import make_train_step

        cfg = dataclasses.replace(get_config('granite-3-8b').reduced(),
                                  num_layers=2, d_model=32, d_ff=64, num_heads=4,
                                  num_kv_heads=2, head_dim=16, vocab_size=256)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = ShardingRules(dp=("pod", "data"), fsdp="data", tensor="model")
        opt = AdamWConfig(lr=1e-3)
        named = lambda tree: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P))
        state_sh = named(train_state_pspecs(cfg, opt, rules))
        step = make_train_step(cfg, opt, rules=rules)
        state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
        state = jax.device_put(state, state_sh)
        batch = lm_batch(0, 0, batch=8, seq=32, vocab=cfg.vocab_size)
        with mesh:
            jstep = jax.jit(step, in_shardings=(state_sh, None), out_shardings=(state_sh, None))
            state, metrics = jstep(state, batch)
        assert np.isfinite(float(metrics['loss']))
        # one more step to prove the state shardings round-trip
        with mesh:
            state, metrics = jstep(state, batch)
        assert int(state['step']) == 2
        print("GSPMD_OK")
        """
    )


def test_elastic_checkpoint_rescale():
    """Save on an 8-way mesh, restore onto a 4-way mesh (different dp width)."""
    _run(
        """
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint

        from repro.launch.mesh import make_mesh

        mesh8 = make_mesh((8,), ("data",))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, {"w": xs})

        mesh4 = make_mesh((4, 2), ("data", "model"))
        sh = {"w": NamedSharding(mesh4, P("data", "model"))}
        r = restore_checkpoint(d, 1, jax.eval_shape(lambda: {"w": x}), shardings=sh)
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(x))
        assert r["w"].sharding == sh["w"]
        print("ELASTIC_OK")
        """
    )
