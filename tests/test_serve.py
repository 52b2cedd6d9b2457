"""Serving engines: LM batching equivalence, determinism, EOS trimming — and the
sketch-solve job-admission path (SolveServer.submit_solve)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import lm
from repro.serve import Engine, ServeConfig, SolveServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(max_batch=4):
    cfg = dataclasses.replace(
        get_config("granite-3-8b").reduced(), num_layers=2, d_model=32, d_ff=64,
        num_heads=2, num_kv_heads=1, head_dim=16, vocab_size=97,
    )
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, params, ServeConfig(max_batch=max_batch, max_len=64))
    return cfg, params, engine


def test_generate_shapes_and_determinism():
    _, _, engine = _setup()
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    a = engine.generate(prompts, max_new_tokens=6)
    b = engine.generate(prompts, max_new_tokens=6)
    assert a == b
    assert len(a) == 2 and all(len(o) == 6 for o in a)
    cfg = engine.cfg
    assert all(t < cfg.vocab_size for o in a for t in o)  # padded ids masked


def test_batched_equals_rectangular_single():
    """Greedy decode of equal-length prompts must not depend on batch packing."""
    _, _, engine = _setup()
    p1, p2 = [3, 1, 4, 1], [2, 7, 1, 8]
    both = engine.generate([p1, p2], max_new_tokens=5)
    solo1 = engine.generate([p1], max_new_tokens=5)
    solo2 = engine.generate([p2], max_new_tokens=5)
    assert both[0] == solo1[0]
    assert both[1] == solo2[0]


def test_multi_chunk_queue():
    _, _, engine = _setup(max_batch=2)
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]  # 3 engine batches
    outs = engine.generate(prompts, max_new_tokens=4)
    assert len(outs) == 5


def test_eos_trimming():
    cfg, params, _ = _setup()
    engine = Engine(cfg, params, ServeConfig(max_batch=2, max_len=64, eos_id=0))
    outs = engine.generate([[1, 2, 3]], max_new_tokens=8)
    row = outs[0]
    if 0 in row:
        assert row[-1] == 0 and 0 not in row[:-1]


# ------------------------------------------------------- sketch-solve admission


def _solve_problem(n=1024, d=16):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d))
    b = A @ jax.random.normal(jax.random.PRNGKey(1), (d,)) + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (n,)
    )
    return key, A, b


def test_submit_solve_deterministic_and_telemetry(tmp_path):
    """Repeat submissions of the same seeded job are bitwise-identical; each job
    leaves a complete telemetry record and the aggregate report sums them."""
    from repro import runtime as rt
    from repro.core import sketches as sk

    _, A, b = _solve_problem()
    spec = sk.SketchSpec("gaussian", 128)
    lat = rt.DropLatency(
        seed=19, inner=rt.LognormalLatency(seed=19, mean_s=0.4, sigma=0.6), drop_prob=0.2
    )
    server = SolveServer(
        latency=lat,
        config=rt.RuntimeConfig(deadline_s=0.5, max_retries=2, backoff_base_s=0.05),
    )
    p = tmp_path / "job0.jsonl"
    j0 = server.submit_solve(A, b, spec, q=8, seed=4, save_events=str(p))
    j1 = server.submit_solve(A, b, spec, q=8, seed=4)
    np.testing.assert_array_equal(j0.xbar, j1.xbar)
    assert j0.result.events.lines() == j1.result.events.lines()
    assert p.read_text().splitlines() == j0.result.events.lines()

    assert j0.job_id == 0 and j1.job_id == 1 and j0.backend == "thread"
    assert j0.summary["effective_q"] == j0.result.count
    np.testing.assert_array_equal(j0.realized_mask, j0.result.realized_mask)

    agg = server.telemetry()
    assert agg["jobs"] == 2 and agg["backend"] == "thread"
    assert agg["retries"] == 2 * j0.summary["retries"]
    assert agg["effective_q_mean"] == pytest.approx(j0.summary["effective_q"])
    assert [pj["job_id"] for pj in agg["per_job"]] == [0, 1]


def test_submit_solve_early_stop_and_rounds():
    """target_error + error_fn stop a multi-round job early; the error trace is
    monotone in arrivals and the stop is recorded in the job summary."""
    from repro import runtime as rt
    from repro.core import sketches as sk

    _, A, b = _solve_problem()
    spec = sk.SketchSpec("gaussian", 128)
    single = 16 / (128 - 16 - 1)  # Lemma 1 for d=16, m=128
    server = SolveServer(
        latency=rt.ConstantLatency(seed=0, value_s=0.1),
        config=rt.RuntimeConfig(deadline_s=10.0, max_retries=0, target_error=single / 8),
    )
    job = server.submit_solve(A, b, spec, q=16, rounds=2, error_fn="theory")
    assert job.summary["stopped_early"]
    assert job.result.count == 8 and job.result.submitted == 32
    assert server.telemetry()["stopped_early"] == 1


@pytest.mark.subprocess
def test_submit_solve_matches_masked_distributed_solve():
    """The serve path reproduces the synchronous mesh solve: submit_solve with a
    latency model == distributed_sketch_solve with the realized mask, for
    gaussian / sjlt (subprocess: 8-device mesh; rtol matches the runtime
    equivalence tests — engine averages in float64, psum in float32)."""
    script = textwrap.dedent(
        """
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
        import jax, jax.numpy as jnp, numpy as np
        from repro import runtime as rt
        from repro.core import distributed, sketches as sk
        from repro.serve import SolveServer

        key = jax.random.PRNGKey(0)
        n, d = 2048, 16
        A = jax.random.normal(key, (n, d))
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))

        for spec in [sk.SketchSpec("gaussian", 128), sk.SketchSpec("sjlt", 128, s=4)]:
            lat = rt.DropLatency(
                seed=13, inner=rt.LognormalLatency(seed=13, mean_s=0.5, sigma=0.6),
                drop_prob=0.2,
            )
            server = SolveServer(
                latency=lat, config=rt.RuntimeConfig(deadline_s=0.55, max_retries=0)
            )
            job = server.submit_solve(A, b, spec, q=8, key=key)
            mask = job.realized_mask
            assert 0 < mask.sum() < 8, (spec.kind, mask)
            xbar = distributed.distributed_sketch_solve(
                mesh, spec, key, A, b, straggler_mask=jnp.asarray(mask))
            np.testing.assert_allclose(
                np.asarray(xbar), job.xbar, rtol=1e-4, atol=1e-4, err_msg=spec.kind)
        print("SERVE_EQUIV_OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=900, env=env
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "SERVE_EQUIV_OK" in out.stdout
