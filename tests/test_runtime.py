"""Runtime subsystem: deterministic replay, retries, early stop, mask equivalence,
all-straggler contract, multiround trace hoisting, trainer delegation, and the
cross-backend determinism contract (inline == thread == process, any pool width)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as rt
from repro.core import distributed, sketches as sk, solve
from repro.launch.mesh import make_mesh
from repro.utils import prng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ latency models


def test_latency_models_deterministic_and_distinct():
    for model in (
        rt.LognormalLatency(seed=3, mean_s=0.5, sigma=0.4),
        rt.HeavyTailLatency(seed=3, scale_s=0.5, alpha=1.5),
        rt.DropLatency(seed=3, inner=rt.LognormalLatency(seed=3), drop_prob=0.3),
    ):
        a = model.sample_wave(16, round_id=2)
        b = model.sample_wave(16, round_id=2)
        np.testing.assert_array_equal(a, b)  # pure function of the coordinate
        assert not np.array_equal(a, model.sample_wave(16, round_id=3))
        # retries are fresh draws, not replays
        assert not np.array_equal(a, model.sample_wave(16, round_id=2, attempt=1))


def test_drop_latency_rate_and_inner_stream():
    inner = rt.LognormalLatency(seed=9, mean_s=1.0, sigma=0.2)
    drop = rt.DropLatency(seed=9, inner=inner, drop_prob=0.4)
    wave = drop.sample_wave(512)
    frac_inf = np.isinf(wave).mean()
    assert 0.3 < frac_inf < 0.5
    # surviving draws equal the inner model's draws (distinct salt, same stream)
    finite = ~np.isinf(wave)
    np.testing.assert_array_equal(wave[finite], inner.sample_wave(512)[finite])


def test_lognormal_quantile_matches_empirical():
    model = rt.LognormalLatency(seed=1, mean_s=2.0, sigma=0.5)
    cut = model.quantile(0.8)
    frac = (model.sample_wave(4096) <= cut).mean()
    assert abs(frac - 0.8) < 0.03


# ------------------------------------------------------------------ engine core


def _toy_problem(n=512, d=8):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d))
    b = A @ jax.random.normal(jax.random.PRNGKey(1), (d,)) + 0.5 * jax.random.normal(
        jax.random.PRNGKey(2), (n,)
    )
    return key, A, b


def test_engine_deterministic_replay(tmp_path):
    """Same seed ⇒ byte-identical event log and bitwise-identical x̄."""
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    lat = rt.DropLatency(
        seed=11, inner=rt.LognormalLatency(seed=11, mean_s=0.4, sigma=0.6), drop_prob=0.2
    )
    cfg = rt.RuntimeConfig(deadline_s=0.5, max_retries=2, backoff_base_s=0.05)

    runs = [
        rt.serverless_sketch_solve(spec, key, A, b, q=8, latency=lat, config=cfg)
        for _ in range(2)
    ]
    assert runs[0].events.lines() == runs[1].events.lines()
    np.testing.assert_array_equal(runs[0].xbar, runs[1].xbar)
    assert runs[0].arrived == runs[1].arrived

    # JSONL round-trips through disk unchanged
    p = tmp_path / "events.jsonl"
    runs[0].events.to_jsonl(str(p))
    assert p.read_text().splitlines() == runs[0].events.lines()


def test_engine_welford_average_is_exact_masked_mean():
    """The streaming average equals the plain mean of exactly the arrived results."""
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    lat = rt.LognormalLatency(seed=5, mean_s=0.4, sigma=0.7)
    cfg = rt.RuntimeConfig(deadline_s=0.45, max_retries=0)
    res = rt.serverless_sketch_solve(spec, key, A, b, q=16, latency=lat, config=cfg)
    assert 0 < res.count < 16  # deadline at ~median: some arrive, some miss
    xs = np.stack(
        [
            np.asarray(solve.sketch_and_solve(spec, prng.worker_key(key, w, r), A, b))
            for (w, r, _) in res.arrived
        ]
    )
    np.testing.assert_allclose(res.xbar, xs.mean(0), rtol=1e-6, atol=1e-6)
    # realized_mask marks exactly the attempt-0 arrivals
    assert res.realized_mask.sum() == res.count


def test_engine_retries_are_fresh_rounds():
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    # median 1.0 » deadline: most first attempts time out, retries eventually land
    lat = rt.LognormalLatency(seed=21, mean_s=1.0, sigma=1.5)
    cfg = rt.RuntimeConfig(deadline_s=0.6, max_retries=4, backoff_base_s=0.1)
    res = rt.serverless_sketch_solve(spec, key, A, b, q=8, latency=lat, config=cfg)

    counts = res.events.counts()
    assert counts.get("timeout", 0) > 0 and counts.get("retry", 0) > 0
    assert res.dispatched == 8 + counts["retry"]
    # every retried attempt carries a round_id outside the initial wave's range,
    # and no (worker, round) coordinate is ever dispatched twice — new i.i.d.
    # sketches, never replays
    dispatches = [ev for ev in res.events if ev.kind == "dispatch"]
    coords = [(ev.worker_id, ev.round_id) for ev in dispatches]
    assert len(coords) == len(set(coords))
    assert all(ev.round_id >= 1 for ev in dispatches if ev.attempt > 0)
    # backoff: the attempt-(a+1) dispatch happens strictly after attempt a timed out
    t_timeout = {(ev.task_id, ev.attempt): ev.t for ev in res.events if ev.kind == "timeout"}
    for ev in dispatches:
        if ev.attempt > 0:
            assert ev.t > t_timeout[(ev.task_id, ev.attempt - 1)]


def test_engine_early_stop_on_theory_target():
    key, A, b = _toy_problem(n=1024, d=16)
    spec = sk.SketchSpec("gaussian", 128)
    single = 16 / (128 - 16 - 1)  # Lemma 1
    target = single / 8  # reachable after exactly 8 arrivals
    cfg = rt.RuntimeConfig(deadline_s=10.0, max_retries=0, target_error=target)
    res = rt.serverless_sketch_solve(
        spec, key, A, b, q=32,
        latency=rt.ConstantLatency(seed=0, value_s=0.1),
        config=cfg, error_fn="theory",
    )
    assert res.stopped_early
    assert res.count == 8 and res.submitted == 32
    assert res.final_error <= target
    counts = res.events.counts()
    assert counts["stop"] == 1 and counts["cancel"] == 32 - 8


def test_engine_all_dropped_raises():
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    lat = rt.DropLatency(seed=0, inner=rt.ConstantLatency(value_s=0.1), drop_prob=1.0)
    eng = rt.ServerlessEngine(
        rt.make_sketch_solve_compute(spec, key, A, b), lat, rt.RuntimeConfig(max_retries=1)
    )
    with pytest.raises(RuntimeError, match="no worker result"):
        eng.run(q=4)


def test_engine_summary_and_error_trace():
    key, A, b = _toy_problem(n=1024, d=16)
    spec = sk.SketchSpec("gaussian", 128)
    cfg = rt.RuntimeConfig(deadline_s=10.0, max_retries=0)
    res = rt.serverless_sketch_solve(
        spec, key, A, b, q=8,
        latency=rt.LognormalLatency(seed=2, mean_s=0.3), config=cfg, error_fn="probe",
    )
    trace = res.events.error_trace()
    assert len(trace) == res.count == 8
    ts = [t for t, _, _ in trace]
    assert ts == sorted(ts)  # arrival order = simulated time order
    assert trace[-1][1] == 8
    s = res.summary(deadline=cfg.deadline_s)
    assert s["effective_q"] == 8 and s["count"] == 8
    assert s["p50_latency_s"] <= s["p95_latency_s"]
    hb = s["heartbeat"]
    assert hb["effective_q"] == 8.0 and "p50_runtime" in hb


# ------------------------------------------------------------ executor backends


def _backend_scenario():
    """A run with drops, timeouts, and retries — the kind of schedule where a
    backend that leaked wall-clock ordering into the event log would diverge."""
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    lat = rt.DropLatency(
        seed=23, inner=rt.LognormalLatency(seed=23, mean_s=0.4, sigma=0.6), drop_prob=0.2
    )
    return key, A, b, spec, lat


def test_backend_inline_matches_thread():
    """Same seed ⇒ byte-identical event log + bitwise x̄ on inline vs thread."""
    key, A, b, spec, lat = _backend_scenario()
    cfg = rt.RuntimeConfig(deadline_s=0.5, max_retries=2, backoff_base_s=0.05)
    runs = {
        kind: rt.serverless_sketch_solve(
            spec, key, A, b, q=8, latency=lat, config=cfg, backend=kind
        )
        for kind in ("inline", "thread")
    }
    assert runs["inline"].events.lines() == runs["thread"].events.lines()
    np.testing.assert_array_equal(runs["inline"].xbar, runs["thread"].xbar)
    assert runs["inline"].arrived == runs["thread"].arrived


def test_backend_thread_pool_width_is_invisible():
    """Event order comes from the simulated clock, never thread scheduling: a
    1-wide and an 8-wide pool replay the identical run."""
    key, A, b, spec, lat = _backend_scenario()
    runs = [
        rt.serverless_sketch_solve(
            spec, key, A, b, q=8, latency=lat,
            config=rt.RuntimeConfig(
                deadline_s=0.5, max_retries=2, backoff_base_s=0.05, max_threads=width
            ),
        )
        for width in (1, 8)
    ]
    assert runs[0].events.lines() == runs[1].events.lines()
    np.testing.assert_array_equal(runs[0].xbar, runs[1].xbar)


@pytest.mark.slow
@pytest.mark.subprocess
def test_backend_process_matches_inline_across_pool_sizes():
    """The process backend (real OS worker processes, spawn) replays the same
    bytes as inline, for 1- and 2-wide pools — the acceptance contract."""
    key, A, b, spec, lat = _backend_scenario()
    cfg = rt.RuntimeConfig(deadline_s=0.5, max_retries=2, backoff_base_s=0.05)
    ref = rt.serverless_sketch_solve(
        spec, key, A, b, q=8, latency=lat, config=cfg, backend="inline"
    )
    import dataclasses

    for width in (1, 2):
        res = rt.serverless_sketch_solve(
            spec, key, A, b, q=8, latency=lat,
            config=dataclasses.replace(cfg, max_threads=width), backend="process",
        )
        assert res.events.lines() == ref.events.lines(), f"pool width {width}"
        np.testing.assert_array_equal(res.xbar, ref.xbar)


def test_engine_reuses_caller_owned_backend_instance():
    """An ExecutorBackend instance passes through make_backend untouched and the
    engine never shuts it down — it survives (and replays across) multiple runs."""
    key, A, b, spec, lat = _backend_scenario()
    compute = rt.make_sketch_solve_compute(spec, key, A, b)
    shared = rt.ThreadBackend(compute, max_workers=2)
    assert rt.make_backend(shared, compute) is shared
    cfg = rt.RuntimeConfig(deadline_s=0.5, max_retries=1)
    eng = rt.ServerlessEngine(compute, lat, cfg, backend=shared)
    a, bb = eng.run(q=4), eng.run(q=4)
    assert a.events.lines() == bb.events.lines()
    shared.shutdown()


# ---------------------------------------------------------- adaptive deadlines


def test_adaptive_deadline_recovers_from_misset_static():
    """A static deadline below the latency median burns its retry budget on
    timeouts; the adaptive policy reads the timeout stream, escalates past the
    median, and lands strictly more results with fewer timeouts."""
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    lat = rt.LognormalLatency(seed=11, mean_s=1.0, sigma=0.4)
    cfg = rt.RuntimeConfig(deadline_s=0.6, max_retries=3, backoff_base_s=0.05)
    static = rt.serverless_sketch_solve(spec, key, A, b, q=8, latency=lat, config=cfg)
    adaptive = [
        rt.serverless_sketch_solve(
            spec, key, A, b, q=8, latency=lat, config=cfg,
            deadline=rt.AdaptiveDeadline(warmup_s=0.6, min_samples=3),
        )
        for _ in range(2)
    ]
    assert adaptive[0].count > static.count
    assert (
        adaptive[0].events.counts().get("timeout", 0)
        < static.events.counts().get("timeout", 0)
    )
    # the adaptive tracker sits inside the replay loop: still fully deterministic
    assert adaptive[0].events.lines() == adaptive[1].events.lines()
    np.testing.assert_array_equal(adaptive[0].xbar, adaptive[1].xbar)
    # dispatch events carry the effective deadline; retries escalate beyond warmup
    dls = [
        ev.extra["deadline_s"]
        for ev in adaptive[0].events
        if ev.kind == "dispatch" and ev.attempt > 0
    ]
    assert dls and max(dls) > 0.6


def test_deadline_policy_resolution_and_float_shorthand():
    cfg = rt.RuntimeConfig(deadline_s=0.7)
    assert rt.resolve_deadline_policy(None, cfg).start().current() == 0.7
    assert rt.resolve_deadline_policy(1.3, cfg).start().current() == 1.3
    pol = rt.AdaptiveDeadline(warmup_s=2.0)
    assert rt.resolve_deadline_policy(pol, cfg) is pol
    assert pol.start().current() == 2.0  # warm-up before min_samples


def test_straggler_policy_bridges_to_deadline_policy():
    from repro.distributed.fault_tolerance import StragglerPolicy

    pol = StragglerPolicy(deadline_quantile=0.8, seed=0)
    static = pol.to_deadline_policy(mean_s=1.0, sigma=0.35)
    assert isinstance(static, rt.StaticDeadline)
    expected = rt.LognormalLatency(mean_s=1.0, sigma=0.35).quantile(0.8)
    assert static.deadline_s == pytest.approx(expected)
    adaptive = pol.to_deadline_policy(mean_s=1.0, sigma=0.35, adaptive=True)
    assert isinstance(adaptive, rt.AdaptiveDeadline)
    assert adaptive.warmup_s == pytest.approx(expected)
    assert adaptive.quantile == 0.8
    # keep-everyone policy: infinite static cutoff, finite adaptive warm-up
    keep = StragglerPolicy(deadline_quantile=1.0)
    import math

    assert math.isinf(keep.to_deadline_policy().deadline_s)
    assert math.isfinite(keep.to_deadline_policy(adaptive=True).warmup_s)


# -------------------------------------------------- runtime vs synchronous mesh


@pytest.mark.subprocess
def test_runtime_matches_masked_distributed_solve():
    """Async run with latency injection == distributed_sketch_solve with the
    realized mask, for gaussian / sjlt / hybrid (subprocess: 8-device mesh)."""
    script = textwrap.dedent(
        """
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
        import jax, jax.numpy as jnp, numpy as np
        from repro import runtime as rt
        from repro.core import distributed, sketches as sk

        key = jax.random.PRNGKey(0)
        n, d = 2048, 16
        A = jax.random.normal(key, (n, d))
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))

        for spec in [
            sk.SketchSpec("gaussian", 128),
            sk.SketchSpec("sjlt", 128, s=4),
            sk.SketchSpec("hybrid", 128, m_prime=512),
        ]:
            lat = rt.DropLatency(
                seed=13, inner=rt.LognormalLatency(seed=13, mean_s=0.5, sigma=0.6),
                drop_prob=0.2,
            )
            cfg = rt.RuntimeConfig(deadline_s=0.55, max_retries=0)
            res = rt.serverless_sketch_solve(spec, key, A, b, q=8, latency=lat, config=cfg)
            mask = res.realized_mask
            assert 0 < mask.sum() < 8, (spec.kind, mask)
            xbar = distributed.distributed_sketch_solve(
                mesh, spec, key, A, b, straggler_mask=jnp.asarray(mask))
            np.testing.assert_allclose(
                np.asarray(xbar), res.xbar, rtol=1e-4, atol=1e-4,
                err_msg=spec.kind)
        print("RUNTIME_EQUIV_OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=900, env=env
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "RUNTIME_EQUIV_OK" in out.stdout


# ------------------------------------------------------- all-straggler contract


def _small_lsq():
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (256, 8))
    b = jax.random.normal(jax.random.PRNGKey(1), (256,))
    return key, A, b


def test_all_straggler_eager_mask_raises():
    key, A, b = _small_lsq()
    mesh = make_mesh((1,), ("data",))
    spec = sk.SketchSpec("gaussian", 64)
    zero = jnp.zeros((1,), jnp.float32)
    for call in (
        lambda: distributed.distributed_sketch_solve(mesh, spec, key, A, b, straggler_mask=zero),
        lambda: distributed.distributed_sketch_solve_master(mesh, spec, key, A, b, straggler_mask=zero),
        lambda: distributed.distributed_sketch_solve_master(
            mesh, spec, key, A, b, straggler_mask=zero, method="qr"
        ),
        lambda: distributed.distributed_sketch_least_norm(
            mesh, sk.SketchSpec("gaussian", 32), key, A[:4, :], b[:4], straggler_mask=zero
        ),
    ):
        with pytest.raises(ValueError, match="no surviving workers"):
            call()


def test_all_straggler_traced_mask_nan_poisons():
    key, A, b = _small_lsq()
    mesh = make_mesh((1,), ("data",))
    spec = sk.SketchSpec("gaussian", 64)
    zero = jnp.zeros((1,), jnp.float32)
    ones = jnp.ones((1,), jnp.float32)

    f = jax.jit(
        lambda m: distributed.distributed_sketch_solve(mesh, spec, key, A, b, straggler_mask=m)
    )
    assert np.isnan(np.asarray(f(zero))).all()
    assert np.isfinite(np.asarray(f(ones))).all()  # non-empty rounds unaffected

    f_zero = jax.jit(
        lambda m: distributed.distributed_sketch_solve(
            mesh, spec, key, A, b, straggler_mask=m, on_empty="zero"
        )
    )
    np.testing.assert_array_equal(np.asarray(f_zero(zero)), 0.0)  # legacy opt-in

    f_master = jax.jit(
        lambda m: distributed.distributed_sketch_solve_master(
            mesh, spec, key, A, b, straggler_mask=m
        )
    )
    assert np.isnan(np.asarray(f_master(zero))).all()

    An, bn = A[:4, :], b[:4]  # n=4 < d=8 for the least-norm variant
    f_ln = jax.jit(
        lambda m: distributed.distributed_sketch_least_norm(
            mesh, sk.SketchSpec("gaussian", 32), key, An, bn, straggler_mask=m
        )
    )
    assert np.isnan(np.asarray(f_ln(zero))).all()


# --------------------------------------------------------- multiround hoisting


def test_multiround_traces_once_and_matches_reference():
    key, A, b = _small_lsq()
    mesh = make_mesh((1,), ("data",))
    spec = sk.SketchSpec("gaussian", 64)
    rounds = 4

    before = distributed.MULTIROUND_TRACE_COUNT
    xbar = distributed.distributed_sketch_solve_multiround(
        mesh, spec, key, A, b, rounds=rounds
    )
    assert distributed.MULTIROUND_TRACE_COUNT == before + 1  # 1 trace, not `rounds`

    xs = np.stack(
        [
            np.asarray(solve.sketch_and_solve(spec, prng.worker_key(key, 0, r), A, b))
            for r in range(rounds)
        ]
    )
    np.testing.assert_allclose(np.asarray(xbar), xs.mean(0), rtol=1e-4, atol=1e-5)


def test_multiround_latency_delegates_to_engine():
    """latency= makes multiround a thin wrapper over the async engine; with a
    no-straggler model it reproduces the synchronous result."""
    key, A, b = _small_lsq()
    mesh = make_mesh((1,), ("data",))
    spec = sk.SketchSpec("gaussian", 64)
    sync = distributed.distributed_sketch_solve_multiround(mesh, spec, key, A, b, rounds=3)
    asyn = distributed.distributed_sketch_solve_multiround(
        mesh, spec, key, A, b, rounds=3,
        latency=rt.ConstantLatency(value_s=0.01),
        runtime_config=rt.RuntimeConfig(deadline_s=1.0, max_retries=0),
    )
    np.testing.assert_allclose(np.asarray(asyn), np.asarray(sync), rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ trainer delegation


def test_trainer_delegates_straggler_simulation_to_runtime():
    import dataclasses

    from repro.configs.base import get_config
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig

    cfg = dataclasses.replace(
        get_config("granite-3-8b").reduced(), num_layers=1, d_model=16, d_ff=32,
        num_heads=2, num_kv_heads=1, head_dim=8, vocab_size=31,
    )

    def step_fn(state, batch, mask):
        return {"step": state["step"] + 1}, {"loss": jnp.float32(0.0), "qprime": mask.sum()}

    def run_once(seed):
        tc = TrainerConfig(
            batch=2, seq=8, log_every=1,
            latency=rt.LognormalLatency(seed=seed, mean_s=1.0, sigma=0.5),
            straggler_q=8, deadline_s=1.0,
        )
        tr = Trainer(cfg, AdamWConfig(lr=1e-3), tc, step_fn=step_fn)
        tr.run(5, state={"step": jnp.int32(0)})
        return tr

    tr_a, tr_b = run_once(7), run_once(7)
    qa = [h["qprime"] for h in tr_a.history]
    qb = [h["qprime"] for h in tr_b.history]
    assert qa == qb  # restart-deterministic straggler pattern
    assert any(q < 8 for q in qa)  # the deadline actually bites
    rep = tr_a.straggler_report()
    assert rep["steps"] == 5.0
    assert {"p50_runtime", "timeouts", "retries", "effective_q"} <= set(rep)
    assert rep["timeouts"] == sum(8 - q for q in qa)
    # a different latency seed sees a different pattern
    assert [h["qprime"] for h in run_once(8).history] != qa
