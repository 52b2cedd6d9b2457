"""Executor backend layer: crash fault-injection, pickling, factory contracts.

The process-backend tests SIGKILL real worker processes via
:class:`repro.runtime.backends.KillSwitch` and pin the recovery story end to end:
a killed worker surfaces as a ``drop`` event, re-enters deadline→backoff→retry
with a fresh round-folded key, and innocent pool-mates (whose futures the broken
pool also poisoned) are transparently re-run and never appear in the event log.
"""
import contextlib
import pickle
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as rt
from repro.core import sketches as sk, solve
from repro.utils import prng


def _toy_problem(n=256, d=8):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d))
    b = A @ jax.random.normal(jax.random.PRNGKey(1), (d,)) + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (n,)
    )
    return key, A, b


# ------------------------------------------------------------------ quick (no pools)


def test_make_backend_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown backend"):
        rt.make_backend("quantum", lambda w, r: np.zeros(2))


def test_make_backend_passes_instances_through():
    inline = rt.InlineBackend(lambda w, r: np.zeros(2))
    assert rt.make_backend(inline, lambda w, r: np.ones(2)) is inline
    assert set(rt.BACKENDS) == {"inline", "thread", "process"}


def test_make_backend_process_refuses_a_tpu_parent(monkeypatch):
    """A chip belongs to one process: on a TPU the process backend must refuse
    instead of spawning children that would fail or hang on the held chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        rt.make_backend("process", lambda w, r: np.zeros(2))
    assert isinstance(rt.make_backend("thread", lambda w, r: np.zeros(2)), rt.ThreadBackend)


def test_task_program_takes_data_as_arguments():
    """The task jit lowers with (key, A, b) as parameters — A is not a constant."""
    key, A, b = _toy_problem()
    compute = rt.make_sketch_solve_compute(sk.SketchSpec("gaussian", 64), key, A, b)
    lowered = compute.lower(1, 0)
    assert [x.shape for x in jax.tree_util.tree_leaves(lowered.in_avals)][1:] == [A.shape, b.shape]
    np.testing.assert_allclose(
        np.asarray(lowered.compile()(compute._key(1, 0), jnp.asarray(A), jnp.asarray(b))),
        compute(1, 0),
        rtol=1e-6,
    )


def test_task_program_is_built_once_under_racing_threads():
    """Eight threads hitting a fresh payload at once make one device copy and one jit."""
    key, A, b = _toy_problem()
    builds = []

    class Counting(rt.SketchSolveCompute):
        def _program(self):
            builds.append(threading.get_ident())
            time.sleep(0.05)  # hold the build open so unguarded threads would pile in
            return super()._program()

    compute = Counting(sk.SketchSpec("gaussian", 64), key, A, b)
    start = threading.Barrier(8)

    def task(w):
        start.wait()
        return compute(w, 0)

    with ThreadPoolExecutor(8) as pool:
        xs = list(pool.map(task, range(8)))
    assert len(builds) == 1
    for w, x in enumerate(xs):
        np.testing.assert_array_equal(x, compute(w, 0))
    assert len(builds) == 1


def test_sketch_solve_compute_pickle_roundtrip():
    """The process backend ships the compute by pickle; the clone must produce
    bitwise-identical results (numpy state, jit rebuilt lazily on the far side)."""
    key, A, b = _toy_problem()
    compute = rt.make_sketch_solve_compute(sk.SketchSpec("gaussian", 64), key, A, b)
    clone = pickle.loads(pickle.dumps(compute))
    np.testing.assert_array_equal(compute(1, 0), clone(1, 0))
    np.testing.assert_array_equal(compute(0, 3), clone(0, 3))


def test_least_norm_compute_pickle_roundtrip():
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (8, 64))  # n < d: the §V right-sketch regime
    b = jax.random.normal(jax.random.PRNGKey(1), (8,))
    compute = rt.make_least_norm_compute(sk.SketchSpec("gaussian", 32), key, A, b)
    clone = pickle.loads(pickle.dumps(compute))
    np.testing.assert_array_equal(compute(2, 1), clone(2, 1))


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names opened."""

    def __init__(self):
        self.names = []

    def __call__(self, name, **_):
        self.names.append(name)
        return contextlib.nullcontext()


@pytest.fixture
def spans(monkeypatch):
    recorder = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recorder)
    return recorder


@contextlib.contextmanager
def _build_events():
    """JAX's trace, lowering and compile events recorded while the block runs."""
    names = ("/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")
    seen = []

    def listener(event, duration, **_):
        if event in names:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def test_equal_configurations_build_one_program_between_them(spans):
    """A second payload of the same (spec, reg, method) takes the program the first
    built: one ``_program`` call, one build span, and its first program call neither
    traces nor compiles. (Its worker key is derived eagerly, outside the program, by
    ``jax.random`` primitives whose own caches JAX may evict and refill, so only the
    program call is watched.)"""
    key, A, b = _toy_problem()
    builds = []

    class Counting(rt.SketchSolveCompute):
        def _program(self):
            builds.append(self)
            return super()._program()

    spec = sk.SketchSpec("gaussian", 64)
    first = Counting(spec, key, A, b, reg=0.5)
    x_first = first(1, 0)
    second = Counting(sk.SketchSpec("gaussian", 64), jax.random.PRNGKey(7), A, b, reg=0.5)
    fn, data = second._ready()
    wkey = second._key(1, 0)
    with _build_events() as seen:
        x_second = np.asarray(fn(wkey, *data))
    assert seen == []
    assert builds == [first] and spans.names.count(rt.tasks.BUILD_SPAN) == 1
    assert fn is first._ready()[0]
    np.testing.assert_array_equal(second(1, 0), x_second)
    assert not np.array_equal(x_first, x_second)  # the second payload's own keys


@pytest.mark.parametrize(
    "changed",
    [
        {"reg": 0.5},
        {"spec": sk.SketchSpec("gaussian", 32)},
        {"spec": sk.SketchSpec("sjlt", 64, s=2)},
        {"method": "qr"},
    ],
    ids=["reg", "spec_m", "spec_kind", "method"],
)
def test_other_configurations_get_their_own_program(changed):
    """Payloads that differ in reg, spec or method get programs of their own, and each
    answer equals a fresh, uncached build's bit for bit."""
    key, A, b = _toy_problem()
    base = {"spec": sk.SketchSpec("gaussian", 64), "reg": 0.0, "method": "fused"}
    payloads = [rt.SketchSolveCompute(p.pop("spec"), key, A, b, **p) for p in (dict(base), {**base, **changed})]
    assert payloads[0]._ready()[0] is not payloads[1]._ready()[0]
    for p in payloads:
        fresh = p._program()
        for w in range(3):
            np.testing.assert_array_equal(p(w, 0), np.asarray(fresh(p._key(w, 0), A, b)))


def test_device_arrays_are_taken_as_they_are(spans):
    """A payload given A and b on the default device makes no copy of them; one given
    numpy arrays copies them once. Both answer with the same bits."""
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    resident = rt.SketchSolveCompute(spec, key, A, b)
    xs = [resident(w, 0) for w in range(3)]
    assert rt.tasks.UPLOAD_SPAN not in spans.names
    assert resident._data[0] is A and resident._data[1] is b
    host = rt.SketchSolveCompute(spec, key, np.asarray(A), np.asarray(b))
    for w, x in enumerate(xs):
        np.testing.assert_array_equal(host(w, 0), x)
    assert spans.names.count(rt.tasks.UPLOAD_SPAN) == 1


def test_payload_built_from_device_arrays_survives_pickle():
    """Device arrays cross the pickle boundary as numpy; the clone copies them on its
    side and answers bit for bit as the original does."""
    key, A, b = _toy_problem()
    compute = rt.SketchSolveCompute(sk.SketchSpec("gaussian", 64), key, A, b)
    x = compute(1, 0)  # device data and program in place before the pickle
    clone = pickle.loads(pickle.dumps(compute))
    assert isinstance(clone.A, np.ndarray) and isinstance(clone.b, np.ndarray)
    assert clone._fn is None and clone._data is None
    np.testing.assert_array_equal(clone(1, 0), x)
    np.testing.assert_array_equal(clone(0, 3), compute(0, 3))


def test_kill_switch_refuses_to_kill_master():
    """On inline/thread the task runs in the master process — KillSwitch must
    refuse rather than SIGKILL the test runner."""
    ks = rt.KillSwitch(inner=lambda w, r: np.zeros(2), kill_coords=((0, 0),))
    with pytest.raises(RuntimeError, match="master process"):
        ks(0, 0)
    np.testing.assert_array_equal(ks(1, 0), np.zeros(2))  # non-matching coords run


# --------------------------------------------------------- crash → drop → retry


def _kill_engine(kill_coords, *, q=2, max_retries=2, latency_seed=0):
    key, A, b = _toy_problem()
    spec = sk.SketchSpec("gaussian", 64)
    compute = rt.KillSwitch(
        inner=rt.make_sketch_solve_compute(spec, key, A, b), kill_coords=kill_coords
    )
    cfg = rt.RuntimeConfig(
        deadline_s=1.0, max_retries=max_retries, backoff_base_s=0.05, max_threads=2
    )
    lat = rt.ConstantLatency(seed=latency_seed, value_s=0.1)
    eng = rt.ServerlessEngine(compute, lat, cfg, backend="process")
    return key, A, b, spec, eng


@pytest.mark.slow
@pytest.mark.subprocess
def test_process_crash_drops_then_retries_with_fresh_key():
    """SIGKILL at (worker 0, round 0): the engine hears a drop, retries with a
    fresh round id, and the retry lands — the acceptance scenario."""
    key, A, b, spec, eng = _kill_engine(kill_coords=((0, 0),))
    res = eng.run(q=2)

    counts = res.events.counts()
    assert counts.get("drop", 0) == 1
    assert counts.get("retry", 0) == 1
    assert counts.get("timeout", 0) == 0
    assert res.count == 2 and res.dispatched == 3
    # the innocent pool-mate (worker 1) arrived normally, untouched by the crash
    assert (1, 0, 0) in res.arrived
    drops = [ev for ev in res.events if ev.kind == "drop"]
    assert [(ev.worker_id, ev.round_id) for ev in drops] == [(0, 0)]
    # the retry carries a *fresh* round (never a replay of the killed coordinate)
    assert (0, 1, 1) in res.arrived
    assert res.summary(deadline=1.0)["drops"] == 1

    # x̄ is the plain mean over exactly the arrived (worker, round) keys
    xs = np.stack(
        [
            np.asarray(solve.sketch_and_solve(spec, prng.worker_key(key, w, r), A, b))
            for (w, r, _) in res.arrived
        ]
    )
    np.testing.assert_allclose(res.xbar, xs.mean(0), rtol=1e-6, atol=1e-6)


@pytest.mark.slow
@pytest.mark.subprocess
def test_process_crash_without_retry_budget_just_drops():
    """max_retries=0: the crashed task is simply lost; the average is over the
    survivors and realized_mask records who made it."""
    _, _, _, _, eng = _kill_engine(kill_coords=((0, 0),), max_retries=0)
    res = eng.run(q=2)
    assert res.count == 1
    assert res.events.counts().get("drop", 0) == 1
    assert "retry" not in res.events.counts()
    np.testing.assert_array_equal(res.realized_mask, np.asarray([0.0, 1.0], np.float32))


@pytest.mark.slow
@pytest.mark.subprocess
def test_process_repeated_crashes_exhaust_budget_and_raise():
    """A task whose every attempt is killed (rounds 0,1,2 for worker 0 with
    q=1) exhausts max_retries and, with no other workers, x̄ is undefined."""
    _, _, _, _, eng = _kill_engine(kill_coords=((0, 0), (0, 1), (0, 2)), q=1)
    with pytest.raises(RuntimeError, match="no worker result"):
        eng.run(q=1)
