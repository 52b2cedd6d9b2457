"""Worker payloads and error estimators for the runtime engine.

A *task* is one serverless invocation: derive the (worker, round) key, sketch,
solve, return x̂_k. The builders here produce ``compute_fn(worker_id, round_id)``
callables over one jitted kernel, reusing the exact solver stack of the
synchronous path — ``solve.sketch_and_solve`` with the fused single-pass
sketch→Gram pipeline by default — and the exact key schedule
``prng.worker_key(base_key, w, round)`` of the ``shard_map`` workers, so an async
run and a mesh run with the same realized worker set agree to float tolerance.

The payloads are *picklable task specs* (plain classes that pickle as numpy state,
the jitted program found again lazily in each process), which is what lets the
``process`` executor backend ship one payload to each worker process and submit
bare ``(worker_id, round_id)`` coordinates afterwards. On the thread/inline
backends they behave exactly like the closures they replaced. The jitted solve
is built once per process per static configuration (the payload's type, sketch
spec and, for sketch-solve, ``reg`` and ``method``) and shared by every thread
and every later payload of that configuration; shapes and dtypes are JAX's own
jit cache, since (A, b) are arguments.

Early-stop estimators (for ``RuntimeConfig.target_error``):

  * :func:`theory_error_fn` — Theorem 1's closed form d/(q′(m−d−1)): predicted
    relative error after q′ Gaussian results (a heuristic proxy for other kinds).
  * :func:`probe_error_fn` — a held-out residual probe: relative excess cost of x̄
    on (A_p, b_p) against the probe's own optimum, no theory assumptions.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sketches as sk, solve, theory
from repro.runtime.backends import ExecutorBackend
from repro.runtime.engine import (
    DeadlinePolicy,
    RuntimeConfig,
    RuntimeResult,
    ServerlessEngine,
)
from repro.runtime.latency import LatencyModel
from repro.utils import prng

# Host spans (``jax.profiler.TraceAnnotation``) each task writes into a profiler
# trace when one is active: the task on its worker thread, and inside it the device
# copy of A and b its payload makes, if it needs one (enqueued, so the span counts
# copies; its length is not the copy's), and the build of a task program this
# process has not built yet (JAX traces and compiles it at its first call, so the
# span counts builds; its length is not the build's).
TASK_SPAN = "repro.task"
UPLOAD_SPAN = "repro.task.upload"
BUILD_SPAN = "repro.task.build"

# Serialises the one-time data set-up and program lookup of every payload in this
# process, so threads racing on a new payload make at most one device copy of A and
# one program. A module lock, not a field: task specs must stay picklable.
_BUILD_LOCK = threading.Lock()

# The jitted task programs built in this process, by static configuration
# (``_PicklableCompute._config``). Programs only, never data: A and b are arguments.
_PROGRAMS: dict = {}


def _key_data(key) -> np.ndarray:
    """Raw uint32 words of a jax PRNG key (legacy or typed) — picklable."""
    try:
        return np.asarray(key)
    except TypeError:  # new-style typed key array
        return np.asarray(jax.random.key_data(key))


def _copy_target():
    """The device a copy of a host array goes to: JAX's default device (a default
    named only by its platform matches no array, so such a payload copies)."""
    return jax.config.jax_default_device or jax.local_devices()[0]


def _resident(x, device) -> bool:
    """``x`` is a ``jax.Array`` held whole on ``device`` alone."""
    return isinstance(x, jax.Array) and x.is_fully_addressable and x.devices() == {device}


class _PicklableCompute:
    """Base for process-shippable payloads: (A, b) + a lazily found jit.

    The jit takes (A, b) as arguments: closed over, they would be embedded in the
    program as constants (GBs at served sizes). When A and b are both ``jax.Array``
    values held whole on the device a copy would go to (JAX's default device), the
    payload uses them as they are, with no copy. Otherwise (numpy, sharded, on
    another device) it copies them to that device once per payload, at its first
    task. Pickled, the payload carries A and b as numpy, so a process copies them
    once on the far side. ``job`` is the id of the served job the payload belongs to
    (set by ``SolveServer``; ``None`` otherwise), written on its host spans.
    """

    def __init__(self, spec: sk.SketchSpec, base_key, A, b):
        self.spec = spec
        self.base_key = _key_data(base_key)
        self.A = A if isinstance(A, jax.Array) else np.asarray(A)
        self.b = b if isinstance(b, jax.Array) else np.asarray(b)
        self.job: Optional[int] = None
        self._fn = None
        self._data = None

    def _config(self) -> tuple:
        """The static configuration the task program depends on: its cache key."""
        return (type(self), self.spec)

    def _program(self) -> Callable:
        """The jitted task program ``(wkey, A, b) -> x̂``."""
        raise NotImplementedError

    def _device_data(self):
        """(A, b) on the copy target: the caller's arrays if already there, else a copy."""
        target = _copy_target()
        if _resident(self.A, target) and _resident(self.b, target):
            return self.A, self.b
        with jax.profiler.TraceAnnotation(UPLOAD_SPAN, job=self.job, bytes=self.A.nbytes + self.b.nbytes):
            return jnp.asarray(np.asarray(self.A)), jnp.asarray(np.asarray(self.b))

    def _ready(self):
        if self._fn is None:
            with _BUILD_LOCK:
                if self._fn is None:  # _fn is published last: a set _fn has its _data
                    self._data = self._device_data()
                    config = self._config()
                    fn = _PROGRAMS.get(config)
                    if fn is None:
                        with jax.profiler.TraceAnnotation(BUILD_SPAN, job=self.job):
                            fn = _PROGRAMS[config] = self._program()
                    self._fn = fn
        return self._fn, self._data

    def _key(self, worker_id: int, round_id: int):
        return prng.worker_key(jnp.asarray(self.base_key), worker_id, round_id)

    def __call__(self, worker_id: int, round_id: int) -> np.ndarray:
        with jax.profiler.TraceAnnotation(TASK_SPAN, job=self.job, worker=worker_id, round=round_id):
            fn, data = self._ready()
            return np.asarray(fn(self._key(worker_id, round_id), *data))

    def lower(self, worker_id: int = 0, round_id: int = 0):
        """The program one task runs, lowered (``.compile().as_text()`` shows it)."""
        fn, data = self._ready()
        return fn.lower(self._key(worker_id, round_id), *data)

    def __getstate__(self):
        state = dict(self.__dict__)
        state["A"], state["b"] = np.asarray(self.A), np.asarray(self.b)  # numpy crosses processes
        state["_fn"] = None  # jit caches and device arrays never cross processes
        state["_data"] = None
        return state


class SketchSolveCompute(_PicklableCompute):
    """One Algorithm-1 worker as a task spec: (worker, round) ↦ x̂ ∈ R^d."""

    def __init__(self, spec, base_key, A, b, *, reg: float = 0.0, method: str = "fused"):
        super().__init__(spec, base_key, A, b)
        self.reg = float(reg)
        self.method = str(method)

    def _config(self):
        return (type(self), self.spec, self.reg, self.method)

    def _program(self):
        spec, reg, method = self.spec, self.reg, self.method
        return jax.jit(
            lambda wkey, A, b: solve.sketch_and_solve(spec, wkey, A, b, reg=reg, method=method)
        )


class LeastNormCompute(_PicklableCompute):
    """§V right-sketch worker (n < d) as a task spec."""

    def _program(self):
        spec = self.spec
        return jax.jit(lambda wkey, A, b: solve.sketch_least_norm(spec, wkey, A, b))


def make_sketch_solve_compute(
    spec: sk.SketchSpec,
    base_key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    reg: float = 0.0,
    method: str = "fused",
) -> SketchSolveCompute:
    """One Algorithm-1 worker as a ``compute_fn``: (worker, round) ↦ x̂ ∈ R^d."""
    return SketchSolveCompute(spec, base_key, A, b, reg=reg, method=method)


def make_least_norm_compute(
    spec: sk.SketchSpec,
    base_key: jax.Array,
    A: jax.Array,
    b: jax.Array,
) -> LeastNormCompute:
    """§V right-sketch worker (n < d) as a ``compute_fn``."""
    return LeastNormCompute(spec, base_key, A, b)


# ----------------------------------------------------------------- error estimators


def theory_error_fn(spec: sk.SketchSpec, d: int) -> Callable[[np.ndarray, int], float]:
    """Predicted relative error after q′ arrivals — Theorem 1, exact for Gaussian
    sketches (documented heuristic otherwise). Ignores x̄: a pure function of the
    realized count, so stopping is decided without touching the data."""
    single = theory.gaussian_single_error(spec.m, d)

    def err(_xbar: np.ndarray, count: int) -> float:
        return single / max(count, 1)

    return err


def probe_error_fn(A_probe: jax.Array, b_probe: jax.Array) -> Callable[[np.ndarray, int], float]:
    """Held-out residual probe: (f_p(x̄) − f_p*) / f_p* on probe rows.

    The probe's own optimum f_p* is computed once; each arrival costs one (n_p, d)
    matvec. With probe rows subsampled from (A, b) this estimates the paper's
    relative approximation error without knowing the full problem's f*."""
    x_p = solve.lstsq(A_probe, b_probe)
    fstar = float(solve.residual_cost(A_probe, b_probe, x_p))

    @jax.jit
    def _cost(x):
        return solve.residual_cost(A_probe, b_probe, x)

    def err(xbar: np.ndarray, _count: int) -> float:
        f = float(_cost(jnp.asarray(xbar, A_probe.dtype)))
        return (f - fstar) / max(fstar, 1e-30)

    return err


def subsample_probe(
    key: jax.Array, A: jax.Array, b: jax.Array, rows: int = 1024
) -> Tuple[jax.Array, jax.Array]:
    """Uniform row probe of (A, b) for :func:`probe_error_fn`."""
    n = A.shape[0]
    idx = jax.random.choice(key, n, (min(rows, n),), replace=False)
    return A[idx], b[idx]


def resolve_error_fn(
    error_fn: Union[None, str, Callable[[np.ndarray, int], float]],
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    probe_rows: int = 1024,
) -> Optional[Callable[[np.ndarray, int], float]]:
    """``"theory"`` / ``"probe"`` / callable / None → the engine's error callback."""
    if error_fn == "theory":
        return theory_error_fn(spec, A.shape[1])
    if error_fn == "probe":
        pk = jax.random.fold_in(key, 0x9B0BE)
        return probe_error_fn(*subsample_probe(pk, A, b, rows=probe_rows))
    return error_fn


# ------------------------------------------------------------------- one-call driver


def serverless_sketch_solve(
    spec: sk.SketchSpec,
    key: jax.Array,
    A: jax.Array,
    b: jax.Array,
    *,
    q: int,
    latency: LatencyModel,
    config: Optional[RuntimeConfig] = None,
    rounds: int = 1,
    reg: float = 0.0,
    method: str = "fused",
    error_fn: Union[None, str, Callable[[np.ndarray, int], float]] = None,
    probe_rows: int = 1024,
    backend: Union[None, str, ExecutorBackend] = None,
    deadline: Union[None, float, DeadlinePolicy] = None,
) -> RuntimeResult:
    """Algorithm 1 on the async engine: ``rounds`` waves of ``q`` workers, averaged
    as they arrive. ``error_fn``: a callable, ``"theory"``, ``"probe"``, or None
    (None still runs every task; "theory"/"probe" also enable the early-stop
    comparison when ``config.target_error`` is set). ``backend`` selects the
    executor (``"inline"``/``"thread"``/``"process"``, default ``config.backend``);
    ``deadline`` an optional :class:`~repro.runtime.engine.DeadlinePolicy`.
    """
    error_fn = resolve_error_fn(error_fn, spec, key, A, b, probe_rows=probe_rows)
    compute = make_sketch_solve_compute(spec, key, A, b, reg=reg, method=method)
    tasks: Sequence[Tuple[int, int]] = [(w, r) for r in range(rounds) for w in range(q)]
    engine = ServerlessEngine(compute, latency, config, backend=backend, deadline=deadline)
    return engine.run(tasks=tasks, error_fn=error_fn)
