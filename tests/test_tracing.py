"""The program's own trace points: device scopes in the lowered programs, and the
served path's host spans (job → task → upload) in a CPU profiler trace."""
import glob
import gzip
import json
import os
import pickle
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as rt
from repro.core import operators, sketches as sk, solve
from repro.kernels import common
from repro.runtime import tasks
from repro.serve import SolveServer, engine as serve_engine
from repro.utils import prng

N, D, M, Q = 256, 8, 32, 4


def _data(n=N, d=D):
    kA, kb = jax.random.split(jax.random.PRNGKey(0))
    return jax.random.normal(kA, (n, d), jnp.float32), jax.random.normal(kb, (n,), jnp.float32)


def _lowered_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize(
    "spec, has_params",
    [(sk.SketchSpec("gaussian", M, use_kernel=True), False), (sk.SketchSpec("sjlt", M, s=3, use_kernel=True), True)],
    ids=["gaussian", "sjlt"],
)
def test_gram_batched_names_its_input_and_parameter_scopes(spec, has_params):
    A, b = _data()
    keys = prng.worker_keys(jax.random.PRNGKey(1), Q)
    text = _lowered_text(lambda k, A_, b_: operators.gram_batched(spec, k, A_, b_), keys, A, b)
    assert common.GRAM_INPUT_SCOPE in text
    assert (common.SKETCH_PARAMS_SCOPE in text) == has_params
    assert solve.SOLVE_TAIL_SCOPE not in text


def test_lstsq_gram_names_the_solve_tail():
    G = jnp.eye(D) * 2.0
    c = jnp.ones((D,))
    text = _lowered_text(solve.lstsq_gram, G, c)
    assert solve.SOLVE_TAIL_SCOPE in text
    assert common.GRAM_INPUT_SCOPE not in text


def _trace_spans(path):
    """(name, start µs, end µs, args) of every complete event in the trace under ``path``."""
    (f,) = glob.glob(os.path.join(path, "**", "*.trace.json.gz"), recursive=True)
    with gzip.open(f, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    return [(e["name"], e["ts"], e["ts"] + e.get("dur", 0), e.get("args", {})) for e in events if e.get("ph") == "X"]


def test_served_jobs_write_job_task_and_upload_spans(tmp_path):
    """Job 1 gets (A, b) on the device and makes no copy; job 2 gets them as numpy and
    copies them once. Neither builds: the warm-up job built the only program."""
    A, b = _data()
    server = SolveServer(latency=rt.ConstantLatency(0.01), config=rt.RuntimeConfig(deadline_s=10.0), backend="thread")
    spec = sk.SketchSpec("gaussian", M)
    server.submit_solve(A, b, spec, Q, seed=9)  # compiles the task program outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.submit_solve(A, b, spec, Q, seed=0)
        server.submit_solve(np.asarray(A), np.asarray(b), spec, Q, seed=1)
    finally:
        jax.profiler.stop_trace()
    spans = _trace_spans(str(tmp_path))
    jobs = {a["job"]: (s, e) for n, s, e, a in spans if n == serve_engine.JOB_SPAN}
    assert sorted(jobs) == ["1", "2"]
    workers, uploads = defaultdict(list), defaultdict(int)
    for n, s, e, a in spans:
        if n == tasks.TASK_SPAN:
            workers[a["job"]].append(a["worker"])
            assert a["round"] == "0"
            js, je = jobs[a["job"]]
            assert js <= s and e <= je  # a task runs inside its job
        elif n == tasks.UPLOAD_SPAN:
            uploads[a["job"]] += 1
            assert int(a["bytes"]) == A.nbytes + b.nbytes
        assert n != tasks.BUILD_SPAN
    for j in jobs:
        assert sorted(workers[j]) == sorted(str(w) for w in range(Q))
    assert dict(uploads) == {"2": 1}


def test_payload_job_id_defaults_to_none_and_survives_pickling():
    A, b = _data(64, 4)
    compute = tasks.make_sketch_solve_compute(sk.SketchSpec("gaussian", 16), jax.random.PRNGKey(0), A, b)
    assert compute.job is None
    compute.job = 7
    assert pickle.loads(pickle.dumps(compute)).job == 7
