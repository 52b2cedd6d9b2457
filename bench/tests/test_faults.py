"""The check refuses a broken timed path, and the control.

Each test drives a whole run of a cell past the harness's look for a chip, on the
CPU at a size a test can hold (kernels in interpret mode), with the cell's own
limits. A sound run must come out correct; with one fault planted in the program,
or with the control (the plain reference at one bf16 pass) in the program's place,
``correct`` must come out false. Faults:

* half of the batch left out, the mean taken over the rest;
* an answer altered where it is produced (each worker's x̂ scaled by 1.01);
* on the mesh, the exchange between chips left out (no psum: chip 0's x̂ alone);
* stale keys: every solve draws the worker keys of one fixed key, not its own, so
  the window's answers repeat.

The mesh runs in a child process with four host devices, its worker keys sharded
over them as on the chip (``REPRO_MESH_BATCH=1``).
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import harness

SIZES = {  # the cells' widths cut to a test's size; q and the mixes as committed
    "fig3a.master": dict(n=8192, d=128, m=1024),
    "fig2.master": dict(n=16384, d=128, targets=5, m=1024, s=4),
    "fig2.served": dict(n=8192, d=128, targets=5, m=1024, s=4),
    "fig3a.mesh4": dict(n=8192, d=128, m=1024),
}
SEED = 2**31 + 12345  # wider than 32 bits, as a benchmark run's seeds may be
STALE_WINDOW_S = 3.0  # long enough for two solves at these sizes, so answers can repeat


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(harness.BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config.update(SIZES[name])
    return cell


def run_small(name: str, solver=None, seconds: float = 1e-3) -> dict:
    run = load_run()
    jax.clear_caches()  # a patched function must reach a fresh trace
    return run.run_cell(small_cell(name), harness.benchmark(), SEED, seconds, False, jax.devices()[:1], solver=solver)


def half_mean(xs, mask=None, **_):
    return jnp.mean(xs[: xs.shape[0] // 2], axis=0)


def altered(solve_fn):
    return lambda *a, **k: solve_fn(*a, **k) * 1.01


def stale(worker_keys):
    return lambda key, q, round_id=0: worker_keys(jax.random.PRNGKey(7), q, round_id)


MASTER = ["fig3a.master", "fig2.master"]


@pytest.mark.parametrize("name", MASTER)
def test_sound_run_is_correct(name):
    res = run_small(name, seconds=STALE_WINDOW_S)
    assert res["correct"] and res["attempted"] >= 2, res["checks"]


@pytest.mark.parametrize("name", MASTER)
def test_half_batch_is_refused(name, monkeypatch):
    from repro.core import averaging

    monkeypatch.setattr(averaging, "masked_average", half_mean)
    assert not run_small(name)["correct"]


@pytest.mark.parametrize("name", MASTER + ["fig2.served"])
def test_altered_answer_is_refused(name, monkeypatch):
    from repro.core import solve

    monkeypatch.setattr(solve, "lstsq_gram", altered(solve.lstsq_gram))
    assert not run_small(name, seconds=0.2 if name == "fig2.served" else 1e-3)["correct"]


@pytest.mark.parametrize("name", MASTER)
def test_stale_keys_are_refused(name, monkeypatch):
    from repro.utils import prng

    monkeypatch.setattr(prng, "worker_keys", stale(prng.worker_keys))
    res = run_small(name, seconds=STALE_WINDOW_S)
    assert res["attempted"] >= 2 and not res["correct"], res


@pytest.mark.parametrize("name", MASTER + ["fig2.served"])
def test_control_is_refused(name):
    runner = harness.load_module("runners", harness.load_cell(name).traffic["runner"])
    assert not run_small(name, solver=runner.control, seconds=0.2 if name == "fig2.served" else 1e-3)["correct"]


def test_served_sound_run_is_correct():
    res = run_small("fig2.served", seconds=0.2)
    assert res["correct"] and res["attempted"] >= 1, res


def test_served_half_batch_is_refused(monkeypatch):
    from repro.runtime import engine

    run = engine.ServerlessEngine.run

    def half(self, q=None, *, tasks=None, error_fn=None):
        return run(self, tasks=tasks[: len(tasks) // 2], error_fn=error_fn)

    monkeypatch.setattr(engine.ServerlessEngine, "run", half)
    assert not run_small("fig2.served", seconds=0.2)["correct"]


MESH_CHILD = r"""
import json, sys
sys.path[:0] = sys.argv[1:3]
import jax, jax.numpy as jnp
from bench.tests import test_faults as tf
from repro.core import averaging, solve

fault = sys.argv[3]
if fault == "no_exchange":
    averaging.psum_average = lambda x, mask, axis, **k: x
elif fault == "half_batch":
    psum = averaging.psum_average
    def half(x, mask, axis, **k):
        w = jax.lax.axis_index(axis)
        return psum(x, mask * (w < jax.lax.axis_size(axis) // 2), axis, **k)
    averaging.psum_average = half
elif fault == "altered":
    solve.lstsq_gram = tf.altered(solve.lstsq_gram)
elif fault == "stale_keys":
    from repro.utils import prng
    prng.worker_keys = tf.stale(prng.worker_keys)
run = tf.load_run()
devices = jax.devices()[:4]
solver = tf.harness.load_module("runners", "mesh").control if fault == "control" else None
seconds = tf.STALE_WINDOW_S if fault in ("none", "stale_keys") else 1e-3
res = run.run_cell(tf.small_cell("fig3a.mesh4"), tf.harness.benchmark(), tf.SEED, seconds, False, devices, solver=solver)
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", ["none", "no_exchange", "half_batch", "altered", "control", "stale_keys"])
def test_mesh_faults(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4", REPRO_MESH_BATCH="1")
    out = subprocess.run(
        [sys.executable, "-c", MESH_CHILD, harness.ROOT, harness.SRC, fault],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] == (fault == "none"), res["checks"]
    if fault in ("none", "stale_keys"):
        assert res["attempted"] >= 2
