"""Master cells: Algorithm 1 on one device, q workers per averaged solve.

The timed program is the composition ``distributed_sketch_solve_master`` runs per
mesh shard (one shard on one chip): ``operators.gram_batched`` (the multi-worker
fused sketch→Gram kernel) → ``jax.vmap(solve.lstsq_gram)`` under the harness's
``bench.solve_tail`` scope → ``averaging.masked_average``. Solve i uses the worker
keys of ``fold_in(run_key, i)``; solves run back to back, each to completion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, harness, reference, work

WARM_INDEX = 2**31 - 1  # the warm-up solve's index; the window counts up from 0


def program(config: dict, q: int):
    """The timed solve ``(run_key, i, A, b) -> x̄`` of the program under test."""
    from repro.core import averaging, operators, solve
    from repro.utils import prng

    spec = harness.sketch_spec(config)
    lstsq_gram = functools.partial(solve.lstsq_gram, reg=config["reg"])

    def solve_once(run_key, i, A, b):
        keys = prng.worker_keys(jax.random.fold_in(run_key, i), q)
        Gs, cs = operators.gram_batched(spec, keys, A, b)
        with jax.named_scope("bench.solve_tail"):
            xs = jax.vmap(lstsq_gram)(Gs, cs)
        return averaging.masked_average(xs, jnp.ones((q,), xs.dtype))

    return solve_once


def control(config: dict, q: int, *_):
    """The plain reference at one bf16 pass, in the program's place."""

    def solve_once(run_key, i, A, b):
        return plain(config, q, jax.random.fold_in(run_key, i), A, b, precision="bf16")

    return solve_once


def plain(config: dict, q: int, key, A, b, precision: str = "highest"):
    """The reference's x̄ for one solve's key."""
    return reference.sketch_solve(key, A, b, family=config["family"], m=config["m"], q=q, s=config["s"], precision=precision)


def shapes(cell: harness.Cell, sharding, n=None):
    """(run_key, i, A, b) as shapes, for compiling without a chip (``bench/rehearse.py``)."""
    cfg = cell.config
    n = n or cfg["n"]
    b_shape = (n,) if cfg["targets"] == 1 else (n, cfg["targets"])
    return (
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((n, cfg["d"]), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct(b_shape, jnp.float32, sharding=sharding),
    )


def rehearsal(cell: harness.Cell, topo):
    """The timed program at the cell's shapes, on one described chip."""
    from jax.sharding import SingleDeviceSharding

    return [("program", program(cell.config, int(cell.traffic["q"])), shapes(cell, SingleDeviceSharding(topo.devices[0])))]


class State:
    """Set-up, window and check of a master cell; the mesh runner reuses the last two."""

    def __init__(self, cell: harness.Cell, seed: int, devices, solver=None):
        cfg, self.q = cell.config, int(cell.traffic["q"])
        self.cell, self.seed, self.devices = cell, seed, devices
        data_key, self.run_key = harness.seed_keys(seed)
        n = cfg["n"]
        self.A, self.b = jax.jit(lambda k: data.make(cfg, k, n))(data_key)
        self.compile((solver or program)(cfg, self.q))

    def compile(self, fn):
        """AOT-compile the solve and run it once outside the window."""
        self.solve = jax.jit(fn).lower(self.run_key, np.int32(0), self.A, self.b).compile()
        jax.block_until_ready(self.solve(self.run_key, np.int32(WARM_INDEX), self.A, self.b))

    def work(self):
        """(flops, bytes) per solve on each chip, by the algorithm's own count."""
        return work.config_work(self.cell.config, self.q)

    def window(self, seconds: float) -> harness.Window:
        step = lambda i: jax.block_until_ready(self.solve(self.run_key, np.int32(i), self.A, self.b))  # noqa: E731
        win = harness.timed_loop(step, seconds)
        win.metrics["solve_s"] = win.elapsed_s / win.count
        return win

    def reference_data(self):
        return self.A, self.b

    def check(self, win: harness.Window):
        """Free the program, then judge the window's x̄s by the numbers the cell's
        limits name (:func:`judge_answers`)."""
        self.solve = None
        A, b = self.reference_data()
        xs = [jax.device_put(np.asarray(x), A.devices().pop()) for x in win.answers]
        keys = [jax.random.fold_in(self.run_key, i) for i in range(len(xs))]
        return harness.judge(self.cell, judge_answers(self.cell, self.seed, self.q, [(A, b)] * len(xs), xs, keys))


def judge_answers(cell: harness.Cell, seed: int, q: int, data_of, xs, keys) -> dict:
    """Per-answer numbers ``{name: {answer: value}}`` for the limits the cell names.

    * ``theorem1_gap``, every answer: |r − 1|, r = ((f(x̄) − f*)/f*) / Theorem 1,
      with x* = lstsq(A, b) once per dataset;
    * ``sketch_gap``, a sample drawn from the seed: ‖x̄ − x̄_ref‖/‖x̄_ref‖ against the
      reference's x̄ for the answer's key (the same sketches);
    * ``repeated_answers``, every answer: 1 where it equals an earlier answer bit for
      bit, else 0. Each answer's fresh keys draw new sketches, so a sound run reads
      0; an answer made with stale keys, or served from a cache, reads 1.
    """
    cfg, numbers = cell.config, {}
    if "theorem1_gap" in cell.limits:
        thm = reference.theorem1(cfg["d"], q, cfg["m"])
        fstar, gaps = {}, {}
        for i, ((A, b), x) in enumerate(zip(data_of, xs)):
            if id(A) not in fstar:
                fstar[id(A)] = float(reference.cost(A, b, reference.lstsq(A, b)))
            f0 = fstar[id(A)]
            gaps[i] = abs((float(reference.cost(A, b, x)) - f0) / f0 / thm - 1.0)
        numbers["theorem1_gap"] = gaps
    if "sketch_gap" in cell.limits:
        picks = harness.sample(seed, len(xs), int(cell.limits["sketch_gap"]["sample"]))
        numbers["sketch_gap"] = {i: harness.rel_gap(xs[i], plain(cfg, q, keys[i], *data_of[i])) for i in picks}
    if "repeated_answers" in cell.limits:
        seen, repeats = set(), {}
        for i, x in enumerate(xs):
            bits = np.asarray(x).tobytes()
            repeats[i] = float(bits in seen)
            seen.add(bits)
        numbers["repeated_answers"] = repeats
    return numbers
