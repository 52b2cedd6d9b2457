"""Served cells: sketch-solve jobs through ``SolveServer.submit_solve``.

One client asks for one job at a time and waits for its answer (closed loop): the
server answers one job at a time. Every job solves the configuration's dataset,
made on the device from the seed at its rows and widths, with worker keys from
``fold_in(run_key, j)``. Set-up runs ``warm_jobs`` jobs with keys the window never
uses: they fill the compile cache with the task program and make the host copy of
the dataset that every job's task payload takes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, harness, work
from bench.runners import master


def program(config: dict, traffic: dict):
    """The job ``(A, b, key) -> (x̄, workers averaged)`` of the program under test."""
    from repro import runtime as rt
    from repro.serve import SolveServer

    server = SolveServer(
        latency=rt.ConstantLatency(traffic["latency_s"]),
        config=rt.RuntimeConfig(deadline_s=traffic["deadline_s"], max_retries=traffic["max_retries"]),
        backend=traffic["backend"],
    )
    spec, q, reg = harness.sketch_spec(config), int(traffic["q"]), config["reg"]

    def job(A, b, key):
        j = server.submit_solve(A, b, spec, q, key=key, reg=reg)
        return j.xbar, int(j.summary["effective_q"])

    return job


def control(config: dict, traffic: dict):
    """The plain reference at one bf16 pass, in the program's place."""
    q = int(traffic["q"])

    def job(A, b, key):
        return np.asarray(master.plain(config, q, key, A, b, precision="bf16")), q

    return job


shapes = master.shapes


def rehearsal(cell: harness.Cell, topo):
    """Each job's task program, ``solve.sketch_and_solve`` as ``runtime/tasks.py`` jits it."""
    from jax.sharding import SingleDeviceSharding
    from repro.core import solve

    spec, reg = harness.sketch_spec(cell.config), cell.config["reg"]
    key, _, A, b = shapes(cell, SingleDeviceSharding(topo.devices[0]))
    return [("task program", lambda k, A_, b_: solve.sketch_and_solve(spec, k, A_, b_, reg=reg), (key, A, b))]


class State:
    def __init__(self, cell: harness.Cell, seed: int, devices, solver=None):
        cfg, tr = cell.config, cell.traffic
        # the server runs on the default device; further chips of the cell only hold the host
        self.cell, self.seed, self.q, self.devices = cell, seed, int(tr["q"]), devices[:1]
        data_key, self.run_key = harness.seed_keys(seed)
        n = cfg["n"]
        self.A, self.b = jax.jit(lambda k: data.make(cfg, k, n))(data_key)
        self.job = (solver or program)(cfg, tr)
        for w in range(int(tr["warm_jobs"])):
            self.job(self.A, self.b, jax.random.fold_in(self.run_key, 2**31 - 1 - w))

    def work(self):
        """(flops, bytes) per job on its chip, by the algorithm's own count."""
        return work.config_work(self.cell.config, self.q)

    def window(self, seconds: float) -> harness.Window:
        step = lambda j: self.job(self.A, self.b, jax.random.fold_in(self.run_key, j))  # noqa: E731
        win = harness.timed_loop(step, seconds)
        win.metrics["jobs_per_s"] = win.count / win.elapsed_s
        return win

    def check(self, win: harness.Window):
        """Free the server, then judge every job: all q workers averaged, and the
        numbers the cell's limits name (``master.judge_answers``)."""
        self.job = None
        short = sum(q_eff != self.q for _, q_eff in win.answers)  # every worker arrives under a constant latency
        if short:
            harness.log(f"served: {short} jobs averaged fewer than q={self.q} workers")
        xs = [jnp.asarray(x) for x, _ in win.answers]
        keys = [jax.random.fold_in(self.run_key, j) for j in range(win.count)]
        numbers = master.judge_answers(self.cell, self.seed, self.q, [(self.A, self.b)] * win.count, xs, keys)
        return harness.judge(self.cell, numbers, extra_failed=short)
