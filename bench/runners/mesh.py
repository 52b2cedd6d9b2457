"""Mesh cells: ``distributed_sketch_solve_master`` on a mesh of the cell's chips.

A and b are made on the device from the seed, replicated to every chip. The mix's q
must equal the number of chips: the program runs one worker per chip, keys sharded
over the mesh, and averages with the ``psum_average`` collective. Solve i uses the
worker keys of ``fold_in(run_key, i)``; the window and the check are the master
runner's, with the reference on chip 0.
"""
from __future__ import annotations

import jax

from bench import data, harness, work
from bench.runners import master


def program(config: dict, q: int, mesh):
    from repro.core import distributed

    spec = harness.sketch_spec(config)

    def solve_once(run_key, i, A, b):
        key = jax.random.fold_in(run_key, i)
        return distributed.distributed_sketch_solve_master(mesh, spec, key, A, b, reg=config["reg"])

    return solve_once


control = master.control
shapes = master.shapes


def rehearsal(cell: harness.Cell, topo):
    """The timed program at the cell's shapes, on a mesh of described chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    q = int(cell.traffic["q"])
    mesh = make_mesh((q,), ("data",), devices=topo.devices[:q])
    return [("program (per chip)", program(cell.config, q, mesh), shapes(cell, NamedSharding(mesh, P())))]


class State(master.State):
    def __init__(self, cell: harness.Cell, seed: int, devices, solver=None):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh

        cfg, self.q = cell.config, int(cell.traffic["q"])
        if self.q != len(devices):
            raise ValueError(f"the mesh mix runs one worker per chip: q={self.q}, chips={len(devices)}")
        self.cell, self.seed, self.devices = cell, seed, devices
        mesh = make_mesh((self.q,), ("data",), devices=devices)
        replicated = NamedSharding(mesh, P())
        data_key, self.run_key = harness.seed_keys(seed)
        n = cfg["n"]
        self.A, self.b = jax.jit(lambda k: data.make(cfg, k, n), out_shardings=(replicated, replicated))(data_key)
        self.compile((solver or program)(cfg, self.q, mesh))

    def work(self):
        """(flops, bytes) per solve on each chip: one worker's pass over all of [A | b]."""
        return work.config_work(self.cell.config, 1)

    def reference_data(self):
        """Chip 0's replica of (A, b); the other replicas are freed."""
        A, b = (x.addressable_shards[0].data for x in (self.A, self.b))
        self.A = self.b = None
        return A, b
