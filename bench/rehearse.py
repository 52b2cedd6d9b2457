#!/usr/bin/env python3
"""Compile each cell's timed program and its reference for a described TPU v5e,
without the chip, at the cell's real shapes, and print what XLA says they need.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload fig2.master ...]

Shows before any chip run that the Mosaic kernels compile at these shapes and that
the program and the reference each fit one chip's memory (they run one after the
other). Nothing runs, so nothing here is a time or a device measurement.
"""
import argparse
import functools
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_PALLAS_INTERPRET"] = "0"  # compile the kernels for the chip, not the interpreter
os.environ["REPRO_MESH_BATCH"] = "1"  # shard worker keys over the mesh, as on the chip (the CPU backend would not)

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import harness, reference  # noqa: E402

sys.path.insert(1, harness.SRC)

import jax  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def describe(name: str, fn, *args) -> None:
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    print(
        f"  {name}: compile {time.perf_counter() - t0:.1f} s, arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
        f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, outputs {mem.output_size_in_bytes / 1e9:.3f} GB, "
        f"Mosaic kernel {'yes' if kernel else 'no'}",
        flush=True,
    )


def rehearse(cell: harness.Cell, topo) -> None:
    """Each program the cell's runner names, then the references its limits use, at
    the answers' shapes on one chip."""
    runner = harness.load_module("runners", cell.traffic["runner"])
    print(f"{cell.name} ({cell.traffic['runner']}):", flush=True)
    for label, fn, args in runner.rehearsal(cell, topo):
        describe(label, fn, *args)
    key, _, A, b = runner.shapes(cell, SingleDeviceSharding(topo.devices[0]))
    if "theorem1_gap" in cell.limits:
        describe("reference lstsq", reference.lstsq, A, b)
    if "sketch_gap" in cell.limits:
        cfg, q = cell.config, int(cell.traffic["q"])
        plain = functools.partial(reference.sketch_solve, family=cfg["family"], m=cfg["m"], q=q, s=cfg["s"])
        describe("reference sketch-and-solve", plain, key, A, b)


def main(argv=None) -> int:
    from jax.experimental import topologies

    bench = harness.benchmark()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", help="cells to rehearse (default: all)")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's programs cannot be read back
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in args.workload or [w["name"] for w in bench["workloads"]]:
        rehearse(harness.load_cell(name, bench), topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
