"""Pallas kernel micro-bench: interpret-mode wall time (CPU).

Wall times here are *interpret-mode* (Python-executed kernel bodies) — they validate
plumbing, not TPU speed. Kernel time and roofline share on the chip come from the
benchmark's profiler trace (``bench/run.py --trace 1``, work counted by
``bench/work.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fwht import ops as fwht_ops
from repro.kernels.gaussian import ops as g_ops
from repro.kernels.sjlt import ops as sjlt_ops
from benchmarks.common import print_table, smoke, timeit, write_csv


def run(quick: bool = True):
    n, d, m, s = (2048, 128, 256, 4) if quick else (8192, 512, 1024, 4)
    if smoke():
        n, d, m = 512, 128, 128
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d), jnp.float32)
    buckets, signs = sjlt_ops.sjlt_params(key, n, s, m)
    kernels = {
        "fwht": lambda: fwht_ops.fwht(A),
        "sjlt": lambda: sjlt_ops.sjlt_apply(A, buckets, signs, m),
        "gaussian_rng_fused": lambda: g_ops.gaussian_sketch(key, A, m),
    }
    rows = [{"kernel": name, "interp_ms": timeit(fn, repeat=2) * 1e3} for name, fn in kernels.items()]
    write_csv("kernel_bench", rows)
    print_table("Pallas kernels (interpret wall)", rows)
    return rows


if __name__ == "__main__":
    run(quick=True)
