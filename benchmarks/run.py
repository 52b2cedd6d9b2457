"""Benchmark orchestrator: one module per paper table/figure + framework extras.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1,thm1,...]

Each module writes results/bench/<name>.csv and prints a table; this runner
aggregates pass/fail-style summaries where a benchmark encodes a checkable claim.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from repro.analysis.annotations import sanctioned_wall_timer
from repro.utils import env as envcfg

from benchmarks import (
    bias_bounds,
    fig1_airline,
    fig2_emnist,
    fig3_synthetic,
    fig4_leastnorm,
    fused_solve_bench,
    gradcomp_bench,
    ihs_baseline,
    kernel_bench,
    multiworker_gram_bench,
    privacy_bound,
    runtime_bench,
    serve_bench,
    sketch_dp_ablation,
    sketch_ops_bench,
    thm1_validation,
)

MODULES = {
    "thm1": thm1_validation,
    "bias": bias_bounds,
    "privacy": privacy_bound,
    "fig1": fig1_airline,
    "fig2": fig2_emnist,
    "fig3": fig3_synthetic,
    "fig4": fig4_leastnorm,
    "ihs": ihs_baseline,
    "gradcomp": gradcomp_bench,
    "sketch_dp": sketch_dp_ablation,
    "kernels": kernel_bench,
    "sketch_ops": sketch_ops_bench,
    "fused": fused_solve_bench,
    "multiworker": multiworker_gram_bench,
    "runtime": runtime_bench,
    "serve": serve_bench,
}


@sanctioned_wall_timer  # per-benchmark wall cost in the progress lines
def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes (slow)")
    ap.add_argument("--only", default="", help="comma-separated module keys")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="one tiny shape per benchmark (sets REPRO_BENCH_SMOKE=1) — the "
        "./test.sh --bench-smoke CI mode; numbers are not meaningful",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    keys = [k.strip() for k in args.only.split(",") if k.strip()] or list(MODULES)
    unknown = sorted(k for k in keys if k not in MODULES)
    if unknown:
        print(
            f"benchmarks.run: unknown benchmark key(s) {', '.join(unknown)}; "
            f"registered keys: {', '.join(sorted(MODULES))}",
            file=sys.stderr,
        )
        return 2
    envcfg.configure_compile_cache()
    failures = []
    for k in keys:
        mod = MODULES[k]
        t0 = time.time()
        print(f"\n########## {k} ({mod.__name__}) ##########", flush=True)
        try:
            mod.run(quick=not args.full)
            print(f"[{k}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(k)
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        return 1
    print(f"\nAll {len(keys)} benchmarks completed; CSVs in results/bench/")
    return 0


if __name__ == "__main__":
    sys.exit(main())
