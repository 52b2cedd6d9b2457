"""Multi-worker fused Gram: one launch for all q sketches vs the per-worker loop,
and the cheap counter-RNG Rademacher family vs the Gaussian draw.

Writes ``results/bench/BENCH_multiworker_gram.json``. Three claims:

  1. Fused q-worker launch (``*_gram_multi``, what ``operators.gram_batched``
     dispatches to for kernel-routed specs) reads A once for all q workers
     instead of q times — ``fused_vs_loop`` per family.
  2. The Rademacher family replaces the per-entry threefry + Box-Muller Gaussian
     draw with one threefry word per 32 entries (``rng_share`` =
     t(gaussian)/t(rademacher) at equal shapes, fused mode).
  3. The headline: the status-quo path before this PR was a per-worker loop of
     Gaussian gram launches; the new path is the fused multi-worker Rademacher
     launch. ``headline_speedup`` = t(gaussian loop)/t(rademacher fused) must be
     ≥ 1.5x at q=8, n=131072, d=256, m=1024.

An extra subprocess row times the gaussian fused gram under REPRO_RNG_ROUNDS=8
(the reduced-round threefry variant; trace-time knob, hence the subprocess)
against the 20-round default in identical conditions.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp

from benchmarks.common import RESULTS_DIR, block, print_table, smoke, write_csv
from repro.analysis.annotations import sanctioned_wall_timer
from repro.utils import prng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Acceptance shape: q workers each sketching the same (n, d) A down to m rows.
FULL_SHAPE = dict(q=8, n=131072, d=256, m=1024)
SMOKE_SHAPE = dict(q=4, n=4096, d=64, m=128)


@sanctioned_wall_timer
def _time(fn, repeat: int) -> float:
    block(fn())
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        block(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _family_fns(family: str, m: int):
    if family == "gaussian":
        from repro.kernels.gaussian import ops as fam_ops

        return (
            lambda keys, A: fam_ops.gaussian_gram_multi(keys, A, m),
            lambda key, A: fam_ops.gaussian_gram(key, A, m),
        )
    from repro.kernels.rademacher import ops as fam_ops

    return (
        lambda keys, A: fam_ops.rademacher_gram_multi(keys, A, m),
        lambda key, A: fam_ops.rademacher_gram(key, A, m),
    )


def _bench_reduced_rounds(shape: dict, repeat: int) -> dict:
    """REPRO_RNG_ROUNDS is resolved at trace time, so both variants are traced and
    timed inside one subprocess with the env flipped between traces."""
    script = textwrap.dedent(
        f"""
        import os, json, time
        import jax, jax.numpy as jnp
        from repro.kernels.gaussian import ops as gops
        from repro.utils import prng

        q, n, d, m = {shape["q"]}, {shape["n"]}, {shape["d"]}, {shape["m"]}
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
        keys = prng.worker_keys(jax.random.PRNGKey(1), q)

        def timeit(fn, repeat={repeat}):
            jax.block_until_ready(fn())
            ts = []
            for _ in range(repeat):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append(time.perf_counter() - t0)
            return min(ts)

        os.environ["REPRO_RNG_ROUNDS"] = "20"
        t20 = timeit(jax.jit(lambda: gops.gaussian_gram_multi(keys, A, m)))
        os.environ["REPRO_RNG_ROUNDS"] = "8"
        t8 = timeit(jax.jit(lambda: gops.gaussian_gram_multi(keys, A, m)))
        print(json.dumps({{"rounds20_s": t20, "rounds8_s": t8, "speedup": t20 / t8}}))
        """
    )
    # The child runs on the CPU: the parent has imported JAX, and on a chip it holds it.
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=1800, env=env
    )
    if out.returncode != 0:
        print(f"WARN: reduced-rounds subprocess failed:\n{out.stderr[-2000:]}")
        return {"error": "subprocess failed"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(quick: bool = True):
    shape = SMOKE_SHAPE if smoke() else FULL_SHAPE
    q, n, d, m = shape["q"], shape["n"], shape["d"], shape["m"]
    repeat = 2 if smoke() else 3

    A = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    keys = prng.worker_keys(jax.random.PRNGKey(1), q)

    rows = []
    times = {}
    for family in ("gaussian", "rademacher"):
        multi, single = _family_fns(family, m)
        fused = jax.jit(lambda keys=keys, A=A, multi=multi: multi(keys, A))
        loop = jax.jit(
            lambda keys=keys, A=A, single=single: jax.lax.map(lambda k: single(k, A), keys)
        )
        t_fused = _time(fused, repeat)
        t_loop = _time(loop, repeat)
        # parity sanity: fused worker slices == loop worker slices, bitwise
        same = bool(jnp.all(fused() == loop()))
        times[family] = {"fused": t_fused, "loop": t_loop}
        for mode, t in (("loop", t_loop), ("fused", t_fused)):
            rows.append(
                {
                    "family": family,
                    "mode": mode,
                    "q": q,
                    "n": n,
                    "d": d,
                    "m": m,
                    "ms": t * 1e3,
                    "fused_vs_loop": t_loop / t_fused if mode == "fused" else 1.0,
                    "bitwise_match": same,
                }
            )

    summary = {
        "backend": jax.default_backend(),
        "shape": shape,
        "rows": rows,
        "fused_vs_loop": {
            fam: times[fam]["loop"] / times[fam]["fused"] for fam in times
        },
        # RNG share at equal shape/mode: the matmul work is identical, so the gap
        # is the Gaussian draw (threefry + Box-Muller per entry vs 1 word / 32).
        "rng_share_fused": times["gaussian"]["fused"] / times["rademacher"]["fused"],
        # Status quo before this PR (per-worker Gaussian gram launches) vs the
        # new path (one Rademacher launch for all q workers).
        "headline_speedup": times["gaussian"]["loop"] / times["rademacher"]["fused"],
        "reduced_rounds_gaussian": _bench_reduced_rounds(shape, repeat),
    }

    write_csv("multiworker_gram_bench", rows)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    json_path = os.path.join(RESULTS_DIR, "BENCH_multiworker_gram.json")
    with open(json_path, "w") as f:
        json.dump(summary, f, indent=2)
    print_table("multi-worker gram: fused single launch vs per-worker loop", rows)
    print(f"JSON summary: {json_path}")

    h = summary["headline_speedup"]
    if smoke():
        print("SMOKE: shapes are tiny; speedup numbers not meaningful")
    elif h >= 1.5:
        print(
            f"PASS: fused multi-worker rademacher gram {h:.2f}x over the per-worker "
            f"gaussian loop at q={q} n={n} d={d} m={m}"
        )
    else:
        print(f"WARN: headline speedup {h:.2f}x < 1.5x on this host — see {json_path}")
    return rows
