"""Fused single-pass sketch→Gram solve vs the two-pass reference, plus the
mesh-vs-loop dispatch of multi-worker batching.

Writes ``results/bench/BENCH_fused_solve.json`` with op/backend/shape, ms and
effective GB/s so the perf trajectory is tracked across PRs. Two claims:

  1. ``sketch_and_solve(method="fused")`` — one streamed pass over [A | b]
     accumulating (G, c), then a d×d Cholesky — beats the two-pass reference
     (materialize (SA, Sb), then QR) at the large-n shape. The headline row is
     the SJLT, where the sketch pass is cheap enough that the avoided SA
     materialization and the QR→Cholesky tail dominate.
  2. ``apply_batched`` dispatch: the shard_map-over-mesh path is only taken when
     the mesh has real devices to shard over (``operators._mesh_batch_enabled``);
     on forced host devices the auto path falls back to the loop, so batched
     dispatch is never slower than the loop fallback. Both forced-mesh and auto
     timings are recorded for SRHT.
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
import numpy as np

from repro.core import sketches as sk, solve
from benchmarks.common import RESULTS_DIR, block, print_table, smoke as _smoke, write_csv
from repro.analysis.annotations import sanctioned_wall_timer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@sanctioned_wall_timer
def _time_pair(fn_a, fn_b, repeat: int = 7):
    """Interleaved min-of-``repeat`` wall seconds for two thunks (after warmup)."""
    block(fn_a())
    block(fn_b())
    ta, tb = [], []
    for _ in range(repeat):
        t0 = time.perf_counter()
        block(fn_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        block(fn_b())
        tb.append(time.perf_counter() - t0)
    return min(ta), min(tb)


def _shapes(quick: bool):
    """(label, spec_builder, n, d, m, headline)."""
    if _smoke():
        return [
            ("sjlt_s4", lambda m: sk.SketchSpec("sjlt", m, s=4), 2048, 32, 128, True),
            ("gaussian", lambda m: sk.SketchSpec("gaussian", m), 2048, 32, 128, False),
            ("srht", lambda m: sk.SketchSpec("srht", m), 2048, 32, 128, False),
        ]
    n_big = 65536 if quick else 262144
    return [
        # headline large-n shape: sparse sketch, fat head — the regime the fused
        # path targets (sketch pass cheap, SA materialization + QR tail visible)
        ("sjlt_s4", lambda m: sk.SketchSpec("sjlt", m, s=4), n_big * 2 if quick else n_big, 256, 1024, True),
        ("gaussian", lambda m: sk.SketchSpec("gaussian", m), n_big, 32, 256, False),
        ("srht", lambda m: sk.SketchSpec("srht", m), n_big, 64, 512, False),
    ]


def _bench_mesh_srht(quick: bool) -> dict:
    """Forced-mesh vs loop apply_batched for SRHT, on 8 fake host devices (subprocess
    so the device count never leaks into this process)."""
    n = 2048 if _smoke() else 65536
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, time
        import jax, jax.numpy as jnp
        from repro.core import operators as ops, sketches as sk
        from repro.utils import prng

        n, d, m, q = {n}, 64, 512, 8
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
        keys = prng.worker_keys(jax.random.PRNGKey(1), q)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("workers",))
        spec = sk.SketchSpec("srht", m)

        def timeit(fn, repeat=5):
            jax.block_until_ready(fn())
            ts = []
            for _ in range(repeat):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append(time.perf_counter() - t0)
            return min(ts)

        os.environ["REPRO_MESH_BATCH"] = "1"
        t_mesh = timeit(jax.jit(lambda: ops.apply_batched(spec, keys, A, mesh=mesh, axis_names=("workers",))))
        os.environ["REPRO_MESH_BATCH"] = "0"
        t_auto = timeit(jax.jit(lambda: ops.apply_batched(spec, keys, A, mesh=mesh, axis_names=("workers",))))
        t_loop = timeit(jax.jit(lambda: ops.apply_batched(spec, keys, A)))
        print(json.dumps({{"n": n, "d": d, "m": m, "q": q,
                           "mesh_forced_s": t_mesh, "auto_s": t_auto, "loop_s": t_loop}}))
        """
    )
    # The child runs on the CPU: the parent has imported JAX, and on a chip it holds it.
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=900, env=env
    )
    if out.returncode != 0:
        print(f"WARN: mesh-vs-loop subprocess failed:\n{out.stderr[-2000:]}")
        return {"error": "subprocess failed"}
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    rec["auto_vs_loop"] = rec["loop_s"] / rec["auto_s"]
    rec["auto_no_slower_than_loop"] = bool(rec["auto_s"] <= rec["loop_s"] * 1.1)
    rec["mesh_forced_vs_loop"] = rec["loop_s"] / rec["mesh_forced_s"]
    return rec


def run(quick: bool = True):
    repeat = 3 if _smoke() else 7
    rows = []
    summary = {"backend": jax.default_backend(), "shapes": {}}

    for i, (label, mk_spec, n, d, m, headline) in enumerate(_shapes(quick)):
        spec = mk_spec(m)
        key = jax.random.fold_in(jax.random.PRNGKey(0), i)
        A = jax.random.normal(key, (n, d), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
        fused = jax.jit(lambda k, A, b, spec=spec: solve.sketch_and_solve(spec, k, A, b))
        twopass = jax.jit(
            lambda k, A, b, spec=spec: solve.sketch_and_solve(spec, k, A, b, method="qr")
        )
        t_fused, t_two = _time_pair(
            lambda: fused(key, A, b), lambda: twopass(key, A, b), repeat=repeat
        )
        # solutions agree to fp32 tolerance (same S under the same key)
        x_f, x_q = fused(key, A, b), twopass(key, A, b)
        err = float(jnp.max(jnp.abs(x_f - x_q)) / jnp.maximum(jnp.max(jnp.abs(x_q)), 1e-30))
        bytes_pass = 4 * n * (d + 1)  # one streamed read of [A | b]
        row = {
            "op": label,
            "backend": summary["backend"],
            "n": n,
            "d": d,
            "m": m,
            "fused_ms": t_fused * 1e3,
            "twopass_ms": t_two * 1e3,
            "speedup": t_two / t_fused,
            "fused_gbps": bytes_pass / t_fused / 1e9,
            "rel_err": err,
            "headline": headline,
        }
        rows.append(row)
        summary["shapes"][label] = row
        if headline:
            summary["headline"] = row

    summary["mesh_apply_batched_srht"] = _bench_mesh_srht(quick)

    write_csv("fused_solve_bench", rows)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    json_path = os.path.join(RESULTS_DIR, "BENCH_fused_solve.json")
    with open(json_path, "w") as f:
        json.dump(summary, f, indent=2)
    print_table("fused single-pass solve vs two-pass (materialize SA + QR)", rows)
    print(f"JSON summary: {json_path}")

    h = summary.get("headline", {})
    if _smoke():
        print("SMOKE: shapes are tiny; speedup numbers not meaningful")
    elif h.get("speedup", 0.0) >= 1.5:
        print(
            f"PASS: fused solve {h['speedup']:.2f}x over materialize-then-Gram at "
            f"n={h['n']} d={h['d']} m={h['m']} ({h['op']})"
        )
    else:
        print(
            f"WARN: fused headline speedup {h.get('speedup', 0.0):.2f}x < 1.5x on this "
            f"host — see {json_path}"
        )
    mesh = summary["mesh_apply_batched_srht"]
    if mesh.get("auto_no_slower_than_loop"):
        print(
            f"PASS: batched SRHT auto-dispatch no slower than loop "
            f"(auto {mesh['auto_s']*1e3:.1f}ms vs loop {mesh['loop_s']*1e3:.1f}ms; "
            f"forced mesh on fake devices: {mesh['mesh_forced_s']*1e3:.1f}ms)"
        )
    elif "error" not in mesh:
        print(f"WARN: batched SRHT auto path slower than loop — see {json_path}")
    return rows
