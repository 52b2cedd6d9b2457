"""Serving launcher.

Two modes:

  * LM serving (the original):
        python -m repro.launch.serve --arch <id> --reduced
    boots the batched engine on a (reduced, CPU) model and runs a batch of
    synthetic requests through prefill + decode, reporting per-phase latency.

  * Sketch-solve job admission (the paper's serving path):
        python -m repro.launch.serve --solve --q 16 --backend process --adaptive
    boots a :class:`repro.serve.SolveServer`, admits ``--jobs`` synthetic
    regression jobs through the async runtime engine on the chosen executor
    backend, and prints per-job + aggregate telemetry (retries, timeouts, drops,
    effective q′, simulated makespan, relative error vs the exact solve).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.analysis.annotations import sanctioned_wall_timer
from repro.configs.base import get_config
from repro.models import lm
from repro.serve import Engine, ServeConfig, SolveServer
from repro.utils import env as envcfg


def _latency_model(args):
    from repro import runtime as rt

    if args.latency == "lognormal":
        return rt.LognormalLatency(seed=args.seed, mean_s=args.mean_s, sigma=0.5)
    if args.latency == "heavytail":
        return rt.HeavyTailLatency(seed=args.seed, scale_s=args.mean_s, alpha=1.5)
    if args.latency == "drift":
        return rt.DriftLatency(seed=args.seed, mean_s=args.mean_s, sigma=0.35, growth=1.3)
    if args.latency == "drop":
        return rt.DropLatency(
            seed=args.seed,
            inner=rt.LognormalLatency(seed=args.seed, mean_s=args.mean_s, sigma=0.5),
            drop_prob=0.2,
        )
    raise ValueError(f"unknown latency model {args.latency!r}")


@sanctioned_wall_timer  # reports wall cost of the admitted jobs to the operator
def solve_main(args) -> int:
    from repro import runtime as rt
    from repro.core import sketches as sk, solve

    key = jax.random.PRNGKey(args.seed)
    A = jax.random.normal(key, (args.n, args.d))
    x_true = jax.random.normal(jax.random.PRNGKey(args.seed + 1), (args.d,))
    b = A @ x_true + 0.1 * jax.random.normal(jax.random.PRNGKey(args.seed + 2), (args.n,))
    x_star = solve.lstsq(A, b)
    f_star = float(solve.residual_cost(A, b, x_star))

    spec = sk.SketchSpec(args.sketch, args.m)
    cfg = rt.RuntimeConfig(
        deadline_s=args.deadline, max_retries=args.retries,
        target_error=args.target_error, max_threads=args.pool,
    )
    deadline = rt.AdaptiveDeadline(warmup_s=args.deadline) if args.adaptive else None
    server = SolveServer(
        latency=_latency_model(args), config=cfg, backend=args.backend, deadline=deadline,
    )

    t0 = time.time()
    for j in range(args.jobs):
        job = server.submit_solve(
            A, b, spec, q=args.q, seed=args.seed + 17 * j, error_fn="probe",
        )
        f = float(solve.residual_cost(A, b, jnp.asarray(job.xbar, A.dtype)))
        rel = (f - f_star) / max(f_star, 1e-30)
        s = job.summary
        print(
            f"job {job.job_id}: q'={s['effective_q']}/{args.q} retries={s['retries']} "
            f"timeouts={s['timeouts']} drops={s['drops']} "
            f"makespan={s['sim_makespan_s']:.2f}s rel_err={rel:.3e}"
        )
    wall = time.time() - t0
    agg = server.telemetry()
    print(
        f"backend={agg['backend']} jobs={agg['jobs']} wall={wall:.2f}s "
        f"mean_q'={agg['effective_q_mean']:.1f} retries={agg['retries']} "
        f"timeouts={agg['timeouts']} drops={agg['drops']} "
        f"adaptive_deadline={bool(args.adaptive)}"
    )
    return 0


@sanctioned_wall_timer  # reports tok/s to the operator
def lm_main(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    sc = ServeConfig(
        max_batch=4, max_len=args.prompt_len + args.max_new + 8, temperature=args.temperature
    )
    engine = Engine(cfg, params, sc)

    prompts = [
        list(range(3 + (i % 5), 3 + (i % 5) + args.prompt_len - (i % 4))) for i in range(args.requests)
    ]
    kwargs = {}
    if cfg.encdec:
        kwargs["frames"] = jax.random.normal(key, (sc.max_batch, cfg.enc_seq, cfg.d_model), jnp.float32)
    t0 = time.time()
    outs = engine.generate(prompts, max_new_tokens=args.max_new, **kwargs)
    dt = time.time() - t0
    toks = sum(len(o) for o in outs)
    print(f"arch={cfg.name} requests={len(prompts)} new_tokens={toks} wall={dt:.2f}s ({toks/dt:.1f} tok/s)")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: prompt={prompts[i][:6]}... -> {o[:12]}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LM mode: architecture id")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # ------------------------------------------------ sketch-solve serving mode
    ap.add_argument("--solve", action="store_true", help="admit sketch-solve jobs")
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--q", type=int, default=16)
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--sketch", default="gaussian")
    ap.add_argument("--backend", default="thread", choices=("inline", "thread", "process"))
    ap.add_argument("--pool", type=int, default=4, help="executor pool width")
    ap.add_argument("--latency", default="lognormal",
                    choices=("lognormal", "heavytail", "drift", "drop"))
    ap.add_argument("--mean-s", type=float, default=1.0, help="latency scale/median")
    ap.add_argument("--deadline", type=float, default=2.0)
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--adaptive", action="store_true", help="rolling-p95 deadlines")
    ap.add_argument("--target-error", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    envcfg.configure_compile_cache()

    if args.solve:
        return solve_main(args)
    if args.arch is None:
        ap.error("pass --arch <id> (LM serving) or --solve (sketch-solve serving)")
    return lm_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
