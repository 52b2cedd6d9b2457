"""End-to-end driver: distributed sketched regression with straggler simulation.

Runs Algorithm 1 over a real jax mesh (shard_map workers + masked psum averaging),
with failures/deadline stragglers injected, multi-round elastic scaling, and the
privacy accountant on. Uses whatever devices exist (1 on this container — the mesh
logic is identical on a pod).

    PYTHONPATH=src python examples/distributed_regression.py --n 200000 --d 256 --workers 8
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core import averaging, distributed, privacy, sketches as sk, solve, theory
from repro.data import student_t_regression
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--m", type=int, default=0, help="sketch dim (default 8d)")
    ap.add_argument("--workers", type=int, default=8, help="logical workers (rounds x devices)")
    ap.add_argument("--sketch", default="gaussian", choices=list(sk.KINDS))
    ap.add_argument("--drop-prob", type=float, default=0.1)
    ap.add_argument("--deadline-quantile", type=float, default=0.9)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    A, b, _ = student_t_regression(key, args.n, args.d, df=2.5)
    x_star = solve.lstsq(A, b)
    f_star = float(solve.residual_cost(A, b, x_star))
    m = args.m or 8 * args.d
    spec = sk.SketchSpec(
        args.sketch, m, m_prime=4 * m if args.sketch == "hybrid" else 0
    )

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    rounds = max(1, args.workers // n_dev)
    q = n_dev * rounds
    print(f"devices={n_dev} rounds={rounds} -> q={q} workers, sketch={args.sketch} m={m}")

    # privacy accounting: the master ships q sketched copies
    acc = privacy.PrivacyAccountant()
    for w in range(q):
        acc.record(m, args.n, tag=f"worker{w}")
    print(acc.report())

    # straggler mask over all q logical workers
    mask = averaging.simulate_straggler_mask(
        jax.random.PRNGKey(1), q, drop_prob=args.drop_prob, deadline_quantile=args.deadline_quantile
    )
    arrived = int(mask.sum())

    # run Algorithm 1 round by round (elastic: each round is a fresh worker wave)
    acc_avg = averaging.StreamingAverage.init(args.d)
    for r in range(rounds):
        round_mask = mask[r * n_dev : (r + 1) * n_dev]
        if int(round_mask.sum()) == 0:
            # every worker of this wave straggled: there is nothing to average
            # (the eager driver raises on an empty round) — the master just moves
            # on to the next wave, exactly like the serverless deployment.
            print(f"round {r}: all workers straggled, skipping")
            continue
        xbar_r = distributed.distributed_sketch_solve(
            mesh, spec, key, A, b, straggler_mask=round_mask, round_id=r
        )
        # weight the round by its realized worker count
        for _ in range(int(round_mask.sum())):
            acc_avg = acc_avg.update(xbar_r)
    xbar = acc_avg.mean

    err = float(solve.relative_error(A, b, xbar, f_star))
    print(f"\narrived {arrived}/{q} workers (stragglers dropped, average unchanged in expectation)")
    print(f"rel_err = {err:.6f}")
    if args.sketch == "gaussian":
        print(f"Thm 1 with realized q'={arrived}: {theory.gaussian_averaged_error(m, args.d, max(arrived,1)):.6f}")


if __name__ == "__main__":
    main()
