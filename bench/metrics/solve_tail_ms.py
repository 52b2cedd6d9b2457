"""Device milliseconds per answer of the ops under ``jax.named_scope("bench.solve_tail")``,
the scope the master runner puts around ``jax.vmap(solve.lstsq_gram)``."""

SCOPE = "bench.solve_tail"


def read(ctx):
    t = ctx.summary.scope_s(0, SCOPE)
    return 1000.0 * t / ctx.window.count if t > 0 else None
