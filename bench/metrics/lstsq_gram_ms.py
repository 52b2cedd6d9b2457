"""Device milliseconds per answer of the ops under the program's
``jax.named_scope("repro.solve_tail")``, which ``solve.lstsq_gram`` puts around the
d×d Cholesky tail in every path (master, mesh worker, served task). The mean over
the chips; on a mesh each chip solves its own worker."""
from bench import harness

SCOPE = "repro.solve_tail"


def read(ctx):
    return harness.load_module("metrics", "gram_input_ms").scope_ms(ctx, SCOPE)
