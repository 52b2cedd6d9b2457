"""Device milliseconds per answer of the ops under the program's
``jax.named_scope("repro.gram.sketch_params")``: the sketch's own per-row parameters
made before the kernel (SJLT's (q, n, s) buckets and signs, hashed, padded and
transposed; SRHT's rows and signs). The mean over the chips."""
from bench import harness

SCOPE = "repro.gram.sketch_params"


def read(ctx):
    return harness.load_module("metrics", "gram_input_ms").scope_ms(ctx, SCOPE)
