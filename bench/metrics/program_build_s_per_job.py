"""Seconds per job that JAX spent building programs inside the window: the summed
durations of its trace, lowering and backend-compile events (``jax.monitoring``;
the last also when the executable comes from the persistent cache), over the jobs."""


def read(ctx):
    return ctx.events.build_seconds / ctx.window.count
