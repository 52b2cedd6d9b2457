"""90th percentile of the jobs' latency from submit to return, host clock, over
every job in the window."""
from bench import harness


def read(ctx):
    return harness.quantile(ctx.window.latencies_s, 0.9)
