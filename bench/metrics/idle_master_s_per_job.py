"""Chip-idle seconds per job inside a ``repro.serve.job`` host span while no
``repro.task`` span is open: the master's own share of the idle time (admission,
payload, engine loop, averaging), beside ``idle_task_s_per_job``. The two never add
up to more than the idle time. Nothing to read: no job span."""
from bench import harness

_tasks = harness.load_module("metrics", "idle_task_s_per_job")


def _outside(xs, ys, t0, t1) -> list:
    """The intervals of ``xs`` that no interval of ``ys`` covers."""
    edges = [t0] + [x for iv in ys for x in iv] + [t1]
    gaps = [[a, b] for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return _tasks.intersect(xs, gaps)


def read(ctx):
    s = ctx.summary
    jobs = _tasks.spans(s, _tasks.JOB_SPAN)
    if not jobs:
        return None
    master = _outside(jobs, _tasks.spans(s, _tasks.TASK_SPAN), s.t0, s.t1)
    return _tasks.per_job(ctx, lambda c: _tasks.length_s(_tasks.intersect(_tasks.idle(s, c), master)))
