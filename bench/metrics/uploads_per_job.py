"""Device copies of [A | b] per served job: the ``repro.task.upload`` host spans that
start in the window, over the jobs. The copy is enqueued, so the span is counted,
not timed. Nothing to read: no ``repro.task`` span (a program without the spans)."""

TASK_SPAN = "repro.task"
UPLOAD_SPAN = "repro.task.upload"


def read(ctx):
    s = ctx.summary
    if not any(n == TASK_SPAN for n, _, _ in s.host):
        return None
    return sum(n == UPLOAD_SPAN and s.t0 <= a < s.t1 for n, a, _ in s.host) / ctx.window.count
