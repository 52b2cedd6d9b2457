"""Chip-idle seconds per job while some ``repro.task`` host span is open: the idle
time of each chip (the window less the union of its op intervals) intersected with
the union of the task spans, clipped to the window; the mean over the chips, over
the jobs. Task spans of the pool's threads overlap, so they are united, never
summed. Nothing to read: no task span (a program without them)."""
from bench import trace

TASK_SPAN = "repro.task"
JOB_SPAN = "repro.serve.job"


def spans(s, name) -> list:
    """The union of the host spans named ``name``, clipped to the window."""
    return trace._union((max(a, s.t0), min(b, s.t1)) for n, a, b in s.host if n == name and b > s.t0 and a < s.t1)


def idle(s, chip) -> list:
    """The chip's idle intervals inside the window."""
    edges = [s.t0] + [x for iv in s.busy_intervals(chip) for x in iv] + [s.t1]
    return [[a, b] for a, b in zip(edges[::2], edges[1::2]) if b > a]


def intersect(xs, ys) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def length_s(xs) -> float:
    return sum(b - a for a, b in xs) / 1e9


def per_job(ctx, chip_seconds):
    """``chip_seconds(chip)`` averaged over the chips, over the window's jobs."""
    s = ctx.summary
    return sum(chip_seconds(c) for c in s.ops) / len(s.ops) / ctx.window.count


def read(ctx):
    s = ctx.summary
    tasks = spans(s, TASK_SPAN)
    if not tasks:
        return None
    return per_job(ctx, lambda c: length_s(intersect(idle(s, c), tasks)))
