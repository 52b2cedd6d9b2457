"""Task programs built per served job: the ``repro.task.build`` host spans that start
in the window, over the jobs. A payload opens the span only when its process has no
program for its configuration yet, so the span is counted, not timed (JAX traces and
compiles the program at its first call). Nothing to read: no ``repro.task`` span, or
a program whose task payloads write no build span (they then build every job)."""
import importlib

TASK_SPAN = "repro.task"
BUILD_SPAN = "repro.task.build"


def writes_build_spans() -> bool:
    """The program's task payloads open ``repro.task.build`` on a build."""
    try:
        tasks = importlib.import_module("repro.runtime.tasks")
    except ImportError:
        return False
    return getattr(tasks, "BUILD_SPAN", None) == BUILD_SPAN


def read(ctx):
    s = ctx.summary
    if not any(n == TASK_SPAN for n, _, _ in s.host) or not writes_build_spans():
        return None
    return sum(n == BUILD_SPAN and s.t0 <= a < s.t1 for n, a, _ in s.host) / ctx.window.count
