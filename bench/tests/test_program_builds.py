"""The reader of ``program_builds_per_job`` on hand-built traces whose answers are
known, and on a program whose task payloads write no build span."""
import os

import pytest
from test_program_spans import BUSY, DATA, SPANS, context, reader, write_trace

from bench import harness, trace

BUILDS = [("repro.task.build", 125.0, 1.0), ("repro.task.build", 605.0, 1.0), ("repro.task.build", -5.0, 1.0)]


@pytest.mark.parametrize(
    "spans, expected",
    [
        ([("bench.window", 0.0, 1000.0), ("repro.serve.job", 50.0, 400.0)], None),  # no task span
        (SPANS, 0.0),  # tasks, no build
        (SPANS + BUILDS, 1.0),  # one build a job; the one before the window is not counted
    ],
    ids=["no_task_span", "no_build", "one_build_a_job"],
)
def test_builds_per_job(tmp_path, spans, expected):
    p = str(tmp_path / "t.trace.json.gz")
    write_trace(p, [BUSY], spans)
    assert reader("program_builds_per_job")(context(p, 1, 2)) == expected


def test_a_program_without_build_spans_reads_nothing(tmp_path, monkeypatch):
    """Task spans but a program that declares no build span: it cannot say how often
    it builds, so the reader reads nothing rather than 0."""
    from repro.runtime import tasks

    monkeypatch.delattr(tasks, "BUILD_SPAN")
    p = str(tmp_path / "t.trace.json.gz")
    write_trace(p, [BUSY], SPANS)
    assert reader("program_builds_per_job")(context(p, 1, 2)) is None


def test_a_recorded_trace_without_task_spans_reads_nothing():
    ctx = trace.Context(
        cell=None, window=harness.Window(count=3, elapsed_s=0.0, answers=[], metrics={}), events=None,
        summary=trace.Summary.from_file(os.path.join(DATA, "fig2_served_small.trace.json.gz"), 1),
        work=(0, 0), device_kind="TPU v5 lite",
    )
    assert reader("program_builds_per_job")(ctx) is None
