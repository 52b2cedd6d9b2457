"""The readers of the program's own scopes and spans, on hand-built traces whose
answers are known, and on the recorded traces of a program that has none of them.

Device scopes (``repro.gram.input``, ``repro.gram.sketch_params``,
``repro.solve_tail``) are read from each op's ``tf_op``; host spans
(``repro.serve.job``, ``repro.task``, ``repro.task.upload``) by name. Task spans of
the pool's threads overlap: the readers unite them.
"""
import gzip
import json
import os

import numpy as np
import pytest

from bench import harness, trace

DATA = os.path.join(harness.BENCH, "testdata")
NEW = (
    "gram_input_ms", "gram_input_ms.served", "sketch_params_ms", "sketch_params_ms.served",
    "lstsq_gram_ms", "idle_task_s_per_job", "idle_master_s_per_job", "uploads_per_job",
)


def reader(name):
    return harness.load_module("metrics", name).read


def write_trace(path, chips_ops, host_spans):
    """Chips' ``XLA Ops`` (name, start µs, dur µs, tf_op) and host spans (name, start, dur)."""
    ev = [{"ph": "M", "pid": 100, "name": "process_name", "args": {"name": "/host:CPU"}}]
    for c, ops in enumerate(chips_ops):
        ev += [
            {"ph": "M", "pid": c, "name": "process_name", "args": {"name": f"/device:TPU:{c}"}},
            {"ph": "M", "pid": c, "tid": 1, "name": "thread_name", "args": {"name": "XLA Ops"}},
        ]
        ev += [{"ph": "X", "pid": c, "tid": 1, "ts": s, "dur": d, "name": n, "args": {"tf_op": op}} for n, s, d, op in ops]
    ev += [{"ph": "X", "pid": 100, "tid": 1 + i, "ts": s, "dur": d, "name": n} for i, (n, s, d) in enumerate(host_spans)]
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)


def context(path, chips, answers):
    return trace.Context(
        cell=None, window=harness.Window(count=answers, elapsed_s=0.0, answers=[], metrics={}),
        events=None, summary=trace.Summary.from_file(path, chips), work=(0, 0), device_kind="TPU v5 lite",
    )


def test_scope_readers_take_the_mean_over_chips(tmp_path):
    p = str(tmp_path / "t.trace.json.gz")
    chip0 = [
        ("fusion.1", 0.0, 40.0, "jit(solve_once)/jit(sjlt_gram_multi)/repro.gram.input/pad"),
        ("copy.2", 40.0, 10.0, "jit(solve_once)/repro.gram.input/concatenate"),
        ("fusion.3", 50.0, 30.0, "jit(solve_once)/repro.gram.sketch_params/vmap(threefry2x32)"),
        ("sjlt_gram.4", 80.0, 100.0, "jit(solve_once)/jit(sjlt_gram_multi)/sjlt_gram"),
        ("fusion.5", 180.0, 8.0, "jit(solve_once)/bench.solve_tail/vmap(repro.solve_tail)/jit(cholesky)/cholesky:"),
        ("fusion.6", 188.0, 2.0, "jit(solve_once)/repro.gram.inputs/add"),  # another scope, not a prefix match
    ]
    chip1 = [("fusion.1", 0.0, 30.0, "jit(f)/repro.gram.input/pad"), ("fusion.5", 30.0, 4.0, "jit(f)/repro.solve_tail/div:")]
    write_trace(p, [chip0, chip1], [("bench.window", 0.0, 200.0)])
    ctx = context(p, 2, 2)
    assert reader("gram_input_ms")(ctx) == pytest.approx((50 + 30) / 2 / 2 / 1000)  # µs → ms, mean of 2 chips, 2 answers
    assert reader("gram_input_ms.served")(ctx) == reader("gram_input_ms")(ctx)
    assert reader("sketch_params_ms")(ctx) == pytest.approx(30 / 2 / 2 / 1000)
    assert reader("sketch_params_ms.served")(ctx) == reader("sketch_params_ms")(ctx)
    assert reader("lstsq_gram_ms")(ctx) == pytest.approx((8 + 4) / 2 / 2 / 1000)
    # in the master cells the new scope sits inside the harness's, under its vmap: both read the same op
    assert reader("lstsq_gram_ms")(context_one_chip(tmp_path, chip0)) == reader("solve_tail_ms")(context_one_chip(tmp_path, chip0))


def context_one_chip(tmp_path, ops):
    p = str(tmp_path / "one.trace.json.gz")
    write_trace(p, [ops], [("bench.window", 0.0, 200.0)])
    return context(p, 1, 2)


# window [0, 1000] µs; the chip is busy in [0, 100], [300, 400] and [900, 1000]
BUSY = [("sjlt_gram.1", 0.0, 100.0, ""), ("sjlt_gram.2", 300.0, 100.0, ""), ("sjlt_gram.3", 900.0, 100.0, "")]
SPANS = [
    ("bench.window", 0.0, 1000.0),
    ("repro.serve.job", 50.0, 400.0),  # [50, 450]
    ("repro.serve.job", 520.0, 480.0),  # [520, 1000]
    ("repro.task", 120.0, 130.0),  # [120, 250], overlaps the next
    ("repro.task", 200.0, 80.0),  # [200, 280]
    ("repro.task", 600.0, 100.0),  # [600, 700], inside the next
    ("repro.task", 650.0, 300.0),  # [650, 950], runs into a busy stretch
    ("repro.task.upload", 130.0, 1.0),
    ("repro.task.upload", 610.0, 1.0),
    ("repro.task.upload", -10.0, 1.0),  # before the window: not counted
]


def test_idle_is_split_between_tasks_and_the_master(tmp_path):
    p = str(tmp_path / "t.trace.json.gz")
    write_trace(p, [BUSY], SPANS)
    ctx = context(p, 1, 2)
    # idle [100, 300] and [400, 900]; tasks' union [120, 280] and [600, 950]:
    # 160 + 300 µs idle under a task (summed, the overlaps would read 560)
    task = reader("idle_task_s_per_job")(ctx)
    assert task == pytest.approx(460e-6 / 2)
    # jobs less tasks: [50, 120], [280, 450], [520, 600], [950, 1000]; idle there 20 + 20 + 50 + 80
    master = reader("idle_master_s_per_job")(ctx)
    assert master == pytest.approx(170e-6 / 2)
    idle = ctx.summary.window_s - ctx.summary.busy_s
    assert (task + master) * 2 <= idle + 1e-12  # [450, 520] is idle with no job open
    assert reader("uploads_per_job")(ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("seed", range(20))
def test_idle_parts_never_exceed_the_idle_time(tmp_path, seed):
    rng = np.random.default_rng(seed)

    def intervals(k, name):
        starts = rng.uniform(-50, 1000, k)
        return [(name, float(s), float(d)) for s, d in zip(starts, rng.uniform(1, 300, k))]

    ops = [(f"op.{i}", s, d, "") for i, (_, s, d) in enumerate(intervals(8, "op"))]
    spans = [("bench.window", 0.0, 1000.0)] + intervals(3, "repro.serve.job") + intervals(9, "repro.task")
    p = str(tmp_path / "r.trace.json.gz")
    write_trace(p, [ops], spans)
    ctx = context(p, 1, 1)
    idle = ctx.summary.window_s - ctx.summary.busy_s
    task, master = reader("idle_task_s_per_job")(ctx), reader("idle_master_s_per_job")(ctx)
    assert 0 <= task and 0 <= master and task + master <= idle + 1e-12


@pytest.mark.parametrize("recorded", ["fig2_master_small", "fig2_served_small"])
def test_a_program_without_scopes_or_spans_reads_nothing(recorded):
    """The recorded traces come from a program with none of the new scopes and spans:
    every new reader returns None there, and raises nothing."""
    ctx = trace.Context(
        cell=None, window=harness.Window(count=3, elapsed_s=0.0, answers=[], metrics={}), events=None,
        summary=trace.Summary.from_file(os.path.join(DATA, recorded + ".trace.json.gz"), 1),
        work=(0, 0), device_kind="TPU v5 lite",
    )
    assert {name: reader(name)(ctx) for name in NEW} == dict.fromkeys(NEW)
