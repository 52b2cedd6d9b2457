"""The reduction from a profiler trace to busy, idle, kernel and scope time.

``bench/testdata/fig2_master_small.trace.json.gz`` is a trace of a fig2.master
window taken through ``run.run_cell`` on a TPU v5 lite (n cut to 2^16, a 2 s
window, the profiler's directory kept instead of deleted);
``fig2_served_small.trace.json.gz`` the same for fig2.served (2^13 rows per
dataset). Each keeps every event, with its args cut to ``tf_op``, the one the
reduction reads. The numbers pinned are what the chip run printed from the whole
trace. The synthetic test pins the arithmetic on events whose answer is known.
"""
import gzip
import json
import os

import pytest

from bench import harness, trace, work

DATA = os.path.join(harness.BENCH, "testdata")


def write_trace(path, device_ops, host_spans):
    """A minimal trace: one chip, its ``XLA Ops`` thread, and host spans (times in µs)."""
    ev = [
        {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 7, "name": "process_name", "args": {"name": "/host:CPU"}},
    ]
    ev += [{"ph": "X", "pid": 3, "tid": 3, "ts": s, "dur": d, "name": n, "args": {"tf_op": scope}} for n, s, d, scope in device_ops]
    ev += [{"ph": "X", "pid": 3, "tid": 2, "ts": 0.0, "dur": 1000.0, "name": "jit_solve(1)"}]  # a module: not an op
    ev += [{"ph": "X", "pid": 7, "tid": 1, "ts": s, "dur": d, "name": n} for n, s, d in host_spans]
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)


def test_synthetic_trace(tmp_path):
    p = str(tmp_path / "t.trace.json.gz")
    write_trace(
        p,
        [
            ("sjlt_gram.1", 10.0, 50.0, "jit(f)/sjlt_gram"),
            ("fusion.3", 40.0, 30.0, "jit(f)/bench.solve_tail/vmap(cholesky)/div"),  # overlaps the kernel
            ("copy.2", 80.0, 10.0, "jit(f)/copy"),
            ("copy.9", 95.0, 20.0, "jit(f)/copy"),  # runs past the window's end
            ("fusion.1", 200.0, 5.0, "jit(f)/add"),  # after the window
        ],
        [("bench.window", 0.0, 100.0), ("bench.step", 0.0, 50.0), ("bench.step", 50.0, 50.0), ("PjitFunction(f)", 72.0, 6.0)],
    )
    s = trace.Summary.from_file(p, 1)
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(75e-6)  # [10, 70] + [80, 90] + [95, 100]
    assert s.kernel_s(0, ("sjlt_gram",)) == pytest.approx(50e-6)
    assert s.scope_s(0, "bench.solve_tail") == pytest.approx(30e-6)
    b = s.breakdown()
    assert b["device_ops"][0] == ["sjlt_gram", pytest.approx(50e-6)]
    assert ["bench.solve_tail/fusion", pytest.approx(30e-6)] in b["device_ops"]
    # gaps [70, 80), [0, 10), [90, 95), longest first, named by the host span open at their middle
    assert b["idle_gaps"] == [
        ["PjitFunction(f)", pytest.approx(10e-6)], ["bench.step", pytest.approx(10e-6)], ["bench.step", pytest.approx(5e-6)]
    ]
    assert len(b["idle_gaps"]) <= trace.TOP and len(b["device_ops"]) <= trace.TOP


def test_trace_without_window_is_refused(tmp_path):
    p = str(tmp_path / "t.trace.json.gz")
    write_trace(p, [("sjlt_gram.1", 10.0, 50.0, "")], [("bench.step", 0.0, 100.0)])
    with pytest.raises(RuntimeError):
        trace.Summary.from_file(p, 1)


def reader(name):
    return harness.load_module("metrics", name).read


def context(summary, answers, work_per_answer=(0, 0)):
    return trace.Context(
        cell=None, window=harness.Window(count=answers, elapsed_s=0.0, answers=[], metrics={}),
        events=None, summary=summary, work=work_per_answer, device_kind="TPU v5 lite",
    )


def test_recorded_master_trace():
    """26 solves of fig2.master at n=2^16 (q=8, m=2000, k=831), as the chip run read them."""
    s = trace.Summary.from_file(os.path.join(DATA, "fig2_master_small.trace.json.gz"), 1)
    assert s.window_s == pytest.approx(2.071818225, rel=1e-6)
    assert s.busy_s == pytest.approx(2.040533095, rel=1e-6)
    ctx = context(s, 26, work.gram_work(8, 2000, 2**16, 831, nnz=20))
    # SJLT's count is memory-bound: the read of [A | b] at 819e9 B/s, 0.27 ms a solve
    assert reader("gram_roofline")(ctx) == pytest.approx(0.39593504340222024, rel=1e-6)
    assert reader("solve_tail_ms")(ctx) == pytest.approx(2.4907520384615385, rel=1e-6)
    assert reader("idle_pct.master")(ctx) == pytest.approx(1.5100325705456163, rel=1e-6)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "sjlt_gram" and len(b["idle_gaps"]) == trace.TOP


def test_recorded_served_trace():
    """3 fig2.served jobs at 2^13 rows per dataset: the device is idle nearly all the time."""
    s = trace.Summary.from_file(os.path.join(DATA, "fig2_served_small.trace.json.gz"), 1)
    ctx = context(s, 3)
    assert s.busy_s == pytest.approx(0.039622875, rel=1e-6)
    assert reader("idle_pct.served")(ctx) == pytest.approx(98.29801781756615, rel=1e-6)
    assert reader("solve_tail_ms")(ctx) is None  # no bench.solve_tail scope on the served path
    assert s.breakdown()["idle_gaps"][0][0] == "PjitFunction(<lambda>)"  # the per-job program build


def test_roofline_reads_nothing_without_kernel(tmp_path):
    p = str(tmp_path / "t.trace.json.gz")
    write_trace(p, [("fusion.1", 10.0, 50.0, "")], [("bench.window", 0.0, 100.0)])
    ctx = context(trace.Summary.from_file(p, 1), 1, (10**9, 10**6))
    assert reader("gram_roofline")(ctx) is None
