#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result as the last stdout line.

    python3 bench/run.py --workload fig3a.master --seed 7 --seconds 30 --trace 0

From the root of a checkout. ``--trace 0`` prints the cell's end-to-end metrics
(``setup_s`` and the runner's own, host clock); ``--trace 1`` takes a profiler trace
of the window and prints the cell's per-layer metrics, the device's busy and window
seconds, and a breakdown. Either way the answers made in the window are judged
against the plain reference afterwards (``correct``), and each number compared is
printed beside its limit: last on stderr, and last in the result line.

Exits 3 with no result line when JAX's first device is not a TPU, or when there are
fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import harness  # noqa: E402

sys.path.insert(1, harness.SRC)


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The cell's end-to-end and per-layer metrics. A metric with a ``workloads`` key
    applies to the cells it lists; a per-layer metric without one applies to every
    cell that reports the end-to-end metric it ``moves``."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return e2e, layer


def run_cell(cell: harness.Cell, bench: dict, seed: int, seconds: float, trace: bool, devices, solver=None) -> dict:
    """Set up, measure, judge; returns the result object. ``solver`` replaces the
    program's timed function (the control and the fault tests use it)."""
    import jax

    runner = harness.load_module("runners", cell.traffic["runner"])
    events = harness.CompileEvents()
    state = runner.State(cell, seed, devices, solver=solver)
    setup_s = time.perf_counter() - T_START
    harness.log(f"{cell.name}: set-up {setup_s!r} s; window of {seconds} s")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    events.active = True
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans from TraceMe only: Python tracing would slow the host
        jax.profiler.start_trace(tdir, profiler_options=options)
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        win = state.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    events.active = False
    harness.log(
        f"{cell.name}: in the window: {events.counts[harness.COMPILE_EVENT]} backend compiles "
        f"({events.counts[harness.CACHE_MISS_EVENT]} persistent-cache misses), "
        f"{events.counts[harness.TRACE_EVENT]} traces, build {events.build_seconds!r} s"
    )
    peak = harness.peak_bytes(state.devices)

    e2e, per_layer = cell_metrics(bench, cell.name)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    out = {}
    if trace:
        from bench import trace as tr

        summary = tr.Summary.from_dir(tdir, len(state.devices))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = tr.Context(cell=cell, window=win, events=events, summary=summary, work=state.work(), device_kind=devices[0].device_kind)
        metrics = {}
        for m in per_layer:
            value = harness.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in e2e:
            if m["name"] != "setup_s":
                metrics[m["name"]] = {"value": win.metrics[m["name"]], "unit": m["unit"]}

    t_check = time.perf_counter()
    correct, failed, checks = state.check(win)
    harness.log(f"{cell.name}: reference and check {time.perf_counter() - t_check!r} s")
    for name, c in checks.items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": correct, "attempted": win.count, "failed": failed, "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.benchmark()
    cell = harness.load_cell(args.workload, bench)
    try:
        devices = harness.require_tpu(cell.chips)
    except harness.NoAccelerator as e:
        harness.log(e.msg)
        return 3
    harness.log(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}; compile cache {harness.use_compile_cache()}")
    result = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
