#!/usr/bin/env python3
"""Read the numbers that the check compares with the control in the program's place,
on the chip at a cell's own size, one seed after another in one process.

    python3 bench/control.py --workload fig2.master --seconds 5 --seeds 901,902,903

The control is each runner's ``control``: the plain reference at one bf16 pass.
Each seed is a whole run of the cell (``run.run_cell``) with a window of
``--seconds``. Prints one JSON line per seed with every number beside its limit,
and a last line with the smallest reading of each number: the upper reading a
limit is set below. A control that crashes has failed and sets no reading.
"""
import argparse
import importlib.util
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import harness  # noqa: E402

sys.path.insert(1, harness.SRC)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(harness.BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    bench = harness.benchmark()
    cell = harness.load_cell(args.workload, bench)
    devices = harness.require_tpu(cell.chips)
    harness.use_compile_cache()
    control = harness.load_module("runners", cell.traffic["runner"]).control
    readings = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(cell, bench, seed, args.seconds, False, devices, solver=control)
        except Exception as e:  # a control that crashes has failed, and sets no reading
            print(json.dumps({"seed": seed, "error": repr(e)[:500]}), flush=True)
            continue
        print(json.dumps({"seed": seed, "correct": res["correct"], "attempted": res["attempted"], "checks": res["checks"]}), flush=True)
        for name, c in res["checks"].items():
            readings.setdefault(name, []).append(c["value"])
    print(json.dumps({"workload": cell.name, "smallest_control": {k: min(v) for k, v in readings.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
