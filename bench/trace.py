"""Reduce a profiler trace of the window to the numbers the per-layer metrics read.

The trace is the ``*.trace.json.gz`` that ``jax.profiler`` writes beside its
``.xplane.pb`` on a TPU: Chrome trace events, read with ``gzip`` and ``json``. Each
chip is a process ``/device:TPU:<i>``; its thread ``XLA Ops`` holds one event per
device operation, named after the HLO instruction (a Pallas kernel's instruction
is named after the kernel), with the op's scope path in ``args.tf_op`` (it carries
``jax.named_scope`` names). The process ``/host:CPU`` carries the harness's spans:
the window (``bench.window``) and each answer inside it (``bench.step``), and JAX's
own host spans.

* busy: the union of the op intervals on a chip, clipped to the window;
  ``busy_s`` is its mean over the chips, ``window_s`` the window's length.
* kernel time: the summed device time of the ops named after a kernel, per chip.
* scope time: the same for a ``jax.named_scope`` name.
* breakdown: the ops that took most device time, and the longest idle gaps, each
  with the innermost host span open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Optional

from bench import harness

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_THREAD = "XLA Ops"
HOST = "/host:CPU"
TOP = 10  # entries in each list of the breakdown


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: int  # ns
    end: int  # ns
    scope: str  # the op's scope path (``tf_op``), empty when absent


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Summary:
    """Per-chip device ops and host spans of one traced window."""

    def __init__(self, ops: dict, host: list, window: tuple):
        self.ops = ops  # chip id -> [Op] inside the window
        self.host = host  # [(name, start, end)] host spans, any thread
        self.t0, self.t1 = window

    @classmethod
    def from_dir(cls, path: str, chips: int) -> "Summary":
        files = glob.glob(os.path.join(path, "**", "*.trace.json.gz"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file under {path}, found {len(files)}")
        return cls.from_file(files[0], chips)

    @classmethod
    def from_file(cls, path: str, chips: int) -> "Summary":
        with gzip.open(path, "rt") as f:
            events = json.load(f)["traceEvents"]
        procs, threads = {}, {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                procs[e["pid"]] = e["args"]["name"]
            elif e.get("ph") == "M" and e.get("name") == "thread_name":
                threads[(e["pid"], e["tid"])] = e["args"]["name"]
        ops, host, window = defaultdict(list), [], None
        for e in events:
            if e.get("ph") != "X":
                continue
            proc = procs.get(e["pid"], "")
            start = round(e["ts"] * 1000)
            end = start + round(e.get("dur", 0) * 1000)
            m = DEVICE.match(proc)
            if m and int(m.group(1)) < chips and threads.get((e["pid"], e.get("tid"))) == OPS_THREAD:
                ops[int(m.group(1))].append(Op(e["name"], start, end, e.get("args", {}).get("tf_op", "")))
            elif proc == HOST:
                host.append((e["name"], start, end))
                if e["name"] == harness.WINDOW_SPAN:
                    window = (start, end)
        if window is None:
            raise RuntimeError(f"no {harness.WINDOW_SPAN!r} span in the trace")
        if len(ops) != chips:
            raise RuntimeError(f"device ops found on {len(ops)} chips, expected {chips}")
        t0, t1 = window
        inside = {c: [o for o in v if o.end > t0 and o.start < t1] for c, v in ops.items()}
        return cls(inside, host, window)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, chip: int) -> list:
        return _union((max(o.start, self.t0), min(o.end, self.t1)) for o in self.ops[chip])

    @property
    def busy_s(self) -> float:
        per_chip = [sum(e - s for s, e in self.busy_intervals(c)) for c in self.ops]
        return sum(per_chip) / len(per_chip) / 1e9

    def _matching_s(self, chip: int, pred) -> float:
        return sum(o.end - o.start for o in self.ops[chip] if pred(o)) / 1e9

    def kernel_s(self, chip: int, names) -> float:
        """Device seconds of the ops named after any of the kernels ``names``."""
        return self._matching_s(chip, lambda o: re.sub(r"\.\d+$", "", o.name) in names)

    def scope_s(self, chip: int, scope: str) -> float:
        """Device seconds of the ops under ``jax.named_scope(scope)``."""
        return self._matching_s(chip, lambda o: scope in o.scope.split("/"))

    def breakdown(self) -> dict:
        """Top device ops by time (summed over chips) and the longest idle gaps."""
        by_op = defaultdict(int)
        for ops in self.ops.values():
            for o in ops:
                by_op[_op_key(o)] += o.end - o.start
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = []
        for c in self.ops:
            busy = self.busy_intervals(c)
            edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, s, e))
        gaps.sort(reverse=True)
        idle = [[self._host_doing(s, e), g / 1e9] for g, s, e in gaps[:TOP]]
        return {"device_ops": [[k, v / 1e9] for k, v in top], "idle_gaps": idle}

    def _host_doing(self, s: int, e: int) -> str:
        """The innermost (shortest) host span open at the middle of the gap [s, e]."""
        mid = (s + e) // 2
        covering = [(he - hs, name) for name, hs, he in self.host if hs <= mid <= he]
        return min(covering)[1] if covering else "no host span"


def _op_key(o: Op) -> str:
    """An op's name for the breakdown: the HLO instruction's name without its number,
    under the harness's scope (``bench.*``) where it has one."""
    stem = re.sub(r"\.\d+$", "", o.name)
    scopes = [p for p in o.scope.split("/") if p.startswith("bench.")]
    return f"{scopes[-1]}/{stem}" if scopes else stem


@dataclasses.dataclass
class Context:
    """What a metric reader is given."""

    cell: "harness.Cell"
    window: "harness.Window"
    events: "harness.CompileEvents"
    summary: Optional[Summary]
    work: tuple  # (flops, bytes) per answer on each chip
    device_kind: str
