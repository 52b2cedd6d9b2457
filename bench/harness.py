"""What every cell shares: finding its files by name, the device check, seeds, the
compile cache, JAX's compile events, the timed loop and the correctness check.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<config>.json``)
and a traffic mix (``bench/traffic/<traffic>.json``). The mix names the runner that
runs it (``bench/runners/<runner>.py``); its limits are in
``bench/limits/<workload>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding a cell adds files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import statistics
import sys
import time
from typing import Callable, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

# JAX's own compile events (jax._src.dispatch): a trace, a lowering, and a backend
# compile, the last also when the executable comes from the persistent cache.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BUILD_EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# Host spans the harness writes into the profiler's trace: the window, and each
# answer (solve or job) inside it.
WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step"


class NoAccelerator(SystemExit):
    """Raised (exit code 3) when JAX finds no TPU or fewer chips than the cell asks for."""

    def __init__(self, msg: str):
        super().__init__(3)
        self.msg = msg


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict


@dataclasses.dataclass
class Window:
    """What a runner's timed loop did: answers to check and end-to-end numbers."""

    count: int
    elapsed_s: float
    answers: list
    metrics: dict
    latencies_s: list = dataclasses.field(default_factory=list)  # per answer, host clock


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_json(os.path.join(BENCH, "configs", w["config"] + ".json")),
        traffic=_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(BENCH, "limits", name + ".json")),
    )


def require_tpu(chips: int):
    """The first ``chips`` devices, all TPUs; otherwise :class:`NoAccelerator`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compile cache, at the fixed path ``<checkout>/.jax_cache``
    (the path is part of the cache's key), for every program the run builds."""
    import jax

    path = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_keys(seed: int):
    """(data key, run key) from all bits of ``seed``: ``PRNGKey`` keeps only the low 32."""
    import jax

    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)


def sketch_spec(config: dict):
    from repro.core import sketches

    return sketches.SketchSpec(config["family"], config["m"], s=config["s"], use_kernel=config["use_kernel"])


def width(config: dict) -> int:
    """k = d + targets, the width of [A | b]."""
    return config["d"] + config["targets"]


class CompileEvents:
    """JAX's compile events while :meth:`on` is set: counts and seconds by event."""

    def __init__(self):
        import jax

        self.active = False
        self.seconds = {e: 0.0 for e in BUILD_EVENTS}
        self.counts = {e: 0 for e in BUILD_EVENTS + (CACHE_MISS_EVENT,)}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if self.active and event in self.seconds:
            self.seconds[event] += duration
            self.counts[event] += 1

    def _event(self, event, **_):
        if self.active and event in self.counts:
            self.counts[event] += 1

    @property
    def build_seconds(self) -> float:
        return sum(self.seconds.values())


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def timed_loop(step: Callable[[int], object], seconds: float) -> Window:
    """Run ``step(i)`` back to back, each to completion, until ``seconds`` have passed.

    The window ends with the step that crosses ``seconds``, so it holds whole steps
    only; the rate is all steps over all the time. Each step's latency is kept.
    """
    import jax

    answers, latencies = [], []
    t0 = time.perf_counter()
    now = t0
    while now - t0 < seconds:
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            answers.append(step(len(answers)))
        start, now = now, time.perf_counter()
        latencies.append(now - start)
    return Window(count=len(answers), elapsed_s=now - t0, answers=answers, metrics={}, latencies_s=latencies)


def quantile(values, p: float) -> float:
    """The p-quantile (0 < p < 1) of ``values`` by ``statistics.quantiles`` (exclusive)."""
    vals = sorted(values)
    if len(vals) == 1:
        return vals[0]
    return statistics.quantiles(vals, n=100, method="exclusive")[round(p * 100) - 1]


def sample(seed: int, count: int, k: int) -> list:
    """k of the window's ``count`` answers, drawn from the seed."""
    return sorted(np.random.default_rng(seed).choice(count, size=min(k, count), replace=False).tolist())


def rel_gap(x, ref) -> float:
    """‖x − ref‖ / ‖ref‖ (Frobenius), in float64."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def judge(cell: Cell, numbers: dict, extra_failed: int = 0) -> tuple[bool, int, dict]:
    """(correct, failed, checks) from per-answer numbers, ``{name: {answer: value}}``.

    Each number's limit is in ``bench/limits/<workload>.json``; what is compared is
    its widest value over the answers judged, and a non-finite value reads as
    infinity. ``failed`` counts the answers over any limit, plus ``extra_failed``.
    """
    checks, over = {}, set()
    for name, values in numbers.items():
        limit = float(cell.limits[name]["limit"])
        vals = {i: v if math.isfinite(v) else math.inf for i, v in values.items()}
        over |= {i for i, v in vals.items() if v > limit}
        checks[name] = {"value": max(vals.values(), default=math.inf), "limit": limit}
    failed = len(over) + extra_failed
    return failed == 0 and all(numbers.values()), failed, checks


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)
