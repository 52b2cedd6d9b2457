"""The algorithm's own work for one sketch→Gram pass, whatever implements it.

For q workers, each forming G_w = (S_w [A | b])ᵀ(S_w [A | b]) with an m-row sketch
whose columns hold ``nnz`` nonzeros each (m for a dense Gaussian S, s for SJLT):

* flops = q·(2·nnz·n·k + 2·m·k²): the sketch product and its Gram, k = d + targets;
* bytes = 4·(n·k + q·k²): [A | b] read once in f32, q Grams written.

No RNG operations, no padding, no zeros of a sparse S and no extra precision passes
are counted, so the share of a roofline computed from these can never pass 100%.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def gram_work(q: int, m: int, n: int, k: int, nnz: int | None = None) -> tuple[int, int]:
    """(flops, bytes) of q workers' fused sketch→Gram over an (n, k) [A | b]; ``nnz``
    is the nonzeros in each column of S, m (dense) when not given."""
    nnz = m if nnz is None else nnz
    return q * (2 * nnz * n * k + 2 * m * k * k), 4 * (n * k + q * k * k)


def config_work(config: dict, q: int, n: int | None = None) -> tuple[int, int]:
    """:func:`gram_work` for a configuration's sketch over its (or ``n``) rows."""
    nnz = config["s"] if config["family"] == "sjlt" else None
    return gram_work(q, config["m"], n or config["n"], config["d"] + config["targets"], nnz)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(flops: int, nbytes: int, device_kind: str) -> float:
    """The roofline's least time for the work on one chip: max(flops / peak FLOP/s,
    bytes / peak bytes/s)."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["bytes_per_s"])
