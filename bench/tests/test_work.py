"""The work counts and the peaks table behind ``gram_roofline``."""
import json
import os

import pytest

from bench import harness, work


def test_fig3a_count_is_the_algorithms_own_work():
    flops, nbytes = work.gram_work(8, 10_000, 2**19, 1001)
    assert flops == 8 * (2 * 10_000 * 2**19 * 1001 + 2 * 10_000 * 1001**2)
    assert nbytes == 4 * (2**19 * 1001 + 8 * 1001**2)


def config(name):
    with open(os.path.join(harness.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["fig3a_studentt", "fig2_emnist"])
def test_counts_hold_no_padding(name):
    cfg = config(name)
    k = harness.width(cfg)
    assert k == cfg["d"] + cfg["targets"]  # 1001 and 831, not the kernels' 1024 and 896
    nnz = cfg["s"] if cfg["family"] == "sjlt" else cfg["m"]
    flops, nbytes = work.config_work(cfg, 8)
    assert flops == 8 * 2 * k * (nnz * cfg["n"] + cfg["m"] * k)
    assert nbytes == 4 * k * (cfg["n"] + 8 * k)


def test_sjlt_count_holds_no_m_n_term():
    """An SJLT column has s nonzeros: m enters only through the Gram, 2·m·k² a worker."""
    cfg = config("fig2_emnist")
    k, n = harness.width(cfg), cfg["n"]
    (f1, b1), (f2, b2) = (work.config_work(dict(cfg, m=m), 8) for m in (2000, 4000))
    assert f2 - f1 == 8 * 2 * 2000 * k * k and b1 == b2
    assert f1 == 8 * (2 * 20 * n * k + 2 * 2000 * k * k)
    # the least time of fig2's pass is the read of [A | b], not the flops
    assert work.least_seconds(f1, b1, "TPU v5 lite") == pytest.approx(4 * (n * k + 8 * k * k) / 819e9)


def test_dense_count_is_the_gaussian_count():
    cfg = config("fig3a_studentt")
    assert work.config_work(cfg, 8) == work.gram_work(8, 10_000, 2**19, 1001)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_v5e_peaks_and_compute_bound():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    flops, nbytes = work.gram_work(8, 10_000, 2**19, 1001)
    assert flops / p["flops_per_s"] > nbytes / p["bytes_per_s"]  # compute-bound
    assert work.least_seconds(flops, nbytes, "TPU v5 lite") == pytest.approx(8.41e13 / 197e12, rel=1e-3)
