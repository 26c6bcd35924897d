"""Byte counts and peaks, worked by hand at small sizes."""

import pytest

from benchmark import costs


def test_spmv_bytes_by_hand():
    # n = 10, nnz = 28 (a 10-row tridiagonal), float32: 28 values read,
    # 10 of x read, 10 of y written = 48 words = 192 bytes.
    assert costs.spmv_bytes(10, 28, 4) == 192
    assert costs.resident_spmv_bytes(28, 4) == 112
    assert costs.spmv_flops(28) == 56


def test_cg_iteration_counts_stored_values_only():
    # Vectors stay on the chip inside the loop: 28 values x 4 bytes.
    assert costs.cg_iteration_bytes(28, 4) == 112
    assert costs.cg_iteration_bytes(28, 2) == 56


def test_least_seconds_and_share():
    p = costs.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    # 819 MB over one chip at 819 GB/s: 1 ms; over four chips: 0.25 ms.
    assert costs.least_seconds(819e6, 0, 1, "TPU v5 lite") == \
        pytest.approx(1e-3)
    assert costs.least_seconds(819e6, 0, 4, "TPU v5 lite") == \
        pytest.approx(2.5e-4)
    # Compute-bound work takes the flop bound: 197 GFLOP -> 1 ms.
    assert costs.least_seconds(0, 197e9, 1, "TPU v5 lite") == \
        pytest.approx(1e-3)
    assert costs.share(1e-3, 2e-3) == pytest.approx(50.0)


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        costs.peak("cpu")
    with pytest.raises(KeyError):
        costs.least_seconds(1.0, 1.0, 1, "TPU v9 imaginary")


def test_poisson_sizes_match_configs():
    import json
    import os

    from benchmark.reference import poisson
    from benchmark.tests.conftest import ROOT

    for name in ("poisson2d-2048", "poisson2d-4096"):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        assert poisson.coefficients(cfg)[4] == cfg["rows"]
        assert poisson.nnz(cfg) == cfg["nnz"]
    # 3 x 3 interior grid by hand: 9 + 2*6 (x) + 2*6 (y) = 33 values.
    small = {"nx": 5, "ny": 5, "xmin": 0, "xmax": 1, "ymin": 0, "ymax": 1}
    assert poisson.nnz(small) == 33
    assert poisson.scipy_operator(small).nnz == 33
