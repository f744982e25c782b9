"""Work counts and the peaks table of the benchmark (CPU)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import work  # noqa: E402
from sparse_ref import Mat  # noqa: E402


def _random_csc(rng, m, n, density):
    dense = (rng.random((m, n)) < density) * rng.standard_normal((m, n))
    rows, cols = np.nonzero(dense.T)          # column-major order
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    return Mat(np.cumsum(indptr), cols, dense.T[rows, cols], (m, n)), dense


def test_ops_equal_brute_force_count():
    rng = np.random.default_rng(3)
    a, da = _random_csc(rng, 9, 7, 0.4)
    b, db = _random_csc(rng, 7, 11, 0.3)
    pairs = sum(1 for i in range(9) for k in range(7) for j in range(11)
                if da[i, k] != 0 and db[k, j] != 0)
    assert pairs > 0
    assert work.spgemm_ops(a, b) == 2 * pairs
    sym = sum(1 for i in range(9) for j in range(11)
              if any(da[i, k] != 0 and db[k, j] != 0 for k in range(7)))
    assert work.symbolic_nnz(a, b) == sym


def test_bytes_count_values_indices_and_pointers():
    a = Mat(np.array([0, 1, 3]), np.array([0, 0, 1]), np.ones(3), (2, 2))
    assert work.csc_bytes(3, 2, 4) == 3 * 8 + 3 * 4
    assert work.multiply_bytes(a, a, 4) == 2 * (3 * 8 + 12) + (4 * 8 + 12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")


def test_shipped_peaks_and_least_time_bound():
    p = work.peaks("TPU v5 lite")
    assert p["flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in json.loads(work.PEAKS.read_text())["source"]
    t, bound = work.least_time(2 * 10**9, 10**6, p, chips=1)
    assert bound == "compute" and t == pytest.approx(2e9 / 197e12)
    t, bound = work.least_time(10**6, 819 * 10**6, p, chips=4)
    assert bound == "memory" and t == pytest.approx(0.25e-3)
