"""hv15r-like structure: nonzeros clustered about the diagonal.

A copy of the arithmetic of the program's ``banded_clustered`` generator
(``src/repro/core/sparse.py``), kept here so that no change to the program
can move the benchmark's inputs: ``degree * n`` entries, each in a uniform
random column, with its row offset from the diagonal drawn from a normal of
standard deviation ``band / 3`` and clipped to the matrix; repeated
positions keep one entry.
"""

from __future__ import annotations

import numpy as np


def structure(cfg: dict, rng: np.random.Generator):
    """``(indptr, indices, (n, n))`` of the pattern, int64, rows sorted
    within each column."""
    n, band, degree = int(cfg["n"]), int(cfg["band"]), float(cfg["degree"])
    nnz = int(degree * n)
    cols = rng.integers(0, n, size=nnz)
    offs = np.rint(rng.standard_normal(nnz) * (band / 3.0)).astype(np.int64)
    rows = np.clip(cols + offs, 0, n - 1)
    keys = np.unique(cols.astype(np.int64) * n + rows)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, keys // n + 1, 1)
    return np.cumsum(indptr), keys % n, (n, n)
