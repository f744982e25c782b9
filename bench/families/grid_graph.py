"""Road-network-like structure: a ``side`` x ``side`` grid graph, each of
its undirected grid edges kept with probability ``keep`` (both arcs or
neither), plus every vertex's self-loop.

Road networks are planar, of low and even degree, and local: a grid with
edges thinned to the source's mean degree keeps all three. Vertex ``i``
sits at ``(i % side, i // side)``. The full grid is the pattern of the
program's 5-point ``laplacian_2d`` (``src/repro/core/sparse.py``), rebuilt
here so that no change to the program can move the benchmark's inputs.
"""

from __future__ import annotations

import numpy as np


def structure(cfg: dict, rng: np.random.Generator):
    """``(indptr, indices, (n, n))`` of the pattern, int64, rows sorted
    within each column."""
    side, keep = int(cfg["side"]), float(cfg["keep"])
    n = side * side
    i = np.arange(n, dtype=np.int64)
    x, y = i % side, i // side
    src, dst = [], []
    for ok, step in ((x + 1 < side, 1), (y + 1 < side, side)):
        u = i[ok]
        u = u[rng.random(len(u)) < keep]
        src.append(u)
        dst.append(u + step)
    u, v = np.concatenate(src), np.concatenate(dst)
    rows = np.concatenate([i, u, v])
    cols = np.concatenate([i, v, u])
    keys = np.unique(cols * n + rows)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, keys // n + 1, 1)
    return np.cumsum(indptr), keys % n, (n, n)
