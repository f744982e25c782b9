"""Plain host-side sparse helpers shared by the references: nothing here
comes from the program under test."""

from __future__ import annotations

from typing import NamedTuple, Tuple

import ml_dtypes
import numpy as np


class Mat(NamedTuple):
    """A CSC matrix: the program's answers carry the same four fields."""
    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    shape: Tuple[int, int]


def keys(m) -> np.ndarray:
    """Column-major position keys ``col * nrows + row`` of ``m``'s entries,
    in storage order (strictly increasing for a canonical CSC)."""
    indptr = np.asarray(m.indptr, dtype=np.int64)
    cols = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                     np.diff(indptr))
    return cols * int(m.shape[0]) + np.asarray(m.indices, dtype=np.int64)


def to_bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest, ties to even), as float64."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def locate(sorted_keys: np.ndarray, k: np.ndarray):
    """Positions of ``k`` in ``sorted_keys`` and whether each is there."""
    pos = np.searchsorted(sorted_keys, k)
    inside = pos < len(sorted_keys)
    found = np.zeros(len(k), dtype=bool)
    found[inside] = sorted_keys[pos[inside]] == k[inside]
    return pos, found


def malformed(answer, shape) -> int:
    """Entries of ``answer`` that cannot be read as a canonical CSC of
    ``shape``: repeated or out-of-order positions, or all of them when the
    arrays disagree with each other or with the shape."""
    n = len(answer.indices)
    if (tuple(answer.shape) != tuple(shape) or len(answer.data) != n
            or len(answer.indptr) != shape[1] + 1
            or int(answer.indptr[-1]) != n):
        return max(n, 1)
    return int(np.count_nonzero(np.diff(keys(answer)) <= 0))
