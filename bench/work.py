"""The work a multiply needs, counted from its operands alone, and the
chip peaks it is held against (``peaks.json``, keyed by ``device_kind``).

Counting from the operands, and not from the tiles a program schedules,
keeps a roofline share that reads the same whatever implements the
multiply: a program that does less padded work reads higher.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
INDEX_BYTES = 4   # int32 row indices and column pointers


def spgemm_ops(a, b) -> int:
    """2 · Σ_k nnz(A(:,k)) · nnz(B(k,:)): one multiply and one add (or one
    add and one min) per pair of entries that meet."""
    row_nnz_b = np.bincount(np.asarray(b.indices), minlength=a.shape[1])
    return 2 * int(np.dot(np.diff(np.asarray(a.indptr)), row_nnz_b))


def csc_bytes(nnz: int, ncols: int, value_bytes: int) -> int:
    """Bytes of a CSC matrix: values and row indices, and column pointers."""
    return nnz * (value_bytes + INDEX_BYTES) + (ncols + 1) * INDEX_BYTES


def multiply_bytes(a, b, c_nnz: int, value_bytes: int = 4) -> int:
    """Bytes the multiply must move at least: read A and B, write C."""
    return (csc_bytes(len(a.indices), a.shape[1], value_bytes)
            + csc_bytes(len(b.indices), b.shape[1], value_bytes)
            + csc_bytes(c_nnz, b.shape[1], value_bytes))


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} in {path}; known: {sorted(table)}")
    return table[device_kind]


def least_time(ops: int, nbytes: int, peak: dict, chips: int):
    """(seconds, bound): the least time ``chips`` chips need for the work,
    the larger of its compute and its memory time, and which it is."""
    t_ops = ops / (chips * peak["flop_per_s"])
    t_bytes = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def symbolic_nnz(a, b) -> int:
    """Entries of the product's pattern: where some pair of entries meets."""
    import scipy.sparse as sp

    def pattern(m):
        return sp.csc_matrix((np.ones(len(m.indices)), np.asarray(m.indices),
                              np.asarray(m.indptr)), shape=m.shape)
    return int((pattern(a) @ pattern(b)).nnz)
