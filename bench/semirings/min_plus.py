"""Plain reference of C = A ⊗ B over (min, +), and the comparison that
decides ``correct`` for min-plus cells.

C_ij = min_k (a_ik + b_kj) over the stored entries. Every product is one
addition of two float32 values, which float64 holds exactly, and rounding
is monotone, so the float32 answer of any correct order of evaluation is
the float64 minimum rounded once to float32: the comparison is exact.

* ``struct_mismatch``: positions in one pattern and not the other, or not
  readable as a canonical CSC. Limit 0.
* ``value_mismatch``: entries at common positions whose float32 value is
  not the reference's. Limit 0.
"""

from __future__ import annotations

import numpy as np

from sparse_ref import Mat, keys, locate, malformed, to_bf16

NUMBERS = ("struct_mismatch", "value_mismatch")


def _product(a, b, ad: np.ndarray, bd: np.ndarray):
    """Sorted position keys and float64 values of a ⊗ b (expand, sort,
    reduce by minimum)."""
    a_ptr = np.asarray(a.indptr, np.int64)
    b_ptr = np.asarray(b.indptr, np.int64)
    a_idx = np.asarray(a.indices, np.int64)
    ks = np.asarray(b.indices, np.int64)
    js = np.repeat(np.arange(len(b_ptr) - 1, dtype=np.int64), np.diff(b_ptr))
    lens = np.diff(a_ptr)[ks]
    total = int(lens.sum())
    ends = np.cumsum(lens)
    flat = np.repeat(a_ptr[ks], lens) + (
        np.arange(total, dtype=np.int64) - np.repeat(ends - lens, lens))
    key = np.repeat(js, lens) * int(a.shape[0]) + a_idx[flat]
    val = ad[flat] + np.repeat(bd, lens)
    order = np.argsort(key, kind="stable")
    key, val = key[order], val[order]
    if not total:
        return key, val
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    return key[first], np.minimum.reduceat(val, first)


def compare(a, b, answer) -> dict:
    """The numbers compared for ``answer`` against the reference of a⊗b."""
    kref, vref = _product(a, b, np.asarray(a.data, np.float64),
                          np.asarray(b.data, np.float64))
    shape = (a.shape[0], b.shape[1])
    bad = malformed(answer, shape)
    if bad and len(answer.data) != len(answer.indices):
        return {"struct_mismatch": bad, "value_mismatch": bad}
    ka = keys(answer)
    pos, found = locate(kref, ka)
    missing = len(kref) - np.count_nonzero(found)
    got = np.asarray(answer.data)[found]
    want = vref[pos[found]].astype(np.float32)
    return {"struct_mismatch":
            int(np.count_nonzero(~found) + missing + bad),
            "value_mismatch": int(np.count_nonzero(got != want))}


def control(a, b) -> Mat:
    """The reference one precision down, in the program's place: operands
    rounded to bfloat16, result rounded to float32."""
    key, val = _product(a, b, to_bf16(a.data), to_bf16(b.data))
    m, n = a.shape[0], b.shape[1]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, key // m + 1, 1)
    return Mat(np.cumsum(indptr), key % m, val.astype(np.float32), (m, n))
