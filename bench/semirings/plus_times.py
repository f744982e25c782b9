"""Plain reference of C = A·B over (+, ×), and the comparison that decides
``correct`` for plus-times cells.

The reference is scipy's sparse product in float64 of the same float32
operands. Two numbers are compared:

* ``extra_entries``: entries of the answer outside the symbolic pattern of
  A·B, or not readable as a canonical CSC. Limit 0.
* ``value_gap``: over the union of both patterns, the widest
  ``|c - c_ref| / (|A|·|B|)_ij``. The denominator is the sum of the
  magnitudes of the products that make the entry, the scale of the
  rounding error of any summation order (Higham, "Accuracy and Stability
  of Numerical Algorithms", §3.1), so cancellation cannot inflate the gap.
  An entry the answer drops reads ``|c_ref| / (|A|·|B|)_ij``, far above
  rounding.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from sparse_ref import Mat, keys, locate, malformed, to_bf16

NUMBERS = ("extra_entries", "value_gap")


def _sp(m, data) -> sp.csc_matrix:
    return sp.csc_matrix((data, np.asarray(m.indices), np.asarray(m.indptr)),
                         shape=m.shape)


def _product(a, b, ad, bd) -> sp.csc_matrix:
    c = (_sp(a, ad) @ _sp(b, bd)).tocsc()
    c.sort_indices()
    return c


def compare(a, b, answer) -> dict:
    """The numbers compared for ``answer`` against the reference of a·b."""
    a64 = np.asarray(a.data, np.float64)
    b64 = np.asarray(b.data, np.float64)
    scale = _product(a, b, np.abs(a64), np.abs(b64))  # every symbolic entry
    ref = _product(a, b, a64, b64)   # exact zeros dropped; ⊆ scale's
    shape = (a.shape[0], b.shape[1])
    bad = malformed(answer, shape)
    if bad and len(answer.data) != len(answer.indices):
        return {"extra_entries": bad, "value_gap": float("inf")}
    ks = keys(Mat(scale.indptr, scale.indices, scale.data, shape))
    r = np.zeros(len(ks))
    r[np.searchsorted(ks, keys(Mat(ref.indptr, ref.indices, ref.data,
                                   shape)))] = ref.data
    pos, found = locate(ks, keys(answer))
    c = np.zeros(len(ks))
    c[pos[found]] = np.asarray(answer.data, np.float64)[found]
    gap = np.abs(c - r) / scale.data
    return {"extra_entries": int(np.count_nonzero(~found)) + bad,
            "value_gap": float(gap.max()) if len(gap) else 0.0}


def control(a, b) -> Mat:
    """The reference one precision down, in the program's place: operands
    rounded to bfloat16 (one MXU pass), products summed and the result
    rounded to float32."""
    c = _product(a, b, to_bf16(a.data), to_bf16(b.data))
    return Mat(c.indptr, c.indices, c.data.astype(np.float32), c.shape)
