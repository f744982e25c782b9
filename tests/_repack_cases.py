"""One case of the values-only repack equivalence grid, shared by the
in-process (``nparts=1``) and the 8-fake-device (``nparts=4``) runs of
``tests/test_repack_scatter.py``.

A session plans ``A ⊗ B`` on the 1D ring, then serves the same structures
with new values on the named side(s). The device-scattered payload stacks
it swapped in must equal ``refill_ring_stacks``'s host stacks bit for
bit, and the served C must equal a cold re-plan on the new values.
"""

import numpy as np

from repro.core import SpGEMMSession, by_name, erdos_renyi
from repro.core.spgemm_1d_device import (build_device_plan,
                                         refill_ring_stacks,
                                         run_device_spgemm)

SEMIRINGS = ("plus_times", "min_plus", "bool_or_and")
SIDES = ("a", "b", "ab")
CHUNKS = (None, 1)
BS = 8


def _values(mat, semiring: str, seed: int):
    """``mat`` with new float32 values of the semiring's kind: explicit
    stored 0.0 everywhere, and stored +inf (the identity) under min-plus."""
    rng = np.random.default_rng(seed)
    out = mat.astype(np.float32)
    n = out.nnz
    if semiring == "bool_or_and":
        out.data[:] = rng.integers(0, 2, n)
        return out
    if semiring == "plus_times":
        out.data[:] = rng.integers(-4, 5, n)
    else:
        out.data[:] = rng.integers(1, 50, n)
        out.data[rng.random(n) < 0.1] = np.inf
    out.data[rng.random(n) < 0.1] = 0.0
    return out


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint32)


def run_case(semiring: str, nparts: int, chunk, side: str) -> dict:
    """Serve one values-only hit; report what matched and what the
    session's repack did."""
    sr = by_name(semiring)
    a = _values(erdos_renyi(40, 36, 3.0, seed=11), semiring, 1)
    b = _values(erdos_renyi(36, 44, 3.0, seed=12), semiring, 2)
    a2 = _values(a, semiring, 3) if "a" in side else a
    b2 = _values(b, semiring, 4) if "b" in side else b
    kw = dict(nparts=nparts, bs=BS, semiring=sr, chunk=chunk)

    s = SpGEMMSession()
    s.matmul(a, b, **kw)
    (entry,) = s._cache.values()
    old = [_bits(x) for x in entry.args[:2]]
    traces = s.stats["traces"]
    c = s.matmul(a2, b2, **kw)

    host = refill_ring_stacks(entry.plan, a2 if "a" in side else None,
                              b2 if "b" in side else None)
    stacks = all(
        np.array_equal(_bits(got), old[i] if want is None else _bits(want))
        for i, (got, want) in enumerate(zip(entry.args[:2], host)))
    ref = run_device_spgemm(build_device_plan(a2, b2, nparts, bs=BS,
                                              semiring=sr, chunk=chunk))
    same_c = (c.shape == ref.shape
              and all(np.array_equal(getattr(c, f), getattr(ref, f))
                      for f in ("indptr", "indices", "data")))
    return dict(scattered=entry.scatter is not None,
                repacked=bool(s.last_call["repacked"]),
                retraced=s.stats["traces"] != traces,
                stacks_equal=stacks, c_equal=same_c)


def all_cases(nparts: int) -> dict:
    """Every (semiring, chunk, side) case at ``nparts``, keyed by its id."""
    return {f"{sr}-{nparts}-{chunk}-{side}": run_case(sr, nparts, chunk, side)
            for sr in SEMIRINGS for chunk in CHUNKS for side in SIDES}
