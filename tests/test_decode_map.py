"""The decode through a structural map is the scan decode, bitwise.

A 1D ring plan's :func:`ring_decode_map` holds C's structural pattern in
CSC order and each entry's position in the fetched output stack;
``decode_tiles(..., decode_map=...)`` gathers there instead of scanning
the stack and sorting. Both decode the same stack here: one built on the
host the way the kernel fills it (each scheduled output tile holds the
semiring product, absent positions the identity) with garbage in the
tiles past each part's count, which the kernel never writes.
"""

import numpy as np
import pytest

from repro.core import (BOOL_OR_AND, MIN_PLUS, PLUS_TIMES, erdos_renyi,
                        local_spgemm)
from repro.core.device_common import decode_tiles
from repro.core.plan import Partition1D
from repro.core.sparse import CSC
from repro.core.spgemm_1d_device import build_device_plan, ring_decode_map

BS = 8
N = 70
# part splits of C's columns that are not multiples of BS
SPLITS = {1: [0, N], 4: [0, 13, 30, 51, N]}
SEMIRINGS = [PLUS_TIMES, MIN_PLUS, BOOL_OR_AND]


def _operand(seed=9, signs=False):
    a = erdos_renyi(N, N, 4.0, seed=seed)
    if signs:
        # ±1 entries: many structural entries of A·A cancel to exactly 0
        a.data[:] = np.where(np.arange(a.nnz) % 2, 1.0, -1.0)
    else:
        a.data[:] = 1 + np.arange(a.nnz) % 5
    return a.astype(np.float32)


def _plan(a, nparts, semiring):
    return build_device_plan(
        a, a, nparts, bs=BS, semiring=semiring,
        part_n=Partition1D(np.array(SPLITS[nparts], dtype=np.int64)))


def _dense_product(a, semiring):
    """C = A ⊗ A densely in float64, the identity where no product lands."""
    d = a.to_dense().astype(np.float64)
    present = np.zeros(a.shape, dtype=bool)
    cols = np.repeat(np.arange(a.ncols), a.col_nnz)
    present[a.indices, cols] = True
    if semiring is MIN_PLUS:
        d[~present] = np.inf
        return np.min(d[:, :, None] + d[None, :, :], axis=1)
    if semiring is BOOL_OR_AND:
        p = present.astype(np.float64)
        return ((p @ p) > 0).astype(np.float64)
    return d @ d


def _stack(plan, a, semiring, garbage=7.5):
    """The ``(P, nc_max, bs, bs)`` output stack the ring would fetch for
    ``plan``: each part's scheduled tiles hold its columns of C (identity
    past the matrix and past the part), the tiles past its count
    ``garbage``."""
    c = _dense_product(a, semiring)
    m, n = plan.out_shape
    ntr = -(-m // BS)
    pad = np.full((ntr * BS, n + BS), semiring.zero)
    pad[:m, :n] = c
    splits = plan.part_n.splits
    out = np.full((plan.nparts, plan.nc_max, BS, BS), garbage,
                  dtype=np.float32)
    for d in range(plan.nparts):
        for t in range(int(plan.c_counts[d])):
            r0 = int(plan.c_rows[d, t]) * BS
            c0 = int(splits[d]) + int(plan.c_cols[d, t]) * BS
            tile = pad[r0:r0 + BS, c0:c0 + BS].copy()
            tile[:, max(int(splits[d + 1]) - c0, 0):] = semiring.zero
            out[d, t] = tile
    return out


def _decode(plan, out, c_counts=None, decode_map=None):
    splits = plan.part_n.splits.astype(np.int64)
    return decode_tiles(out, plan.c_rows, plan.c_cols,
                        plan.c_counts if c_counts is None else c_counts,
                        plan.semiring, plan.out_shape,
                        col_off=splits[:-1], col_lim=splits[1:],
                        decode_map=decode_map)


def _bitwise(x, y):
    assert x.shape == y.shape
    for f in ("indptr", "indices", "data"):
        u, v = getattr(x, f), getattr(y, f)
        assert u.dtype == v.dtype, f
        assert np.array_equal(u, v), f


@pytest.mark.parametrize("nparts", [1, 4])
@pytest.mark.parametrize("semiring", SEMIRINGS, ids=lambda s: s.name)
def test_map_decode_is_the_scan_bitwise(semiring, nparts):
    a = _operand()
    plan = _plan(a, nparts, semiring)
    out = _stack(plan, a, semiring)
    dmap = ring_decode_map(plan, a, a)
    scan = _decode(plan, out)
    mapped = _decode(plan, out, decode_map=dmap)
    _bitwise(mapped, scan)
    # nothing cancels here: the answer shares the map's read-only arrays
    assert mapped.indices is dmap.indices and mapped.indptr is dmap.indptr
    assert not mapped.indices.flags.writeable
    assert not mapped.indptr.flags.writeable
    assert mapped.data.flags.writeable
    # and it is the product, in the semiring's own oracle
    ref = local_spgemm.spgemm(a, a, semiring)
    assert np.array_equal(mapped.indptr, ref.indptr)
    assert np.array_equal(mapped.indices, ref.indices)


@pytest.mark.parametrize("nparts", [1, 4])
def test_cancelled_entries_are_compacted(nparts):
    """Plus-times over ±1 entries: structural entries that sum to exactly
    0 are dropped, and the column pointers follow the kept entries."""
    a = _operand(signs=True)
    plan = _plan(a, nparts, PLUS_TIMES)
    out = _stack(plan, a, PLUS_TIMES)
    dmap = ring_decode_map(plan, a, a)
    scan = _decode(plan, out)
    mapped = _decode(plan, out, decode_map=dmap)
    assert 0 < scan.nnz < len(dmap.indices)
    _bitwise(mapped, scan)
    assert np.all(mapped.data != 0)
    ref = local_spgemm.spgemm(a, a, PLUS_TIMES)
    assert np.array_equal(mapped.indptr, ref.indptr)
    assert np.array_equal(mapped.indices, ref.indices)


@pytest.mark.parametrize("garbage", [7.5, 0.0, np.inf])
def test_tiles_past_the_counts_are_never_read(garbage):
    """The kernel leaves the tiles past each part's count unwritten; the
    map gathers none of them, whatever they hold."""
    a = _operand()
    plan = _plan(a, 4, MIN_PLUS)
    assert np.any(plan.c_counts < plan.nc_max)
    out = _stack(plan, a, MIN_PLUS, garbage=garbage)
    dmap = ring_decode_map(plan, a, a)
    _bitwise(_decode(plan, out, decode_map=dmap), _decode(plan, out))


def test_other_tile_arguments_take_the_scan():
    """A decode called with other ``c_counts`` than the map was built
    for scans, and honours them: half the tiles never reach the answer."""
    a = _operand()
    plan = _plan(a, 4, PLUS_TIMES)
    out = _stack(plan, a, PLUS_TIMES)
    dmap = ring_decode_map(plan, a, a)
    half = plan.c_counts // 2
    got = _decode(plan, out, c_counts=half, decode_map=dmap)
    _bitwise(got, _decode(plan, out, c_counts=half))
    assert got.nnz < _decode(plan, out, decode_map=dmap).nnz
    assert got.indices is not dmap.indices


def test_map_arrays_are_what_the_decode_takes_as_they_are():
    """Positions are numpy's own index type, which ``take`` uses without
    a conversion; the index arrays are ``CSC``'s int64."""
    a = _operand()
    plan = _plan(a, 4, MIN_PLUS)
    dmap = ring_decode_map(plan, a, a)
    assert dmap.pos.dtype == np.intp
    assert dmap.indptr.dtype == dmap.indices.dtype == np.int64
    assert dmap.stack_shape == (4, plan.nc_max, BS, BS)


def _diagonal(k):
    """The first ``k`` diagonal entries of an N×N matrix."""
    indptr = np.minimum(np.arange(N + 1), k).astype(np.int64)
    return CSC(indptr, np.arange(k, dtype=np.int64),
               np.ones(k, np.float32), (N, N))


def test_a_structural_tile_outside_the_plan_raises():
    """The map is built from the call's operands; one whose structure
    reaches a tile the plan has no slot for is refused."""
    small = _diagonal(BS)       # A·A is the one tile (0, 0)
    plan = _plan(small, 1, PLUS_TIMES)
    assert plan.c_counts.tolist() == [1]
    ring_decode_map(plan, small, small)
    full = _diagonal(N)
    with pytest.raises(ValueError, match="none of the plan's output tiles"):
        ring_decode_map(plan, full, full)
