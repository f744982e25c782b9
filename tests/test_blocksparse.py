"""Block-sparse tile format + product schedule + Pallas bsr kernel sweep."""

import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (BOOL_OR_AND, MIN_PLUS, PLUS_TIMES, erdos_renyi,
                        banded_clustered, from_coo, from_dense, spgemm)
from repro.core.blocksparse import build_schedule, flags_from_c_slot, from_csc
from repro.kernels.bsr_spgemm import (bsr_spgemm_pallas, bsr_spgemm_ref,
                                      local_spgemm_device, schedule_flags)


@given(st.integers(4, 40), st.integers(4, 40), st.integers(0, 2**31),
       st.sampled_from([4, 8, 16]))
@settings(max_examples=25, deadline=None)
def test_blockize_roundtrip(m, n, seed, bs):
    rng = np.random.default_rng(seed)
    dense = (rng.random((m, n)) < 0.2) * rng.standard_normal((m, n))
    bsm = from_csc(from_dense(dense), bs=bs)
    np.testing.assert_allclose(bsm.to_dense(), dense.astype(np.float32),
                               atol=1e-6)


@given(st.integers(2, 30), st.integers(2, 30), st.integers(0, 2**31),
       st.sampled_from([4, 8, 16]))
@settings(max_examples=25, deadline=None)
def test_csc_roundtrip_preserves_explicit_entries(m, n, seed, bs):
    """from_csc → to_csc is lossless for entries the semiring considers
    nonzero — including explicit stored 0.0 values, which an
    identity-filled min-plus container must NOT conflate with "absent"
    (they are zero-cost edges)."""
    rng = np.random.default_rng(seed)
    nnz = int(rng.integers(1, m * n + 1))
    flat = rng.choice(m * n, size=nnz, replace=False)
    vals = rng.integers(-3, 4, size=nnz).astype(np.float64)  # incl. 0.0
    mat = from_coo(flat % m, flat // m, vals, (m, n))
    bsm = from_csc(mat, bs=bs, fill=MIN_PLUS.zero)
    back = bsm.to_csc(semiring=MIN_PLUS)
    np.testing.assert_array_equal(back.indptr, mat.indptr)
    np.testing.assert_array_equal(back.indices, mat.indices)
    np.testing.assert_array_equal(back.data, mat.data.astype(np.float32))
    # default (fill-relative) prune gives the same answer with no semiring
    back2 = bsm.to_csc()
    np.testing.assert_array_equal(back2.data, mat.data.astype(np.float32))


def test_local_device_spgemm_all_semirings():
    """The scheduled kernel and the jnp ref agree bitwise with the host
    oracle under every registered semiring on int-valued operands."""
    rng = np.random.default_rng(21)
    da = np.rint(2 * ((rng.random((40, 33)) < 0.3)
                      * rng.standard_normal((40, 33))))
    db = np.rint(2 * ((rng.random((33, 27)) < 0.3)
                      * rng.standard_normal((33, 27))))
    a, b = from_dense(da), from_dense(db)
    for sr in (PLUS_TIMES, BOOL_OR_AND, MIN_PLUS):
        host = spgemm(a, b, sr)
        bsa = from_csc(a, bs=8, fill=sr.zero)
        bsb = from_csc(b, bs=8, fill=sr.zero)
        for use_kernel in (True, False):
            dev = local_spgemm_device(bsa, bsb, use_kernel=use_kernel,
                                      semiring=sr)
            got = dev.to_csc(semiring=sr)
            np.testing.assert_array_equal(got.indptr, host.indptr,
                                          err_msg=sr.name)
            np.testing.assert_array_equal(got.indices, host.indices)
            np.testing.assert_array_equal(got.data,
                                          host.data.astype(np.float32))


def test_empty_schedule_min_plus_decodes_empty():
    """nprod == 0 must return identity payloads: a min-plus empty output
    decodes to an empty matrix, not to a dense block of zeros."""
    z = from_csc(from_dense(np.zeros((24, 24))), bs=8, fill=MIN_PLUS.zero)
    c = local_spgemm_device(z, z, semiring=MIN_PLUS)
    assert c.ntiles == 0
    assert c.to_csc(semiring=MIN_PLUS).nnz == 0
    # the kernel-level early return is identity-filled too
    import jax.numpy as jnp
    out = bsr_spgemm_pallas(
        jnp.zeros((1, 8, 8)), jnp.zeros((1, 8, 8)),
        jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
        jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
        nprod=0, nc=2, bs=8, interpret=True, semiring=MIN_PLUS)
    assert np.isinf(np.asarray(out)).all()
    out_r = bsr_spgemm_ref(
        jnp.zeros((1, 8, 8)), jnp.zeros((1, 8, 8)),
        jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32),
        jnp.zeros(0, jnp.int32), nc=2, semiring=MIN_PLUS)
    assert np.isinf(np.asarray(out_r)).all()


def test_schedule_covers_all_products():
    a = erdos_renyi(100, 100, 4.0, seed=11)
    bsa = from_csc(a, bs=16)
    sched = build_schedule(bsa, bsa)
    # c_slot nondecreasing (revisit-free requirement for the kernel)
    assert (np.diff(sched.c_slot) >= 0).all()
    assert sched.flops == 2 * sched.nprod * 16 ** 3


def _naive_join(a, b):
    """Per-k loop reference for the vectorized schedule join."""
    nk = a.grid[1]
    order_a = np.argsort(a.tile_cols, kind="stable")
    order_b = np.argsort(b.tile_rows, kind="stable")
    ak, bk = a.tile_cols[order_a], b.tile_rows[order_b]
    ca = np.bincount(ak, minlength=nk)
    cb = np.bincount(bk, minlength=nk)
    sa = np.concatenate([[0], np.cumsum(ca)])
    sb = np.concatenate([[0], np.cumsum(cb)])
    a_sl, b_sl = [], []
    for k in range(nk):
        if ca[k] == 0 or cb[k] == 0:
            continue
        a_sl.append(np.repeat(order_a[sa[k]:sa[k + 1]], cb[k]))
        b_sl.append(np.tile(order_b[sb[k]:sb[k + 1]], ca[k]))
    if not a_sl:
        z = np.zeros(0, np.int64)
        return z, z
    return np.concatenate(a_sl), np.concatenate(b_sl)


@given(st.integers(8, 60), st.integers(0, 2**31), st.sampled_from([8, 16]))
@settings(max_examples=20, deadline=None)
def test_schedule_vectorization_matches_naive(n, seed, bs):
    """The repeat/segment-gather join reproduces the per-k loop exactly
    (same products, same order — the kernel depends on the order)."""
    a = erdos_renyi(n, n, 3.0, seed=seed % 1000)
    bsa = from_csc(a, bs=bs)
    a_ref, b_ref = _naive_join(bsa, bsa)
    sched = build_schedule(bsa, bsa)
    # the join is dedup-sorted by output key afterwards; compare pre-sort
    # order via the (a, b) pair multiset and the sort's stability:
    oi = bsa.tile_rows[a_ref].astype(np.int64)
    oj = bsa.tile_cols[b_ref].astype(np.int64)
    order = np.argsort(oj * bsa.grid[0] + oi, kind="stable")
    np.testing.assert_array_equal(sched.a_slot, a_ref[order])
    np.testing.assert_array_equal(sched.b_slot, b_ref[order])


@pytest.mark.parametrize("gen,bs", [
    (lambda: erdos_renyi(200, 200, 5.0, seed=3), 32),
    (lambda: banded_clustered(190, 15, 4.0, seed=4), 16),
    (lambda: erdos_renyi(64, 64, 2.0, seed=5), 8),
])
def test_kernel_matches_dense(gen, bs):
    a = gen()
    bsa = from_csc(a, bs=bs)
    c = local_spgemm_device(bsa, bsa, use_kernel=True)
    dense = a.to_dense().astype(np.float32)
    np.testing.assert_allclose(c.to_dense(), dense @ dense,
                               atol=1e-2, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_vs_ref_dtypes(dtype):
    a = erdos_renyi(96, 96, 3.0, seed=9)
    bsa = from_csc(a, bs=16, dtype=np.float32)
    sched = build_schedule(bsa, bsa)
    tiles = jnp.asarray(bsa.tiles).astype(dtype)
    out_k = bsr_spgemm_pallas(
        tiles, tiles, jnp.asarray(sched.a_slot), jnp.asarray(sched.b_slot),
        jnp.asarray(sched.c_slot), jnp.asarray(schedule_flags(sched)),
        nprod=sched.nprod, nc=sched.nc, bs=16, interpret=True)
    out_r = bsr_spgemm_ref(
        tiles, tiles, jnp.asarray(sched.a_slot), jnp.asarray(sched.b_slot),
        jnp.asarray(sched.c_slot), nc=sched.nc)
    tol = 1e-5 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("semiring", [PLUS_TIMES, BOOL_OR_AND, MIN_PLUS],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("window,seg_start", [(2, 0), (4, 3), (10 ** 6, 2)])
def test_kernel_windows_match_ref(semiring, window, seg_start):
    """Cutting the schedule into windows of ``window`` products (one
    ``pallas_call`` each, runs of one output tile split across cuts) gives
    the single-launch answer bitwise, also from a segment offset."""
    rng = np.random.default_rng(17)
    d = np.rint(3 * ((rng.random((24, 24)) < 0.5)
                     * rng.standard_normal((24, 24))))
    bsa = from_csc(from_dense(d), bs=8, fill=semiring.zero)
    sched = build_schedule(bsa, bsa)
    n = sched.nprod - seg_start
    # 3x3 tile grid: every output tile has a run of 3 products, so windows
    # of 2 and 4 cut through runs
    assert (sched.nprod, sched.nc) == (27, 9)
    tiles = jnp.asarray(bsa.tiles)
    sl = [jnp.asarray(x) for x in (sched.a_slot, sched.b_slot, sched.c_slot)]
    # a segment's flags are its own: its first product opens its run
    flags = np.concatenate([flags_from_c_slot(sched.c_slot[:seg_start]),
                            flags_from_c_slot(sched.c_slot[seg_start:])])
    out_k = bsr_spgemm_pallas(tiles, tiles, *sl, jnp.asarray(flags),
                              nprod=n, nc=sched.nc, bs=8, interpret=True,
                              semiring=semiring, seg_start=seg_start,
                              window=window)
    out_r = bsr_spgemm_ref(tiles, tiles, *sl, nc=sched.nc,
                           semiring=semiring, seg_start=seg_start, seg_len=n)
    visited = np.unique(sched.c_slot[seg_start:])
    np.testing.assert_array_equal(np.asarray(out_k)[visited],
                                  np.asarray(out_r)[visited])


def test_empty_schedule():
    z = from_csc(from_dense(np.zeros((32, 32))), bs=16)
    c = local_spgemm_device(z, z)
    assert c.ntiles == 0
    assert c.to_dense().shape == (32, 32)


def test_fill_fraction_diagnostic():
    a = banded_clustered(128, 6, 3.0, seed=6)
    bs_small = from_csc(a, bs=8)
    bs_big = from_csc(a, bs=64)
    # coarser tiles waste more payload on a thin band
    assert bs_small.fill_fraction() >= bs_big.fill_fraction()
