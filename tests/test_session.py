"""Persistent SpGEMM session — structure-keyed plan/executable caching.

Pins the cache semantics of ``core.session.SpGEMMSession``:

  * a structure-identical repeat multiply reports ``plan_seconds == 0``,
    increments ``plan_cache_hits``, performs **zero retraces** (observed
    through the engines' trace probe — the traced body fires a host
    callback at trace time only) and decodes bitwise-identical to a
    cold-plan run;
  * a values-only change takes the payload-repack path (plan + executable
    reused, still zero retraces) and matches a cold re-plan bitwise;
  * one extra nonzero tile, a semiring change, an engine change and a
    geometry change each force a cache miss;
  * the LRU bound evicts oldest-first and the stats surface is exactly
    ``device_common.SESSION_STATS``.

In-process tests run the full shard_map + scheduled-kernel path at
``nparts=1`` (smoke-test contract: the parent process sees one device);
the multi-device semantics run in an 8-fake-device subprocess.
"""

import textwrap

import numpy as np
import pytest
from _device_harness import run_subprocess
from _trace import host_spans, record

from repro.core import SpGEMMSession, erdos_renyi, from_coo
from repro.core.sparse import CSC


def _int_matrix(n=50, seed=3):
    """Integer-valued operand: partial sums exact in f32, so session
    results must agree bitwise with cold-plan runs."""
    a = erdos_renyi(n, n, 4.0, seed=seed)
    a.data[:] = np.rint(2 * a.data)
    a.data[a.data == 0] = 1.0
    return a


def _positive_matrix():
    """A float32 operand with positive integer values: no entry of its
    product cancels, so every structural entry is in the answer."""
    a = _int_matrix().astype(np.float32)
    a.data[:] = np.abs(a.data)
    return a


def _cold_run(a, b, bs, semiring=None, engine="auto"):
    from repro.core import PLUS_TIMES
    from repro.core.spgemm_1d_device import (build_device_plan,
                                             run_device_spgemm)
    plan = build_device_plan(a, b, 1, bs=bs,
                             semiring=semiring or PLUS_TIMES)
    return run_device_spgemm(plan, engine=engine)


def _assert_bitwise(c, ref):
    assert np.array_equal(c.indptr, ref.indptr)
    assert np.array_equal(c.indices, ref.indices)
    assert np.array_equal(c.data, ref.data)


def test_repeat_multiply_skips_planning_and_retrace():
    """Second structure-identical multiply: plan_seconds == 0, hit counted,
    zero retraces, bitwise-identical decode to a cold-plan run."""
    a = _int_matrix()
    s = SpGEMMSession()
    c1 = s.matmul(a, a, bs=16)
    assert s.stats["plan_cache_misses"] == 1
    assert not s.last_call["cache_hit"]
    assert s.last_call["plan_seconds"] > 0
    traces_after_cold = s.stats["traces"]
    assert traces_after_cold >= 1

    c2 = s.matmul(a, a, bs=16)
    assert s.stats["plan_cache_hits"] == 1
    assert s.last_call["cache_hit"]
    assert s.last_call["plan_seconds"] == 0.0
    assert s.stats["traces"] == traces_after_cold      # zero retraces
    assert s.stats["plan_seconds_saved"] > 0
    _assert_bitwise(c2, c1)
    _assert_bitwise(c1, _cold_run(a, a, bs=16))


def _published_degree_matrix():
    """HV15R's 140 entries per column (here 140.4: n=1024, band 1024, 207
    draws) with float32 normal values, for tiles at the MXU's width."""
    from repro.core import banded_clustered

    a = banded_clustered(1024, 1024, 207, seed=0)
    assert abs(a.nnz / a.shape[1] - 140.33) < 0.01 * 140.33
    return CSC(a.indptr, a.indices,
               np.random.default_rng(1).standard_normal(a.nnz)
               .astype(np.float32), a.shape)


def _value_gap(a, c):
    """The widest ``|c - c_ref| / (|A|·|A|)_ij`` of ``c`` against the
    float64 product ``a·a``, over A·A's symbolic pattern; and the count of
    entries of ``c`` outside that pattern."""
    import scipy.sparse as sp

    def dense(m, data):
        return sp.csc_matrix((data, m.indices, m.indptr),
                             shape=m.shape).toarray()

    a64 = dense(a, a.data.astype(np.float64))
    ref, scale = a64 @ a64, np.abs(a64) @ np.abs(a64)
    got = dense(c, c.data.astype(np.float64))
    pattern = dense(a, np.ones(a.nnz)) @ dense(a, np.ones(a.nnz)) > 0
    gap = np.abs(got - ref)[pattern] / scale[pattern]
    return gap.max(), np.count_nonzero(got[~pattern])


@pytest.mark.parametrize("operand, bs", [
    (_int_matrix, 16), (_published_degree_matrix, 128)],
    ids=["integer", "published_degree"])
def test_values_only_change_repacks_without_replanning(operand, bs):
    """Same structure, new values: cache hit + payload repack, no retrace,
    and the decode matches a cold plan built on the new values bitwise.
    With float32 normal values at HV15R's degree, the cold call and the
    repack both come within float32 rounding of the float64 product (gap
    at most 1e-6), with no entry outside its pattern."""
    a = operand()
    s = SpGEMMSession()
    c1 = s.matmul(a, a, bs=bs)
    traces = s.stats["traces"]

    a2 = a.astype(np.float32)       # payload dtype: repack stays legal
    a2.data[:] = a.data * 3.0 + 1.0            # same structure, new values
    c = s.matmul(a2, a2, bs=bs)
    assert s.last_call["cache_hit"] and s.last_call["repacked"]
    assert s.stats["payload_repacks"] == 1
    assert s.stats["traces"] == traces
    _assert_bitwise(c, _cold_run(a2, a2, bs=bs))
    if operand is _published_degree_matrix:
        for m, got in ((a, c1), (a2, c)):
            gap, extra = _value_gap(m, got)
            assert gap <= 1e-6 and extra == 0, (gap, extra)

    # bit-identical values again: the repack itself is skipped
    s.matmul(a2, a2, bs=bs)
    assert s.last_call["cache_hit"] and not s.last_call["repacked"]
    assert s.stats["payload_repacks"] == 1


def test_one_sided_value_change_repacks_one_side():
    """Only the changed operand is re-blockized (the repack helpers accept
    None for the untouched side) and the decode still matches a cold
    re-plan bitwise."""
    a = _int_matrix(seed=1)
    b = _int_matrix(seed=2)
    s = SpGEMMSession()
    s.matmul(a, b, bs=16)
    traces = s.stats["traces"]
    b2 = b.astype(np.float32)       # payload dtype: repack stays legal
    b2.data[:] = b.data + 2.0
    b2.data[b2.data == 0] = 1.0
    c = s.matmul(a, b2, bs=16)
    assert s.last_call["cache_hit"] and s.last_call["repacked"]
    assert s.stats["traces"] == traces
    _assert_bitwise(c, _cold_run(a, b2, bs=16))
    # the partial-repack helpers themselves: untouched side comes back
    # None; the ring hands over the changed side's values alone, and the
    # host refill (its reference) a whole stack
    from repro.core.spgemm_1d_device import (build_device_plan,
                                             refill_ring_stacks,
                                             repack_ring_payloads)
    plan = build_device_plan(a, b, 1, bs=16)
    new_a, new_b = repack_ring_payloads(plan, b=b2)
    assert new_a is None
    assert np.array_equal(new_b, b2.data[plan.b_order])
    new_a, new_b = refill_ring_stacks(plan, b=b2)
    assert new_a is None and new_b is not None
    assert new_b.shape == plan.b_tiles.shape


def test_repeated_repacks_compile_nothing():
    """The values-only scatter is compiled with the entry (the cold
    call): the first, second and third repack of the entry trace and
    compile nothing."""
    import jax.monitoring

    a = _int_matrix().astype(np.float32)
    s = SpGEMMSession()
    s.matmul(a, a, bs=16)
    (entry,) = s._cache.values()
    assert entry.scatter is not None
    compiles = []

    def listen(event, *args, **kwargs):
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    served = []
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for shift in (1.0, 2.0, 3.0):
            a2 = CSC(a.indptr, a.indices, a.data + shift, a.shape)
            served.append((a2, s.matmul(a2, a2, bs=16)))
            assert s.last_call["repacked"]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert s.stats["payload_repacks"] == 3
    for a2, c in served:
        _assert_bitwise(c, _cold_run(a2, a2, bs=16))


def test_scatter_fault_before_the_swap_quarantines(monkeypatch):
    """A repack that fails after the new values are on the device, before
    the fresh stacks are swapped in, quarantines the entry: the call is
    served by the next rung from the new values, and the next call on the
    key re-plans and serves them cleanly."""
    from repro.runtime import RetryPolicy
    from repro.runtime.faults import SimulatedXlaRuntimeError

    a = _int_matrix().astype(np.float32)
    s = SpGEMMSession(retry_policy=RetryPolicy(max_retries=0, backoff_s=0.0))
    s.matmul(a, a, bs=16)
    (entry,) = s._cache.values()
    put = []

    def failing_program(pos, vals):
        put.append(vals)
        raise SimulatedXlaRuntimeError("simulated scatter failure")

    monkeypatch.setattr(entry.scatter, "programs", (failing_program,) * 2)
    a2 = CSC(a.indptr, a.indices, a.data * 3.0 + 1.0, a.shape)
    ref = _cold_run(a2, a2, bs=16)
    _assert_bitwise(s.matmul(a2, a2, bs=16), ref)
    assert len(put) == 1 and put[0].shape == (1, a.nnz)  # values were put
    assert s.last_call["degraded"] and s.last_call["engine"] == "jnp"
    assert s.stats["quarantined"] == 1
    assert entry.args == [] and entry.scatter is None    # buffers released

    misses = s.stats["plan_cache_misses"]
    _assert_bitwise(s.matmul(a2, a2, bs=16), ref)
    assert s.last_call["engine"] == "pallas" and not s.last_call["degraded"]
    assert not s.last_call["cache_hit"]
    assert s.stats["plan_cache_misses"] == misses + 1


def test_dtype_mismatched_repack_rejected_same_dtype_accepted():
    """A values-only repack whose operand dtype differs from the session's
    payload dtype raises a typed ``ValidationError`` (stage "repack") at
    ingress — blockize would silently narrow f64 values into the f32-keyed
    entry — and the rejection must neither quarantine the healthy entry
    nor fall through the degradation ladder (a colder rung would replan
    and *accept* the cast). A same-dtype values repack stays the ordinary
    happy path."""
    from repro.core.validate import ValidationError
    a = _int_matrix()
    s = SpGEMMSession()
    s.matmul(a, a, bs=16)

    bad = a.astype(np.float64)
    bad.data[:] = a.data + 2.0       # new values AND a foreign dtype
    with pytest.raises(ValidationError, match="repack") as ei:
        s.matmul(bad, bad, bs=16)
    assert ei.value.stage == "repack"
    assert s.stats["validation_failures"] == 1
    assert s.stats["payload_repacks"] == 0      # rejected before mutation
    assert s.stats["quarantined"] == 0          # entry stays healthy
    assert s.stats["fallbacks"] == 0            # no ladder laundering

    # same structure + same values at the payload dtype: happy repack
    good = a.astype(np.float32)
    good.data[:] = a.data + 2.0
    c = s.matmul(good, good, bs=16)
    assert s.last_call["cache_hit"] and s.last_call["repacked"]
    assert s.stats["payload_repacks"] == 1
    _assert_bitwise(c, _cold_run(good, good, bs=16))


def test_chunk_is_part_of_cache_key():
    """The k-chunk streaming knob keys the 1D entry like geometry does:
    chunked and unchunked plans are distinct cache entries, both decode
    bitwise to the cold run, and an invalid chunk is rejected upfront."""
    a = _int_matrix()
    s = SpGEMMSession()
    c0 = s.matmul(a, a, bs=16)
    c1 = s.matmul(a, a, bs=16, chunk=2)
    assert not s.last_call["cache_hit"]
    assert s.stats["plan_cache_misses"] == 2
    _assert_bitwise(c1, c0)
    s.matmul(a, a, bs=16, chunk=2)              # chunked entry now cached
    assert s.last_call["cache_hit"]
    with pytest.raises(ValueError, match="chunk"):
        s.matmul(a, a, bs=16, chunk=0)
    # 2d ignores chunk (like nblocks): same entry either way
    s.matmul(a, a, algorithm="2d", grid=1, bs=16)
    s.matmul(a, a, algorithm="2d", grid=1, bs=16, chunk=4)
    assert s.last_call["cache_hit"]


def test_interpret_alongside_session_is_rejected():
    """Apps fix the Pallas interpret policy at session construction; a
    conflicting explicit interpret must not be silently ignored."""
    from repro.apps import device_spgemm_fn, sketch_apply
    from repro.apps.mcl import mcl
    from repro.core import from_coo as _fc
    s = SpGEMMSession()
    with pytest.raises(ValueError, match="interpret"):
        device_spgemm_fn(session=s, interpret=True)
    one = _fc([0], [0], [1.0], (1, 1))
    with pytest.raises(ValueError, match="interpret"):
        mcl(one, session=s, interpret=True)
    with pytest.raises(ValueError, match="interpret"):
        sketch_apply(one, one, session=s, interpret=True)


def test_one_extra_nonzero_tile_forces_miss():
    """A single stored entry in a previously-empty tile is a different
    structure: the session must re-plan and re-trace."""
    a = _int_matrix()
    s = SpGEMMSession()
    s.matmul(a, a, bs=16)
    traces = s.stats["traces"]

    rows, cols, vals = a.to_coo()
    # bottom-right corner tile of a 50x50 matrix at bs=16 is sparse; the
    # exact position only needs to be previously absent
    assert not ((rows == 49) & (cols == 49)).any()
    a2 = from_coo(np.append(rows, 49), np.append(cols, 49),
                  np.append(vals, 1.0), a.shape)
    c = s.matmul(a2, a2, bs=16)
    assert not s.last_call["cache_hit"]
    assert s.stats["plan_cache_misses"] == 2
    assert s.stats["traces"] > traces
    _assert_bitwise(c, _cold_run(a2, a2, bs=16))


def test_semiring_change_forces_miss():
    from repro.core import MIN_PLUS
    a = _int_matrix()
    s = SpGEMMSession()
    s.matmul(a, a, bs=16)
    c = s.matmul(a, a, bs=16, semiring=MIN_PLUS)
    assert not s.last_call["cache_hit"]
    assert s.stats["plan_cache_misses"] == 2
    _assert_bitwise(c, _cold_run(a, a, bs=16, semiring=MIN_PLUS))
    # and the min-plus entry is itself now cached
    s.matmul(a, a, bs=16, semiring=MIN_PLUS)
    assert s.last_call["cache_hit"]


def test_engine_and_geometry_are_separate_entries():
    a = _int_matrix()
    s = SpGEMMSession()
    cp = s.matmul(a, a, bs=16, engine="pallas")
    cj = s.matmul(a, a, bs=16, engine="jnp")
    assert s.stats["plan_cache_misses"] == 2
    _assert_bitwise(cp, cj)                     # engines agree bitwise
    s.matmul(a, a, bs=8)                        # different tile size
    assert s.stats["plan_cache_misses"] == 3
    assert len(s) == 3


def test_algorithms_share_session_not_entries():
    """1D / 2D / 3D all run through one session on a single device and
    decode identically; each algorithm is its own cache entry."""
    a = _int_matrix()
    s = SpGEMMSession()
    c1 = s.matmul(a, a, algorithm="1d", nparts=1, bs=16)
    c2 = s.matmul(a, a, algorithm="2d", grid=1, bs=16)
    c3 = s.matmul(a, a, algorithm="3d", grid=1, layers=1, bs=16)
    assert s.stats["plan_cache_misses"] == 3
    _assert_bitwise(c2, c1)
    _assert_bitwise(c3, c1)
    for alg, kw in (("1d", dict(nparts=1)), ("2d", dict(grid=1)),
                    ("3d", dict(grid=1, layers=1))):
        s.matmul(a, a, algorithm=alg, bs=16, **kw)
        assert s.last_call["cache_hit"], alg


def test_decode_map_is_built_on_the_second_use(tmp_path):
    """The cold call scans and builds no map; the plan's second use (a
    hit) builds it once, between its dispatch and its fetch; that call
    and every later one, repacks included, decode through it, bitwise
    as the scan decodes."""
    a = _positive_matrix()
    a2 = a.astype(np.float32)
    a2.data[:] = a.data * 2.0 + 1.0
    s = SpGEMMSession()
    calls = [a, a, a2, a]
    got = record(tmp_path, lambda: [s.matmul(m, m, bs=16) for m in calls])
    spans = host_spans(tmp_path)
    assert [c["mapped"] for n, _, _, c in spans
            if n == "spgemm.decode.prune"] == [0, 1, 1, 1]
    assert [n for n, *_ in spans if n in (
        "spgemm.execute.dispatch", "spgemm.decode.structure")] == [
        "spgemm.execute.dispatch", "spgemm.execute.dispatch",
        "spgemm.decode.structure", "spgemm.execute.dispatch",
        "spgemm.execute.dispatch"]
    (entry,) = s._cache.values()
    dmap = entry.plan.decode_map
    assert [c for n, _, _, c in spans if n == "spgemm.decode.structure"] \
        == [{"nnz": len(dmap.indices)}]
    for m, c in zip(calls, got):
        _assert_bitwise(c, _cold_run(m, m, bs=16))
    for c in got[1:]:
        assert c.indices is dmap.indices and c.indptr is dmap.indptr


def test_served_answers_index_arrays_are_read_only():
    """Answers decoded through the map share its index arrays: they are
    read-only, and each answer's values are its own."""
    a = _positive_matrix()
    s = SpGEMMSession()
    s.matmul(a, a, bs=16)
    c1, c2 = s.matmul(a, a, bs=16), s.matmul(a, a, bs=16)
    for c in (c1, c2):
        for arr in (c.indices, c.indptr):
            with pytest.raises(ValueError, match="read-only"):
                arr[0] = 1
    c1.data[0] += 1
    assert c2.data[0] != c1.data[0]


@pytest.mark.parametrize("algorithm,geom", [
    ("2d", dict(grid=1)), ("3d", dict(grid=1, layers=1))])
def test_summa_engines_build_no_decode_map(tmp_path, algorithm, geom):
    a = _int_matrix().astype(np.float32)
    s = SpGEMMSession()
    record(tmp_path, lambda: [s.matmul(a, a, algorithm=algorithm, bs=16,
                                       **geom) for _ in range(3)])
    spans = host_spans(tmp_path)
    assert [c["mapped"] for n, _, _, c in spans
            if n == "spgemm.decode.prune"] == [0, 0, 0]
    assert "spgemm.decode.structure" not in {n for n, *_ in spans}


def test_decode_map_is_dropped_on_eviction():
    m0, m1 = (_int_matrix(seed=i).astype(np.float32) for i in range(2))
    s = SpGEMMSession(maxsize=1)
    s.matmul(m0, m0, bs=16)
    s.matmul(m0, m0, bs=16)
    (entry,) = s._cache.values()
    plan = entry.plan
    assert plan.decode_map is not None
    s.matmul(m1, m1, bs=16)
    assert s.stats["evictions"] == 1
    assert plan.decode_map is None
    # the evicted structure comes back cold: it scans again, then maps
    s.matmul(m0, m0, bs=16)
    (entry,) = s._cache.values()
    assert entry.plan is not plan and entry.plan.decode_map is None
    s.matmul(m0, m0, bs=16)
    assert entry.plan.decode_map is not None


def test_lru_eviction_oldest_first():
    mats = [_int_matrix(seed=i) for i in range(3)]
    s = SpGEMMSession(maxsize=2)
    for m in mats:
        s.matmul(m, m, bs=16)
    assert s.stats["evictions"] == 1 and len(s) == 2
    s.matmul(mats[0], mats[0], bs=16)           # oldest was evicted
    assert not s.last_call["cache_hit"]
    s.matmul(mats[2], mats[2], bs=16)           # newest survived
    assert s.last_call["cache_hit"]


def test_session_stats_surface():
    from repro.core.device_common import SESSION_STATS
    s = SpGEMMSession()
    assert set(s.stats) == set(SESSION_STATS)
    a = _int_matrix()
    s.matmul(a, a, bs=16)
    s.matmul(a, a, bs=16)
    assert set(s.stats) == set(SESSION_STATS)
    assert s.stats["calls"] == 2


def test_invalid_algorithm_and_maxsize():
    a = _int_matrix()
    s = SpGEMMSession()
    with pytest.raises(ValueError, match="algorithm"):
        s.matmul(a, a, algorithm="4d")
    with pytest.raises(ValueError, match="maxsize"):
        SpGEMMSession(maxsize=0)


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import numpy as np
    from repro.core import SpGEMMSession, by_name, erdos_renyi
    from repro.core.spgemm_1d_device import build_device_plan, run_device_spgemm
    from repro.core.spgemm_2d_device import build_summa_plan, run_device_summa

    a = erdos_renyi(70, 70, 4.0, seed=9)
    a.data[:] = np.rint(2 * a.data)
    a.data[a.data == 0] = 1.0
    a2 = a.astype(np.float32)       # payload dtype: repack stays legal
    a2.data[:] = a.data * 2.0 + 1.0

    s = SpGEMMSession()
    for srname in ("plus_times", "bool_or_and", "min_plus"):
        sr = by_name(srname)
        c1 = s.matmul(a, a, nparts=4, bs=8, semiring=sr)
        traces = s.stats["traces"]
        c2 = s.matmul(a, a, nparts=4, bs=8, semiring=sr)
        assert s.last_call["cache_hit"], srname
        assert s.stats["traces"] == traces, srname
        # the hit built the plan's decode map and decoded through it: the
        # answer shares its indices unless entries cancelled
        dmap = next(reversed(s._cache.values())).plan.decode_map
        assert dmap is not None, srname
        assert (c2.indices is dmap.indices) == (
            c2.nnz == len(dmap.indices)), srname
        ref = run_device_spgemm(
            build_device_plan(a, a, 4, bs=8, semiring=sr))
        for x in (c1, c2):
            assert np.array_equal(x.indptr, ref.indptr), srname
            assert np.array_equal(x.indices, ref.indices), srname
            assert np.array_equal(x.data, ref.data), srname
        # values-only repack on the real multi-device ring
        c3 = s.matmul(a2, a2, nparts=4, bs=8, semiring=sr)
        assert s.last_call["repacked"], srname
        assert s.stats["traces"] == traces, srname
        ref3 = run_device_spgemm(
            build_device_plan(a2, a2, 4, bs=8, semiring=sr))
        assert np.array_equal(c3.data, ref3.data), srname
        assert np.array_equal(c3.indices, ref3.indices), srname
        assert np.array_equal(c3.indptr, ref3.indptr), srname
        assert c3.data.dtype == ref3.data.dtype, srname

    # 2D SUMMA entries on a 2x2 grid through the same session
    c2d = s.matmul(a, a, algorithm="2d", grid=2, bs=8)
    t2d = s.stats["traces"]
    c2d_rep = s.matmul(a2, a2, algorithm="2d", grid=2, bs=8)
    assert s.last_call["cache_hit"] and s.last_call["repacked"]
    assert s.stats["traces"] == t2d
    ref2d = run_device_summa(build_summa_plan(a2, a2, grid=2, bs=8))
    assert np.array_equal(c2d_rep.data, ref2d.data)
    print("HITS", s.stats["plan_cache_hits"])
    print("ALLOK")
""")


def test_session_on_8_devices():
    """Cache-hit + values-repack semantics hold on a real multi-device
    mesh for all three semirings (1D ring) and the 2D SUMMA grid."""
    out = run_subprocess(MULTI_DEVICE_SCRIPT, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ALLOK" in out.stdout
