"""Multi-tenant SpGEMM service: coalescing, budgets, breakers, telemetry.

Everything here drives :class:`repro.serve.SpGEMMService` through its
public admission API with a *fake injectable clock* — no wall-clock
sleeps, no timing assertions against real time (the PR 7 retry
discipline extended to serving).
"""

import numpy as np
import pytest

from repro.core import session as session_mod
from repro.core.session import SpGEMMSession
from repro.core.sparse import banded_clustered, erdos_renyi
from repro.core.spgemm_1d import spgemm_1d
from repro.core.validate import ValidationError
from repro.serve import (SERVICE_STATS, ServicePolicy, SpGEMMRequest,
                         SpGEMMService, TenantOverloadError)
from repro.serve import spgemm_service as service_mod


class Clock:
    """Manual monotonic clock: ``tick`` advances per call (0 = frozen)."""

    def __init__(self, tick=0.0):
        self.now = 0.0
        self.tick = tick

    def __call__(self):
        self.now += self.tick
        return self.now

    def advance(self, dt):
        self.now += dt


def _graph(n=96, d=4.0, seed=0):
    g = banded_clustered(n, max(n // 16, 4), d, seed=seed)
    g.data[:] = np.rint(2 * g.data)
    g.data[g.data == 0] = 1.0
    return g.astype(np.float32)


def _distinct(i, n=64):
    g = erdos_renyi(n, n, 3.0, seed=100 + i)
    g.data[:] = 1.0
    return g.astype(np.float32)


def _oracle(g):
    return spgemm_1d(g, g, 1).concat().prune(0.0).astype(np.float32)


@pytest.fixture(scope="module")
def shared_graph():
    return _graph()


def test_service_stats_surface_pinned():
    svc = SpGEMMService()
    assert set(svc.stats()) == set(SERVICE_STATS)
    # and stays pinned after traffic
    g = _distinct(0)
    svc.serve([SpGEMMRequest(tenant="a", a=g, b=g, bs=16)])
    assert set(svc.stats()) == set(SERVICE_STATS)


def test_cross_tenant_coalescing_one_trace_n_results(shared_graph):
    """N requests for the same structure+values from DIFFERENT tenants
    cost one session multiply — one plan, one trace — and every caller
    gets the bitwise-identical decoded result."""
    g = shared_graph
    svc = SpGEMMService()
    reqs = [SpGEMMRequest(tenant=t, a=g, b=g, bs=16)
            for t in ("alice", "bob", "carol", "alice")]
    results = svc.serve(reqs)

    assert all(r.ok for r in results)
    assert [r.leader for r in results] == [True, False, False, False]
    assert all(r.coalesced for r in results)
    want = _oracle(g)
    for r in results:
        np.testing.assert_array_equal(r.value.indptr, want.indptr)
        np.testing.assert_array_equal(r.value.indices, want.indices)
        np.testing.assert_array_equal(r.value.data, want.data)

    sess = svc.session.stats
    assert sess["traces"] == 1
    assert sess["plan_cache_misses"] == 1
    st = svc.stats()
    assert st["requests"] == 4 and st["served"] == 4
    assert st["coalesced"] == 3
    assert st["coalesce_rate"] == pytest.approx(0.75)


def test_values_variant_rides_repack_path(shared_graph):
    """Same structure, different values: a separate coalescing group that
    reuses the cached plan/executable via the session's values-only
    repack — no second trace, no second planning pass."""
    g = shared_graph
    jit = g.astype(np.float32)
    jit.data[:] = g.data + 1.0
    svc = SpGEMMService()
    first = svc.serve([SpGEMMRequest(tenant="alice", a=g, b=g, bs=16)])[0]
    second = svc.serve([SpGEMMRequest(tenant="bob", a=jit, b=jit, bs=16)])[0]

    assert first.ok and second.ok
    assert not second.coalesced                  # different group...
    assert second.cache_hit                      # ...same cached plan
    assert second.call_stats["repacked"]
    sess = svc.session.stats
    assert sess["traces"] == 1
    assert sess["payload_repacks"] == 1
    assert sess["plan_cache_misses"] == 1
    want = _oracle(jit)
    np.testing.assert_array_equal(second.value.data, want.data)


@pytest.mark.parametrize("own_session", [False, True])
def test_tenant_quota_evicts_only_that_tenant(own_session):
    """tenant_quota bounds entries per tenant, LRU-first, and the
    eviction is attributed to the owning tenant — another tenant's
    cached plans are untouched. The service enforces it on a session it
    was handed as on one it built."""
    svc = SpGEMMService(SpGEMMSession() if own_session else None,
                        policy=ServicePolicy(tenant_quota=2))
    gb = _distinct(9)
    assert svc.serve([SpGEMMRequest(tenant="b", a=gb, b=gb, bs=16)])[0].ok
    for i in range(3):
        g = _distinct(i)
        assert svc.serve([SpGEMMRequest(tenant="a", a=g, b=g, bs=16)])[0].ok

    assert svc.cached_entries("a") == 2
    assert svc.cached_entries("b") == 1
    assert svc.stats()["evictions_by_tenant"] == {"a": 1}
    # the evicted (oldest) structure replans on return; the survivor hits
    g0 = _distinct(0)
    r = svc.serve([SpGEMMRequest(tenant="a", a=g0, b=g0, bs=16)])[0]
    assert r.ok and not r.cache_hit
    g2 = _distinct(2)
    r = svc.serve([SpGEMMRequest(tenant="a", a=g2, b=g2, bs=16)])[0]
    assert r.ok and r.cache_hit


def test_global_byte_budget_bounds_cache():
    """max_bytes evicts LRU-first but always keeps the newest entry, so
    an oversized multiply still serves; bytes_cached tracks the payload
    stacks of what actually stays resident."""
    svc = SpGEMMService(policy=ServicePolicy(max_bytes=1))
    for i in range(3):
        g = _distinct(i)
        assert svc.serve([SpGEMMRequest(tenant="a", a=g, b=g, bs=16)])[0].ok
    assert svc.session.cached_entries() == 1
    assert sum(svc.stats()["evictions_by_tenant"].values()) == 2
    assert svc.session.cached_bytes() > 0
    assert svc.session.stats["bytes_cached"] == svc.session.cached_bytes()


def test_breaker_opens_per_tenant_and_recovers():
    """Tenant A's failures open A's breaker only; while open, A is
    rejected at admission (typed TenantOverloadError, never raised); the
    cooldown elapsing on the injectable clock half-opens it and one
    success closes it."""
    clk = Clock()
    svc = SpGEMMService(policy=ServicePolicy(breaker_threshold=2,
                                             breaker_cooldown_s=10.0),
                        clock=clk)
    g = _graph(64)
    bad = erdos_renyi(48, 32, 3.0, seed=7).astype(np.float32)  # 48x32

    for _ in range(2):
        r = svc.serve([SpGEMMRequest(tenant="a", a=bad, b=bad, bs=16)])[0]
        assert not r.ok and isinstance(r.error, ValidationError)
    assert svc.breaker_state("a") == "open"
    assert svc.breaker_state("b") == "closed"

    r = svc.serve([SpGEMMRequest(tenant="a", a=g, b=g, bs=16)])[0]
    assert r.rejected and not r.ok and r.value is None
    assert isinstance(r.error, TenantOverloadError)
    assert r.error.stage == "admit"

    # tenant B serves normally through A's outage
    r = svc.serve([SpGEMMRequest(tenant="b", a=g, b=g, bs=16)])[0]
    assert r.ok and not r.rejected

    clk.advance(10.0)
    assert svc.breaker_state("a") == "half_open"
    r = svc.serve([SpGEMMRequest(tenant="a", a=g, b=g, bs=16)])[0]
    assert r.ok
    assert svc.breaker_state("a") == "closed"

    st = svc.stats()
    assert st["failed"] == 2
    assert st["rejected_breaker"] == 1
    assert st["served"] == 2          # B through the outage + A recovered
    assert st["requests"] == 5        # rejection still counted as admitted


def test_failure_charges_every_group_member():
    """A coalesced group that fails charges each member's tenant breaker
    — riders share the outcome, not just the leader."""
    clk = Clock()
    svc = SpGEMMService(policy=ServicePolicy(breaker_threshold=1,
                                             breaker_cooldown_s=5.0),
                        clock=clk)
    bad = erdos_renyi(48, 32, 3.0, seed=7).astype(np.float32)
    results = svc.serve([SpGEMMRequest(tenant=t, a=bad, b=bad, bs=16)
                         for t in ("a", "b")])
    assert not any(r.ok for r in results)
    assert svc.breaker_state("a") == "open"
    assert svc.breaker_state("b") == "open"


def test_prefetch_warms_the_plan(shared_graph):
    g = shared_graph
    svc = SpGEMMService()
    assert svc.prefetch("alice", g, g, bs=16)
    r = svc.serve([SpGEMMRequest(tenant="alice", a=g, b=g, bs=16)])[0]
    assert r.ok and r.cache_hit
    assert r.call_stats["plan_seconds"] == 0.0
    assert svc.stats()["prefetched"] == 1


def test_prefetch_failure_counts_against_breaker():
    clk = Clock()
    svc = SpGEMMService(policy=ServicePolicy(breaker_threshold=1,
                                             breaker_cooldown_s=5.0),
                        clock=clk)
    bad = erdos_renyi(48, 32, 3.0, seed=7).astype(np.float32)
    assert not svc.prefetch("a", bad, bad, bs=16)
    assert svc.breaker_state("a") == "open"


def test_latency_on_injectable_clock(shared_graph):
    """Latency accounting is fully deterministic on the injected clock:
    one tick between a group's start and finish, shared by every member
    of the group — tier-1 never reads wall time here."""
    g = shared_graph
    clk = Clock(tick=1.0)
    svc = SpGEMMService(clock=clk)
    results = svc.serve([SpGEMMRequest(tenant=t, a=g, b=g, bs=16)
                         for t in ("alice", "bob")])
    assert [r.latency_s for r in results] == [1.0, 1.0]
    st = svc.stats()
    assert st["latency_p50_s"] == 1.0
    assert st["latency_p99_s"] == 1.0


def test_coalesce_disabled_serves_per_request(shared_graph):
    g = shared_graph
    svc = SpGEMMService(policy=ServicePolicy(coalesce=False))
    results = svc.serve([SpGEMMRequest(tenant="a", a=g, b=g, bs=16)
                         for _ in range(3)])
    assert all(r.ok and not r.coalesced and r.leader for r in results)
    st = svc.stats()
    assert st["coalesced"] == 0
    # the session cache still serves the repeats
    assert st["cache_hits"] == 2


def test_byo_session_rejects_stale_kwargs(shared_graph):
    sess = SpGEMMSession(maxsize=4)
    svc = SpGEMMService(session=sess)
    assert svc.session is sess
    with pytest.raises(ValueError):
        SpGEMMService(session=sess, interpret=True)
    with pytest.raises(ValueError):
        SpGEMMService(session=sess, max_retries=2)


def test_serve_empty_batch():
    svc = SpGEMMService()
    assert svc.serve([]) == []
    assert svc.run_pending() == {}


@pytest.mark.parametrize("values_only", [False, True],
                         ids=["cold", "values_only"])
def test_one_served_request_fingerprints_each_operand_once(
        monkeypatch, values_only):
    """A served request's structure and values fingerprints are computed
    once each per operand, on a cold call and on a values-only call:
    every module that binds a fingerprint function is counted."""
    a, b = _graph(seed=1), _graph(seed=1)
    svc = SpGEMMService()
    if values_only:
        assert svc.serve([SpGEMMRequest(tenant="t", a=a, b=b, bs=16)])[0].ok
        a = a.astype(np.float32)
        a.data[:] += 1.0
    counts = {}

    def counting(name, fn):
        def wrapped(m):
            counts[name, id(m)] = counts.get((name, id(m)), 0) + 1
            return fn(m)
        return wrapped

    for mod in (session_mod, service_mod):
        for name in ("structure_fingerprint", "values_fingerprint"):
            if hasattr(mod, name):
                monkeypatch.setattr(mod, name,
                                    counting(name, getattr(mod, name)))
    (r,) = svc.serve([SpGEMMRequest(tenant="t", a=a, b=b, bs=16)])
    assert r.ok and r.cache_hit == values_only
    assert r.call_stats["repacked"] == values_only
    assert counts == {(name, id(m)): 1
                      for name in ("structure_fingerprint",
                                   "values_fingerprint")
                      for m in (a, b)}


def _respelled(g, algorithm, change):
    """The request for ``g`` under ``algorithm`` and a second request
    that differs from it only by ``change``."""
    geom = dict(nparts=1) if algorithm == "1d" else dict(grid=1)
    first = SpGEMMRequest(tenant="x", a=g, b=g, bs=16, algorithm=algorithm,
                          **geom)
    knobs = dict(tenant="y", a=g, b=g, bs=16, algorithm=algorithm, **geom)
    if change == "values":
        g2 = g.astype(np.float32)
        g2.data[:] += 1.0
        knobs.update(a=g2, b=g2)
    elif change == "unused_geometry":
        # the grid of a 1d call and the layers of a 2d call plan nothing
        knobs["grid" if algorithm == "1d" else "layers"] = 2
    elif change == "engine":
        knobs.update(engine="pallas")        # what "auto" resolves to
    elif change != "same":
        knobs[change] = 2
    return first, SpGEMMRequest(**knobs)


@pytest.mark.parametrize("change", ["same", "nblocks", "chunk", "values",
                                    "unused_geometry", "engine"])
@pytest.mark.parametrize("algorithm", ["1d", "2d"])
def test_coalesce_exactly_when_one_entry_serves_both(
        shared_graph, algorithm, change):
    """Two requests coalesce into one group exactly when the session,
    serving them one after the other, answers the second from the
    first's cache entry without a repack."""
    first, second = _respelled(shared_graph, algorithm, change)
    sess = SpGEMMSession()
    solo = SpGEMMService(sess, policy=ServicePolicy(coalesce=False)) \
        .serve([first, second])
    assert all(r.ok for r in solo)
    one_entry = solo[1].call_stats["key"] == solo[0].call_stats["key"]
    assert one_entry == solo[1].cache_hit
    same_answer = one_entry and not solo[1].call_stats["repacked"]

    both = SpGEMMService(sess).serve([first, second])
    assert all(r.ok for r in both)
    assert both[1].coalesced == same_answer
    assert same_answer == (change in ("same", "unused_geometry", "engine")
                           or (change in ("nblocks", "chunk")
                               and algorithm == "2d"))
    for r, req in zip(both, (first, second)):
        np.testing.assert_array_equal(r.value.data, _oracle(req.a).data)
