"""SpGEMM-as-a-service: a request-driven multi-tenant layer over sessions.

The paper's workloads — graph algorithms, AMG setup, randomized sketching
— are exactly the shape where many callers repeatedly multiply against the
same shared structure (a social graph, a mesh operator), so the 1D
algorithm's plan reuse pays off at *serving* scale: sustained throughput
under concurrent mixed load, not one multiply's latency (ROADMAP open
item 1; Ranawaka et al., arXiv:2408.11988 make the distributed case).

:class:`SpGEMMService` is that layer, built strictly on top of
:class:`~repro.core.session.SpGEMMSession` (ROADMAP session policy —
replint RS004 — holds here too: the service never plans or compiles
anything itself):

  * **admission queue** — :meth:`submit` accepts
    ``SpGEMMRequest(tenant, a, b, semiring, algorithm, ...)`` and returns
    a ticket; :meth:`run_pending` drains the queue and returns a
    ``{ticket: ServedResult}`` map (:meth:`serve` is the submit+drain
    convenience for a whole batch).
  * **fingerprint coalescing** — the session prepares (validates and
    fingerprints) each queued request once, and requests are grouped on
    the prepared ``Call.group`` (cache key + values fingerprints): N
    concurrent callers multiplying the same shared graph cost ONE session
    multiply — one plan, one executable, one trace — and all N receive the
    same decoded result. Same structure with different values is a
    separate group that rides the values-only repack on the shared plan.
  * **per-tenant budgets** — the service owns tenant accounting: it
    records which tenant created each cached entry, enforces
    ``tenant_quota`` / ``tenant_max_bytes`` over them LRU-first, and
    charges every eviction to the creator (``evictions_by_tenant``).
  * **warm-plan prefetch** — :meth:`prefetch` pre-builds (and caches) the
    plan/executable for a declared structure, so a tenant's first real
    request is already a cache hit.
  * **failure routing** — whatever escapes the session's typed-error
    retry/degradation ladder is returned as a failed
    :class:`ServedResult` (never raised through the drain loop), recorded
    against the *requesting tenant's* circuit breaker
    (:class:`~repro.runtime.fault_tolerance.CircuitBreaker`): a tenant
    whose requests keep failing is rejected at admission until its
    cooldown elapses, and tenant A's faults never open tenant B's
    breaker.
  * **telemetry** — :meth:`stats` exports exactly the
    :data:`SERVICE_STATS` surface (p50/p99 latency, coalesce rate, cache
    hit rate, bytes moved planned/padded, per-tenant evictions);
    ``benchmarks/serving_throughput.py`` merges it into
    ``BENCH_paper_figs.json`` and ``tools/bench_smoke.sh`` gates it.

All timing runs on an injectable ``clock`` (latencies) and the session's
injectable retry sleep (backoff) — tier-1 never wall-clock sleeps,
matching the PR 7 retry discipline.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter, OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..core.semiring import PLUS_TIMES, Semiring
from ..core.session import Call, SpGEMMSession
from ..core.sparse import CSC
from ..core.validate import SpGEMMError
from ..runtime.fault_tolerance import CircuitBreaker
from ..runtime.spans import span

__all__ = ["SERVICE_STATS", "ServicePolicy", "SpGEMMRequest", "ServedResult",
           "SpGEMMService", "TenantOverloadError"]

# the serving telemetry surface — tests/test_spgemm_service.py pins these
# keys; benchmarks/serving_throughput.py exports them as rows:
#   requests            : tickets admitted (incl. later rejections)
#   served              : requests answered with a result
#   failed              : requests refused at the session's ingress or whose
#                         group's multiply failed (typed SpGEMMError)
#   rejected_breaker    : requests refused at admission — tenant's circuit
#                         was open
#   coalesced           : requests served by another request's multiply
#                         (group size − 1, summed)
#   coalesce_rate       : coalesced / served
#   cache_hits          : executed groups served from the session's plan
#                         cache (no host planning)
#   cache_hit_rate      : cache_hits / executed groups
#   latency_p50_s / latency_p99_s : request latency percentiles on the
#                         injectable clock (a coalesced member's latency
#                         is its group's)
#   bytes_moved_planned / bytes_moved_padded : communication bytes of the
#                         executed plans, summed per executed group
#   prefetched          : warm-plan prefetches performed
#   evictions_by_tenant : {tenant: evictions} that the service's calls
#                         caused (entry creator pays)
SERVICE_STATS = ("requests", "served", "failed", "rejected_breaker",
                 "coalesced", "coalesce_rate", "cache_hits",
                 "cache_hit_rate", "latency_p50_s", "latency_p99_s",
                 "bytes_moved_planned", "bytes_moved_padded",
                 "prefetched", "evictions_by_tenant")


@dataclasses.dataclass(frozen=True)
class ServicePolicy:
    """Admission/budget policy, fixed at service construction.

    ``tenant_quota`` / ``tenant_max_bytes`` bound the entries each tenant
    created; the service enforces them. ``max_bytes`` forwards to the
    session the service creates (ignored when a session is supplied — its
    own budget stands). ``coalesce=False`` disables fingerprint grouping
    (every request is its own session call; the serving benchmark's
    baseline). Breaker knobs shape the per-tenant circuit breakers.
    """

    tenant_quota: Optional[int] = None
    tenant_max_bytes: Optional[int] = None
    max_bytes: Optional[int] = None
    coalesce: bool = True
    breaker_threshold: int = 3
    breaker_cooldown_s: float = 30.0


@dataclasses.dataclass
class SpGEMMRequest:
    """One admission-queue entry: tenant + operands + the execution knobs
    of :meth:`SpGEMMSession.prepare`."""

    tenant: str
    a: CSC
    b: CSC
    algorithm: str = "1d"
    semiring: Semiring = PLUS_TIMES
    nparts: int = 1
    grid: int = 1
    layers: int = 1
    bs: int = 32
    nblocks: Optional[int] = None
    chunk: Optional[int] = None
    dtype: type = np.float32
    engine: str = "auto"

    def matmul_kwargs(self) -> dict:
        return dict(algorithm=self.algorithm, semiring=self.semiring,
                    nparts=self.nparts, grid=self.grid, layers=self.layers,
                    bs=self.bs, nblocks=self.nblocks, chunk=self.chunk,
                    dtype=self.dtype, engine=self.engine)


@dataclasses.dataclass
class ServedResult:
    """Outcome of one admitted request.

    ``ok`` — a result was produced; ``value`` is the decoded CSC.
    ``rejected`` — refused at admission (open breaker); never executed.
    ``error`` — the typed :class:`SpGEMMError` for failed/rejected
    requests. ``coalesced`` — served by a group of size > 1; ``leader``
    — this request's multiply actually ran (False for riders).
    ``cache_hit`` / ``call_stats`` mirror the session's ``last_call``
    for the group's multiply; ``latency_s`` is measured on the service
    clock (shared across a group).
    """

    tenant: str
    ok: bool
    value: Optional[CSC] = None
    error: Optional[Exception] = None
    rejected: bool = False
    coalesced: bool = False
    leader: bool = False
    cache_hit: bool = False
    latency_s: float = 0.0
    call_stats: dict = dataclasses.field(default_factory=dict)


class TenantOverloadError(SpGEMMError):
    """Request refused at admission: the tenant's circuit breaker is open
    (too many consecutive failures; retry after the cooldown)."""


class SpGEMMService:
    """Request-driven multi-tenant SpGEMM service over one shared session.

    ``session`` — bring your own (its ``max_bytes`` stands), or None to
    have the service build one from ``policy`` (``interpret`` and any extra
    ``session_kwargs`` — fault injectors, retry policy, injectable retry
    sleep — forward to the constructor).
    ``clock`` — injectable monotonic-seconds source for latency
    accounting and breaker cooldowns; tests drive a fake clock, tier-1
    never waits on wall time.
    """

    def __init__(self, session: Optional[SpGEMMSession] = None, *,
                 policy: ServicePolicy = ServicePolicy(),
                 clock: Callable[[], float] = time.monotonic,
                 interpret: Optional[bool] = None,
                 **session_kwargs):
        if any(v is not None and v < 1
               for v in (policy.tenant_quota, policy.tenant_max_bytes)):
            raise ValueError("tenant budgets must be >= 1 or None")
        self.policy = policy
        self.clock = clock
        if session is None:
            session = SpGEMMSession(interpret=interpret,
                                    max_bytes=policy.max_bytes,
                                    **session_kwargs)
        elif interpret is not None or session_kwargs:
            raise ValueError(
                "interpret/session kwargs are fixed when the session is "
                "created; construct the SpGEMMSession yourself or let the "
                "service build it")
        self.session = session
        # cache key -> the tenant whose call created the entry
        self._owners: Dict[tuple, str] = {}
        self._evictions_by_tenant: Dict[str, int] = Counter()
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._queue: List[Tuple[int, SpGEMMRequest]] = []
        self._rejected: Dict[int, ServedResult] = {}
        self._next_ticket = 0
        self._latencies: List[float] = []
        self._counts = {"requests": 0, "served": 0, "failed": 0,
                        "rejected_breaker": 0, "coalesced": 0,
                        "cache_hits": 0, "groups_executed": 0,
                        "prefetched": 0}
        self._bytes = {"planned": 0, "padded": 0}

    # ---- admission ---------------------------------------------------------

    def _breaker(self, tenant: str) -> CircuitBreaker:
        br = self._breakers.get(tenant)
        if br is None:
            br = CircuitBreaker(threshold=self.policy.breaker_threshold,
                                cooldown_s=self.policy.breaker_cooldown_s,
                                clock=self.clock)
            self._breakers[tenant] = br
        return br

    def breaker_state(self, tenant: str) -> str:
        """closed / open / half_open for ``tenant`` (closed if unseen)."""
        br = self._breakers.get(tenant)
        return br.state if br is not None else "closed"

    def submit(self, request: SpGEMMRequest) -> int:
        """Admit one request; returns its ticket.

        An open tenant breaker rejects here — fail-fast at admission, the
        queue never sees the request; the rejection is delivered through
        :meth:`run_pending` like any other outcome.
        """
        ticket = self._next_ticket
        self._next_ticket += 1
        self._counts["requests"] += 1
        if not self._breaker(request.tenant).allow():
            self._counts["rejected_breaker"] += 1
            self._rejected[ticket] = ServedResult(
                tenant=request.tenant, ok=False, rejected=True,
                error=TenantOverloadError(
                    f"tenant {request.tenant!r} circuit breaker is open "
                    f"(cooldown {self.policy.breaker_cooldown_s}s)",
                    stage="admit", context={"tenant": request.tenant}))
            return ticket
        self._queue.append((ticket, request))
        return ticket

    def prefetch(self, tenant: str, a: CSC, b: CSC, **knobs) -> bool:
        """Warm-plan prefetch: run one multiply for a declared structure so
        the plan/executable is cached before real traffic arrives (the
        session only caches entries that executed cleanly, so a prefetch
        is a full multiply whose result is discarded). Returns True if the
        plan is now warm; a failed prefetch counts against the tenant's
        breaker exactly like a failed request."""
        self._counts["prefetched"] += 1
        try:
            self._run(tenant, self.session.prepare(a, b, **knobs))
        except SpGEMMError:
            self._breaker(tenant).record_failure()
            return False
        self._breaker(tenant).record_success()
        return True

    # ---- tenant accounting -------------------------------------------------

    def _run(self, tenant: str, call: Call) -> CSC:
        """One session multiply on ``tenant``'s behalf, then the tenant
        accounting: a cold call's entry is the tenant's, its budgets are
        enforced LRU-first over its entries, and every eviction — the
        session's global ones included — is charged to the creator."""
        c = self.session.run(call)
        last = self.session.last_call
        if not last["cache_hit"]:
            self._owners[last["key"]] = tenant
        evicted = [k for k, _ in last["evicted"]]
        cached = self.session.entries()
        owned = [(k, n) for k, n in cached if self._owners.get(k) == tenant]
        quota, cap = self.policy.tenant_quota, self.policy.tenant_max_bytes
        while (quota is not None and len(owned) > quota) or (
                cap is not None and len(owned) > 1
                and sum(n for _, n in owned) > cap):
            evicted.append(owned.pop(0)[0])
            self.session.evict(evicted[-1])
        for key in evicted:
            self._evictions_by_tenant[self._owners.pop(key, "<untagged>")] \
                += 1
        # forget entries the session quarantined or dropped
        live = {k for k, _ in cached}
        self._owners = {k: t for k, t in self._owners.items() if k in live}
        return c

    def cached_entries(self, tenant: Optional[str] = None) -> int:
        """Cached entry count — all, or those ``tenant`` made."""
        return sum(1 for k, _ in self.session.entries()
                   if tenant is None or self._owners.get(k) == tenant)

    def cached_bytes(self, tenant: Optional[str] = None) -> int:
        """Device bytes of cached entries — all, or those ``tenant`` made."""
        return sum(n for k, n in self.session.entries()
                   if tenant is None or self._owners.get(k) == tenant)

    # ---- the drain loop ----------------------------------------------------

    def run_pending(self) -> Dict[int, ServedResult]:
        """Drain the admission queue: prepare each request once, coalesce,
        execute one multiply per group through the session, deliver every
        outstanding outcome (including admission rejections) keyed by
        ticket."""
        batch, self._queue = self._queue, []
        out, self._rejected = self._rejected, {}

        calls: List[Union[Call, SpGEMMError]] = []
        for _, req in batch:
            try:
                calls.append(self.session.prepare(req.a, req.b,
                                                  **req.matmul_kwargs()))
            except SpGEMMError as e:
                calls.append(e)
        groups: "OrderedDict[object, tuple]" = OrderedDict()
        with span("spgemm.service.coalesce", requests=len(batch)):
            for (ticket, req), call in zip(batch, calls):
                # coalescing off, or refused at the session's ingress →
                # the ticket is its own group
                key = call.group if self.policy.coalesce \
                    and isinstance(call, Call) else ticket
                groups.setdefault(key, (call, []))[1].append((ticket, req))

        for call, members in groups.values():
            t0 = self.clock()
            c, err = None, call if isinstance(call, SpGEMMError) else None
            if err is None:
                try:
                    c = self._run(members[0][1].tenant, call)
                except SpGEMMError as e:
                    err = e
            latency = self.clock() - t0
            ok = err is None
            stats = dict(self.session.last_call) if ok else {}
            if ok:
                self._counts["groups_executed"] += 1
                self._counts["served"] += len(members)
                self._counts["coalesced"] += len(members) - 1
                self._counts["cache_hits"] += stats["cache_hit"]
                self._bytes["planned"] += int(stats["comm_bytes_planned"])
                self._bytes["padded"] += int(stats["comm_bytes_padded"])
            else:
                self._counts["failed"] += len(members)
            for i, (ticket, req) in enumerate(members):
                br = self._breaker(req.tenant)
                if ok:
                    br.record_success()
                else:
                    br.record_failure()
                self._latencies.append(latency)
                out[ticket] = ServedResult(
                    tenant=req.tenant, ok=ok, value=c, error=err,
                    coalesced=len(members) > 1, leader=i == 0,
                    cache_hit=bool(stats.get("cache_hit", False)),
                    latency_s=latency, call_stats=stats)
        return out

    def serve(self, requests: Sequence[SpGEMMRequest]) -> List[ServedResult]:
        """Submit a batch and drain it: results in request order."""
        tickets = [self.submit(r) for r in requests]
        done = self.run_pending()
        return [done[t] for t in tickets]

    # ---- telemetry ---------------------------------------------------------

    def stats(self) -> dict:
        """The :data:`SERVICE_STATS` surface, computed from the counters."""
        n = self._counts
        lat = np.asarray(self._latencies, dtype=np.float64)
        served = n["served"]
        executed = n["groups_executed"]
        return {
            "requests": n["requests"],
            "served": served,
            "failed": n["failed"],
            "rejected_breaker": n["rejected_breaker"],
            "coalesced": n["coalesced"],
            "coalesce_rate": n["coalesced"] / served if served else 0.0,
            "cache_hits": n["cache_hits"],
            "cache_hit_rate":
                n["cache_hits"] / executed if executed else 0.0,
            "latency_p50_s":
                float(np.percentile(lat, 50)) if lat.size else 0.0,
            "latency_p99_s":
                float(np.percentile(lat, 99)) if lat.size else 0.0,
            "bytes_moved_planned": self._bytes["planned"],
            "bytes_moved_padded": self._bytes["padded"],
            "prefetched": n["prefetched"],
            "evictions_by_tenant": dict(self._evictions_by_tenant),
        }
