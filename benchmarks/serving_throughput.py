"""Serving throughput — the multi-tenant SpGEMM service under mixed load.

The ROADMAP's "millions of users" direction measured honestly: two tenants
share one graph structure (the serving sweet spot the paper's plan reuse
enables) and stream waves of requests through ``serve.spgemm_service``:

  * **alice** multiplies the shared graph as-is — her K requests per wave
    carry identical fingerprints and coalesce into ONE session multiply;
  * **bob** multiplies a values-jittered twin — same structure, different
    values, so his group rides the values-only repack path on the *same*
    cached plan/executable alice warmed.

Rows (gated by ``tools/bench_smoke.sh``):

  * ``mixed/throughput_coalesced_rps`` vs ``mixed/throughput_uncoalesced_rps``
    — the same workload through a coalescing service vs one with
    coalescing disabled (every request its own session call; the session
    cache still serves it, so the baseline is the *strong* one) —
    ``mixed/throughput_ratio_x`` must stay ≥ 5×;
  * ``mixed/coalesce_rate`` / ``mixed/cache_hit_rate`` — both must be > 0;
  * ``mixed/p50_latency_s`` / ``mixed/p99_latency_s`` and
    ``mixed/bytes_planned_MB`` / ``mixed/bytes_padded_MB`` — the
    telemetry surface, recorded into the trajectory;
  * ``alice/match_oracle`` / ``bob/match_oracle`` — every served result
    bitwise-equal to the ``spgemm_1d`` host oracle (integer-valued
    operands make that exact);
  * ``quota/evictions`` — a third tenant with a 1-entry quota cycling
    through distinct structures: per-tenant budgets actually evict, and
    only that tenant pays.

``python -m benchmarks.serving_throughput --json [PATH]`` merges rows into
``BENCH_paper_figs.json`` exactly like ``device_compare``.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.sparse import CSC, banded_clustered, erdos_renyi
from repro.core.spgemm_1d import spgemm_1d
from repro.serve import ServicePolicy, SpGEMMRequest, SpGEMMService

from .common import Csv
from .device_compare import DEFAULT_JSON, intify, merge_json

WAVES = 4
PER_TENANT = 16          # requests per tenant per wave
BS = 32


def _bitwise(c: CSC, ref: CSC) -> float:
    return float(np.array_equal(c.indptr, ref.indptr)
                 and np.array_equal(c.indices, ref.indices)
                 and np.array_equal(c.data, ref.data))


def _requests(g: CSC, g_jit: CSC) -> list:
    reqs = [SpGEMMRequest(tenant="alice", a=g, b=g, bs=BS)
            for _ in range(PER_TENANT)]
    reqs += [SpGEMMRequest(tenant="bob", a=g_jit, b=g_jit, bs=BS)
             for _ in range(PER_TENANT)]
    return reqs


def _run_waves(svc: SpGEMMService, g: CSC, g_jit: CSC) -> list:
    results = []
    for _ in range(WAVES):
        results.extend(svc.serve(_requests(g, g_jit)))
    return results


def main(scale: int = 1) -> Csv:
    csv = Csv("serving_throughput")

    n = 256 * scale
    g = intify(banded_clustered(n, max(n // 32, 8), 5.0, seed=31))
    g = g.astype(np.float32)
    g_jit = g.astype(np.float32)
    g_jit.data[:] = g.data + 1.0
    g_jit.data[g_jit.data == 0] = 3.0

    oracle = {
        "alice": spgemm_1d(g, g, 1).concat().prune(0.0).astype(np.float32),
        "bob": spgemm_1d(g_jit, g_jit, 1).concat().prune(0.0)
               .astype(np.float32),
    }

    # --- coalescing service: shared structure, one plan, N results ----------
    svc = SpGEMMService()
    svc.prefetch("alice", g, g, bs=BS)           # warm the shared plan
    t0 = time.perf_counter()
    results = _run_waves(svc, g, g_jit)
    t_co = time.perf_counter() - t0
    nreq = len(results)

    ok = [r for r in results if r.ok]
    assert len(ok) == nreq, f"{nreq - len(ok)} serving failures"
    match = {t: 1.0 for t in ("alice", "bob")}
    for r in results:
        match[r.tenant] = min(match[r.tenant],
                              _bitwise(r.value, oracle[r.tenant]))
    stats = svc.stats()

    # --- uncoalesced baseline: same workload, grouping disabled -------------
    base = SpGEMMService(policy=ServicePolicy(coalesce=False))
    base.prefetch("alice", g, g, bs=BS)
    t0 = time.perf_counter()
    base_results = _run_waves(base, g, g_jit)
    t_un = time.perf_counter() - t0
    assert all(r.ok for r in base_results)

    rps_co = nreq / max(t_co, 1e-9)
    rps_un = len(base_results) / max(t_un, 1e-9)
    csv.add("mixed/requests", nreq,
            f"{WAVES} waves x 2 tenants x {PER_TENANT}")
    csv.add("mixed/throughput_coalesced_rps", rps_co)
    csv.add("mixed/throughput_uncoalesced_rps", rps_un)
    csv.add("mixed/throughput_ratio_x", rps_co / max(rps_un, 1e-9),
            "coalesced steady state vs per-request session calls")
    csv.add("mixed/coalesce_rate", stats["coalesce_rate"])
    csv.add("mixed/cache_hit_rate", stats["cache_hit_rate"])
    csv.add("mixed/p50_latency_s", stats["latency_p50_s"])
    csv.add("mixed/p99_latency_s", stats["latency_p99_s"])
    csv.add("mixed/bytes_planned_MB", stats["bytes_moved_planned"] / 2**20)
    csv.add("mixed/bytes_padded_MB", stats["bytes_moved_padded"] / 2**20)
    csv.add("alice/match_oracle", match["alice"],
            "every served result vs spgemm_1d host oracle, bitwise")
    csv.add("bob/match_oracle", match["bob"],
            "values-jittered twin rides the repack path")
    csv.add("mixed/session_traces", svc.session.stats["traces"],
            "one trace serves both tenants")
    csv.add("mixed/payload_repacks", svc.session.stats["payload_repacks"])

    # --- per-tenant quota: distinct structures cycle through one slot -------
    qsvc = SpGEMMService(policy=ServicePolicy(tenant_quota=1))
    structs = [intify(erdos_renyi(n // 2, n // 2, 4.0, seed=40 + i))
               .astype(np.float32) for i in range(3)]
    for m in structs:
        qres = qsvc.serve([SpGEMMRequest(tenant="carol", a=m, b=m, bs=BS)])
        assert qres[0].ok
    qstats = qsvc.stats()
    csv.add("quota/evictions", qstats["evictions_by_tenant"].get("carol", 0),
            "tenant_quota=1 over 3 distinct structures")
    csv.add("quota/entries_cached", qsvc.cached_entries("carol"))
    return csv


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--json", nargs="?", const=DEFAULT_JSON, default=None,
                    metavar="PATH",
                    help="merge rows into PATH (replacing previous "
                         f"serving_throughput rows; default {DEFAULT_JSON})")
    args = ap.parse_args()
    out_csv = main(scale=args.scale)
    out_csv.emit()
    if args.json is not None:
        merge_json(out_csv, args.json, args.scale)
        print(f"# merged {len(out_csv.entries)} serving_throughput rows "
              f"into {args.json}")
