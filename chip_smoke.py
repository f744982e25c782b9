#!/usr/bin/env python3
"""Smoke test: the SpGEMM serving path, compiled, on a TPU chip.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --chips 4 [--seed N]  # the distributed engines

One chip: A² of an hv15r-like banded matrix (``banded_clustered(131072,
1024, 16)``, about 367k tile products at ``bs=128``) is served through
``SpGEMMService`` -> ``SpGEMMSession.matmul`` -> 1D ring -> Pallas BSR
kernel: tenant A's cold request, tenant A and B together (one coalesced
cache hit), then tenant B's values-jittered variant (the repack path).
Every result must equal the host oracle ``local_spgemm.spgemm`` bitwise;
values are integers in [-4, 4] without zeros, so every sum is exact
whatever the MXU pass count. Then one ``bool_or_and`` and one ``min_plus``
session multiply on a smaller input whose schedule still spans several
kernel launch windows.

``--chips 4`` runs only the distributed engines: the 1D ring at
``nparts=4`` and 2D SUMMA at ``grid=2`` on ``banded_clustered(262144,
1024, 16)``, each against the same oracle, each with its shards on four
distinct chips.

The script runs in one process and starts none. It fails — non-zero exit,
no result line — when JAX finds no TPU, when a kernel would run in the
Pallas interpreter, when any call degrades to another engine or
algorithm, and on any mismatch. Its last stdout line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Times it prints are single smoke readings, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (BOOL_OR_AND, CSC, MIN_PLUS, PLUS_TIMES,  # noqa: E402
                        banded_clustered, spgemm)
from repro.core.session import SpGEMMSession  # noqa: E402
from repro.core.spgemm_1d_device import (build_device_plan,  # noqa: E402
                                         compile_ring, decode_ring_output)
from repro.core.spgemm_2d_device import (build_summa_plan,  # noqa: E402
                                         compile_summa, decode_summa_output)
from repro.kernels.bsr_spgemm.kernel import SCHEDULE_WINDOW  # noqa: E402
from repro.kernels.launch import resolve_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import SpGEMMRequest, SpGEMMService  # noqa: E402

# hv15r-like inputs: band and mean column degree of banded_clustered
BAND, DEGREE, BS = 1024, 16, 128
SERVE_N, SEMIRING_N, FOUR_CHIP_N = 131072, 16384, 262144


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_label() -> str:
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind} x{len(jax.devices())}"


def hv15r_like(n: int, seed: int, band: int = BAND,
               degree: int = DEGREE) -> CSC:
    """The banded, clustered structure with integer values in [-4, 4]."""
    return with_int_values(banded_clustered(n, band, degree, seed=seed),
                           seed)


def with_int_values(a: CSC, seed: int) -> CSC:
    """Same structure as ``a``, values drawn from {±1, ±2, ±3, ±4}."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 5, size=a.nnz) * rng.choice((-1, 1), size=a.nnz)
    return CSC(a.indptr, a.indices, vals.astype(np.float32), a.shape)


def require_equal(c: CSC, ref: CSC, what: str) -> None:
    """Bitwise CSC equality with the host oracle."""
    require(c.shape == ref.shape, f"{what}: shape {c.shape} != {ref.shape}")
    require(np.array_equal(c.indptr, ref.indptr), f"{what}: indptr differs")
    require(np.array_equal(c.indices, ref.indices),
            f"{what}: indices differ")
    require(np.array_equal(c.data, ref.data.astype(np.float32)),
            f"{what}: values differ")


def require_pallas(call: dict, what: str) -> None:
    require(call.get("engine") == "pallas" and call.get("degraded") is False,
            f"{what}: served by engine={call.get('engine')!r} "
            f"degraded={call.get('degraded')!r}")


def windows(nprod: int) -> int:
    """Kernel launches (``pallas_call``\\ s) one schedule of ``nprod``
    products takes."""
    return -(-nprod // SCHEDULE_WINDOW)


def log_plan(what: str, plan_stats: dict, payload_bytes: int) -> None:
    s = plan_stats
    log(f"{what} plan: a_tiles={s['na_max']} b_tiles={s['nb_max']} "
        f"c_tiles={s['nc_max']} nprod={s['nprod_total']} "
        f"nprod_per_device={s['nprod_max']} "
        f"windows={windows(s['nprod_max'])} payload_bytes={payload_bytes}")


def serve_phase(n: int, bs: int, seed: int, band: int = BAND) -> dict:
    """Plus-times A² through the service: cold, coalesced hit, repack."""
    a = hv15r_like(n, seed, band)
    a_j = with_int_values(a, seed + 1)
    svc = SpGEMMService()

    def req(tenant, m):
        return SpGEMMRequest(tenant=tenant, a=m, b=m, algorithm="1d",
                             nparts=1, bs=bs, semiring=PLUS_TIMES)

    (cold,) = svc.serve([req("tenant-a", a)])
    warm = svc.serve([req("tenant-a", a), req("tenant-b", a)])
    (rep,) = svc.serve([req("tenant-b", a_j)])
    for r in (cold, *warm, rep):
        require(r.ok, f"{r.tenant}: request failed: {r.error!r}")
        require_pallas(r.call_stats, r.tenant)
    require(not cold.cache_hit, "cold request was a cache hit")
    require(all(r.cache_hit and r.coalesced for r in warm),
            "same-structure requests did not coalesce into a hit")
    require(rep.call_stats["repacked"], "jittered request was not repacked")
    stats = svc.session.stats
    require(stats["fallbacks"] == 0, f"fallbacks={stats['fallbacks']}")

    t0 = time.perf_counter()
    ref, ref_j = spgemm(a, a), spgemm(a_j, a_j)
    oracle_s = time.perf_counter() - t0
    require_equal(cold.value, ref, "cold")
    for r in warm:
        require_equal(r.value, ref, f"warm {r.tenant}")
    require_equal(rep.value, ref_j, "repacked")

    info = dict(plan_stats=cold.call_stats["plan_stats"],
                payload_bytes=svc.session.cached_bytes(),
                cold_s=cold.latency_s, warm_s=warm[0].latency_s,
                repack_s=rep.latency_s, oracle_s=oracle_s,
                nnz_c=ref.nnz, traces=stats["traces"])
    log_plan(f"serve A^2 n={n} nnz={a.nnz}", info["plan_stats"],
             info["payload_bytes"])
    log(f"serve C nnz={ref.nnz}: 4 results match the host oracle bitwise; "
        f"traces={stats['traces']} fallbacks={stats['fallbacks']}")
    label = device_label()
    log(f"serve times on {label} (smoke readings, not a benchmark): "
        f"cold (plan+compile+run+decode) {info['cold_s']:.3f} s, "
        f"warm coalesced hit {info['warm_s']:.3f} s, "
        f"repack {info['repack_s']:.3f} s; host oracle {oracle_s:.3f} s")
    return info


def semiring_phase(n: int, bs: int, seed: int, band: int = BAND) -> dict:
    """One bool_or_and and one min_plus session multiply vs the oracle."""
    a = hv15r_like(n, seed + 2, band)
    session = SpGEMMSession()
    info = {}
    for sr in (BOOL_OR_AND, MIN_PLUS):
        t0 = time.perf_counter()
        c = session.matmul(a, a, algorithm="1d", nparts=1, bs=bs,
                           semiring=sr)
        took = time.perf_counter() - t0
        require_pallas(session.last_call, sr.name)
        require_equal(c, spgemm(a, a, sr), sr.name)
        info[sr.name] = dict(plan_stats=session.last_call["plan_stats"],
                             cold_s=took)
        log_plan(f"{sr.name} A^2 n={n}", info[sr.name]["plan_stats"],
                 session.cached_bytes())
        log(f"{sr.name}: matches the host oracle bitwise; cold call on "
            f"{device_label()} {took:.3f} s (smoke reading)")
    require(session.stats["fallbacks"] == 0,
            f"fallbacks={session.stats['fallbacks']}")
    return info


def _placed(args, devices, what: str) -> None:
    for x in args:
        held = [s.device for s in x.addressable_shards]
        require(len(held) == len(devices) and set(held) == set(devices),
                f"{what}: shards on {held}, expected one on each of "
                f"{devices}")


def four_chip_phase(n: int, bs: int, seed: int, band: int = BAND) -> dict:
    """1D ring (nparts=4) and 2D SUMMA (grid=2), one shard per device."""
    devices = jax.devices()[:4]
    a = hv15r_like(n, seed, band)
    t0 = time.perf_counter()
    ref = spgemm(a, a)
    log(f"four-chip input n={n} nnz={a.nnz}; host oracle C nnz={ref.nnz} "
        f"in {time.perf_counter() - t0:.3f} s")
    info = {}
    engines = (
        ("1d ring nparts=4",
         lambda: build_device_plan(a, a, nparts=4, bs=bs),
         compile_ring, decode_ring_output),
        ("2d summa grid=2",
         lambda: build_summa_plan(a, a, grid=2, bs=bs),
         compile_summa, decode_summa_output),
    )
    for what, plan_fn, compile_fn, decode in engines:
        plan = plan_fn()
        fn, args = compile_fn(plan)
        _placed(args, devices, what)
        times = []
        for _ in range(2):   # cold (compile + run), then warm
            t0 = time.perf_counter()
            out = fn(*args).block_until_ready()
            times.append(time.perf_counter() - t0)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        require_equal(decode(plan, np.asarray(out)), ref, what)
        info[what] = dict(plan_stats=plan.stats, cold_s=times[0],
                          warm_s=times[1], bytes_in_use=in_use)
        log_plan(what, plan.stats, sum(int(x.nbytes) for x in args))
        log(f"{what}: matches the host oracle bitwise; shards on "
            f"{[d.id for d in devices]}; bytes_in_use per device {in_use}")
        log(f"{what} on {device_label()} (smoke readings): cold "
            f"{times[0]:.3f} s, warm {times[1]:.3f} s (device run, no "
            "decode)")
        del fn, args, out
    return info


def require_chip(chips: int) -> None:
    """Refuse to run anywhere but on ``chips`` TPU chips, compiled."""
    backend = jax.default_backend()
    require(backend == "tpu", f"no TPU: JAX backend is {backend!r}")
    require(resolve_interpret(None) is False,
            "Pallas kernels would run in the interpreter")
    require(len(jax.devices()) >= chips,
            f"need {chips} chips, JAX sees {len(jax.devices())}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated input")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed engines on 4 chips")
    args = ap.parse_args(argv)
    require_chip(args.chips)
    log(f"compile cache: {enable_compile_cache()}")

    if args.chips == 4:
        info = four_chip_phase(FOUR_CHIP_N, BS, args.seed)
        for what, v in info.items():
            require(all(b is not None and b > 0 for b in v["bytes_in_use"]),
                    f"{what}: a device holds nothing: {v['bytes_in_use']}")
    else:
        serve_phase(SERVE_N, BS, args.seed)
        semiring_phase(SEMIRING_N, BS, args.seed)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
