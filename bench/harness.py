"""The benchmark harness: one cell, one seed, one process.

Everything a cell is made of is found by name, so a later change adds a
cell, a configuration, a traffic mix or a metric as files of its own:

* ``BENCHMARK.json`` lists the cells and the metrics;
* ``bench/configs/<config>.json``: a deployment (structure family and
  sizes, value distribution, semiring, tile size, and the limits of the
  comparison that decides ``correct``);
* ``bench/traffic/<traffic>.json``: the request stream (closed loop, one
  client; structures and value sets it cycles through; algorithm and ring
  width);
* ``bench/families/<family>.py``: a structure generator;
* ``bench/semirings/<semiring>.py``: the plain reference, its comparison
  and its lower-precision control;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

The program under test is entered only through ``SpGEMMService.serve``;
from it the harness reads only the session's counters and the executed
plan's stats.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import inputs as inputs_mod
import tracereduce
import work
from sparse_ref import Mat

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
REQUEST_SPAN, WINDOW_SPAN = "bench.request", "bench.window"
CHECKED_ANSWERS = 8   # answers of the window compared, drawn from the seed
WARMUP_REQUESTS = 2   # served in set-up, before the window


class ChipMissing(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---- discovery ----------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bm: dict, name: str) -> dict:
    for c in bm["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bm['workloads']]}")


def load_json(kind: str, name: str, base: Path = BENCH) -> dict:
    path = Path(base) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {name!r} at {path}")
    return json.loads(path.read_text())


def config_of(bm: dict, cell: dict, root: Path = ROOT) -> dict:
    """The cell's configuration file, as ``BENCHMARK.json`` names it."""
    for c in bm["configs"]:
        if c["name"] == cell["config"]:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {cell['config']!r} in BENCHMARK.json")


def metrics_of(bm: dict, cell: dict, trace: bool) -> List[dict]:
    """The cell's metrics: end-to-end ones, or per-layer ones when traced;
    a metric with ``workloads`` belongs to those cells only."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


# ---- the chip -------------------------------------------------------------------

def require_chips(chips: int) -> None:
    """Refuse anything but ``chips`` TPU chips: no CPU, no interpreter."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise ChipMissing(f"no TPU: JAX backend is {backend!r}")
    if len(jax.devices()) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, JAX sees "
                          f"{len(jax.devices())}")


def enable_compile_cache(path: Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    caching every program however fast it compiled, so that only a cell's
    first run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


# ---- the run --------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    window: Optional[tracereduce.Window]   # the traced window
    plan_stats: dict                       # the warm-up's executed plan
    ops: float                             # work of one multiply
    nbytes: float
    peak: dict                             # published peaks of one chip
    chips: int


def program_matrix(st: inputs_mod.Structure, vals: np.ndarray):
    from repro.core import CSC

    return CSC(st.indptr, st.indices, vals, st.shape)


def reservoir(rng: np.random.Generator, k: int):
    """Algorithm R over a stream: keeps ``k`` items drawn uniformly."""
    kept: list = []
    seen = 0

    def offer(item):
        nonlocal seen
        seen += 1
        if len(kept) < k:
            kept.append(item)
        else:
            j = int(rng.integers(0, seen))
            if j < k:
                kept[j] = item
    return kept, offer


@contextlib.contextmanager
def profiled(trace: bool, log_dir: Optional[Path]):
    if not trace:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the host's own runtime events suffice
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(trace: bool, name: str):
    if not trace:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(bm: dict, cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False,
             cfg: Optional[dict] = None, traffic: Optional[dict] = None,
             service_factory: Optional[Callable] = None,
             peaks_path: Path = work.PEAKS, root: Path = ROOT) -> dict:
    """Set up, drive the timed window, compare, and return the result line
    as a dict (``checks`` last). ``control`` also returns the numbers of
    the lower-precision control on the same requests. Files are found
    under the checkout ``root``; ``cfg``, ``traffic``, ``service_factory``
    and ``peaks_path`` stand in for the named files, the program's service
    and the peaks table in tests."""
    from repro.core.semiring import by_name
    from repro.serve import SpGEMMRequest, SpGEMMService

    base = Path(root) / "bench"
    cfg = cfg if cfg is not None else config_of(bm, cell, root)
    traffic = traffic if traffic is not None else load_json(
        "traffic", cell["traffic"], base)
    if traffic["operation"] != "a_times_a":
        raise ValueError(f"unknown operation {traffic['operation']!r}")
    chips = int(cell["chips"])
    ref = inputs_mod.load_module("semirings", cfg["semiring"], base)
    semiring = by_name(cfg["semiring"])

    t0 = time.perf_counter()
    data = inputs_mod.generate(cfg, traffic, seed, base)
    log(f"inputs: {len(data.structures)} structure(s) of nnz "
        f"{[s.nnz for s in data.structures]}, {len(data.values[0])} value "
        f"set(s) each, in {time.perf_counter() - t0:.3f} s")

    def matrix(i: int):
        s, v = data.request(i)
        return program_matrix(data.structures[s], data.values[s][v])

    def request(i: int):
        m = matrix(i)
        return SpGEMMRequest(
            tenant="bench", a=m, b=m, algorithm=traffic["algorithm"],
            nparts=int(traffic["nparts"]), bs=int(cfg["bs"]),
            semiring=semiring)

    # warm-up: the cold call (plan, compile, first multiply), then one call
    # of the mix's own path (a values-only repack), whose first run is
    # slower than the rest
    svc = (service_factory or SpGEMMService)()
    plan_stats: dict = {}
    for i in range(WARMUP_REQUESTS):
        t0 = time.perf_counter()
        (warm,) = svc.serve([request(i)])
        if not warm.ok:
            raise RuntimeError(f"warm-up request {i} failed: "
                               f"{warm.error!r}")
        plan_stats = plan_stats or dict(warm.call_stats["plan_stats"])
        log(f"warm-up request {i}: {time.perf_counter() - t0:.3f} s")
        del warm
    log(f"plan: {plan_stats}")
    traces_before = svc.session.stats["traces"]
    setup_s = time.perf_counter() - t_start

    kept, offer = reservoir(
        inputs_mod.rng(seed, inputs_mod.SAMPLE_STREAM), CHECKED_ANSWERS)
    latencies: List[float] = []
    attempted = failed = 0
    log_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace \
        else None
    try:
        with profiled(trace, log_dir), span(trace, WINDOW_SPAN):
            t_win = time.perf_counter()
            i = WARMUP_REQUESTS
            while True:
                req = request(i)
                with span(trace, REQUEST_SPAN):
                    t0 = time.perf_counter()
                    (res,) = svc.serve([req])
                    t1 = time.perf_counter()
                attempted += 1
                latencies.append(t1 - t0)
                if res.ok:
                    offer((i, res.value))
                else:
                    failed += 1
                    log(f"request {i} failed: {res.error!r}")
                del req, res
                i += 1
                if t1 - t_win >= seconds:
                    break
        window_s = t1 - t_win
        device = device_info(chips)
        stats = dict(svc.session.stats)
        del svc
        gc.collect()
        window = None
        if trace:
            t0 = time.perf_counter()
            window = tracereduce.Window.of(
                tracereduce.load_xplane(tracereduce.find_xplane(log_dir)),
                REQUEST_SPAN, WINDOW_SPAN)
            log(f"trace read in {time.perf_counter() - t0:.3f} s")
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    log(f"window: {attempted} requests in {window_s:.3f} s, latencies "
        f"{[round(x, 4) for x in latencies]}")

    # ---- correct: every number compared, against its limit ----------------
    t0 = time.perf_counter()
    readings: Dict[str, float] = {k: 0 for k in ref.NUMBERS}
    ctrl: Dict[str, float] = {k: 0 for k in ref.NUMBERS}
    for i, answer in sorted(kept, key=lambda x: x[0]):
        m = matrix(i)
        a = Mat(m.indptr, m.indices, m.data, m.shape)
        for k, v in ref.compare(a, a, answer).items():
            readings[k] = max(readings[k], v)
        if control:
            for k, v in ref.compare(a, a, ref.control(a, a)).items():
                ctrl[k] = max(ctrl[k], v)
    log(f"compared {len(kept)} answers with the reference in "
        f"{time.perf_counter() - t0:.3f} s")
    limits = cfg["checks"]
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in ref.NUMBERS}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["fallbacks"] = {"value": stats["fallbacks"], "limit": 0}
    checks["retraces_in_window"] = {
        "value": stats["traces"] - traces_before, "limit": 0}
    correct = bool(kept) and all(c["value"] <= c["limit"]
                                 for c in checks.values())

    # ---- metrics ----------------------------------------------------------
    if trace:
        # the work of one multiply, over the structures the mix cycles
        mats = [Mat(st.indptr, st.indices, None, st.shape)
                for st in data.structures]
        ctx = Context(window=window, plan_stats=plan_stats,
                      ops=np.mean([work.spgemm_ops(a, a) for a in mats]),
                      nbytes=np.mean([work.multiply_bytes(
                          a, a, work.symbolic_nnz(a, a)) for a in mats]),
                      peak=work.peaks(device["kind"], peaks_path),
                      chips=chips)
        metrics = read_layer_metrics(metrics_of(bm, cell, True), ctx, base)
        busy = window.device_busy_s()
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = window.window_s
    else:
        e2e = {"multiply_s": window_s / attempted,
               "multiply_p95_s": float(np.percentile(latencies, 95)),
               "peak_hbm_gib": device["memory_peak_bytes"] / 2 ** 30,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(bm, cell, False)}
    out = {"correct": correct, "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": window.top_ops(),
                            "idle_gaps": window.idle_gaps()}
    if control:
        out["control"] = {k: {"value": ctrl[k], "limit": limits[k]}
                          for k in ref.NUMBERS}
    out["checks"] = checks
    return out


def read_layer_metrics(specs: List[dict], ctx: Context,
                       base: Path = BENCH) -> dict:
    """Run each per-layer metric's reader; a reader that finds nothing
    returns None and its metric is left out of the line."""
    out = {}
    for m in specs:
        got = inputs_mod.load_module("metrics", m["name"], base).read(ctx)
        if got is None:
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        entry["unit"] = m["unit"]
        out[m["name"]] = entry
    return out
