"""Run every paper-figure benchmark; print ``bench,name,value,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--scale N] [--only SUBSTR]
                                          [--json [PATH]]

``--json`` additionally writes the collected rows (raw values, plus
planner wall-time and padded/exact ratios from ``device_ring``) to
``BENCH_paper_figs.json`` — the recorded bench trajectory that
``tools/bench_smoke.sh`` checks for perf regressions.

The JSON write is a *merge*, keyed ``(bench, name)``: a ``--only`` run
updates just its own rows and leaves every other bench's recorded
trajectory in place (it used to truncate the file to the subset that ran,
destroying the trajectory the smoke script gates on). Per-run failure
counts append to ``failures_history`` so a clean partial run can't erase
the record of an earlier failing one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import (cv_mema, device_compare, device_ring, fault_injection,
               fig04_permutation, fig05_comm_volume, fig06_block_fetch,
               fig07_config_sweep, fig08_breakdown, fig09_strong_scaling,
               fig10_rta, fig12_outer_product, fig13_bc, moe_dispatch,
               session_amortization, serving_throughput)

MODULES = [
    fig04_permutation, fig05_comm_volume, fig06_block_fetch,
    fig07_config_sweep, fig08_breakdown, fig09_strong_scaling,
    fig10_rta, fig12_outer_product, fig13_bc, cv_mema, moe_dispatch,
    device_ring, device_compare, session_amortization, fault_injection,
    serving_throughput,
]

DEFAULT_JSON = "BENCH_paper_figs.json"


def merge_trajectory(path: str, entries: list, scale: int, failures: int,
                     only) -> dict:
    """Merge this run's rows into the trajectory file at ``path``.

    Rows are keyed ``(bench, name)``: new rows replace same-key old ones,
    every other recorded row survives. ``failures`` for the current run is
    kept at the top level (so exit-status consumers see it) and also
    appended to ``failures_history`` with the run's scope.
    """
    data = dict(scale=scale, failures=0, rows=[])
    if os.path.exists(path):
        try:
            with open(path) as fh:
                data = json.load(fh)
        except (json.JSONDecodeError, OSError) as e:
            # a trajectory is history; never silently destroy it. Park the
            # unreadable file next to the fresh one and say so — if even
            # the rename fails, crash rather than overwrite.
            corrupt = path + ".corrupt"
            print(f"# warning: trajectory {path} is unreadable "
                  f"({type(e).__name__}: {e}); preserving it as {corrupt} "
                  f"and starting fresh", file=sys.stderr)
            os.replace(path, corrupt)
            data = dict(scale=scale, failures=0, rows=[])
    merged = {(r.get("bench"), r.get("name")): r
              for r in data.get("rows", []) if isinstance(r, dict)}
    for r in entries:
        merged[(r.get("bench"), r.get("name"))] = r
    data["rows"] = list(merged.values())
    data["scale"] = scale if only is None else data.get("scale", scale)
    data["failures"] = failures
    history = data.get("failures_history")
    if not isinstance(history, list):
        history = []
    history.append(dict(only=only, scale=scale, failures=failures))
    data["failures_history"] = history
    with open(path, "w") as fh:
        json.dump(data, fh, indent=1)
    return data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--only", type=str, default=None,
                    help="run only modules whose name contains SUBSTR")
    ap.add_argument("--json", nargs="?", const=DEFAULT_JSON, default=None,
                    metavar="PATH",
                    help=f"also write rows as JSON (default {DEFAULT_JSON})")
    args = ap.parse_args(argv)
    enable_compile_cache()

    modules = [m for m in MODULES
               if args.only is None or args.only in m.__name__]
    if not modules:
        print(f"# no benchmark matches --only {args.only!r}", file=sys.stderr)
        return 1

    print("bench,name,value,derived")
    entries = []
    failures = 0
    for mod in modules:
        t0 = time.perf_counter()
        try:
            csv = mod.main(scale=args.scale)
            csv.emit()
            entries.extend(csv.entries)
            print(f"# {mod.__name__}: ok "
                  f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"# {mod.__name__}: FAILED", file=sys.stderr)

    if args.json is not None:
        data = merge_trajectory(args.json, entries, args.scale, failures,
                                args.only)
        print(f"# merged {len(entries)} rows into {args.json} "
              f"({len(data['rows'])} total)", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
