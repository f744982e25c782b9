#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, refuses to run without a TPU (or
with fewer chips than the cell asks for), builds the cell's inputs from the
seed, warms up with one served multiply, drives the timed window of served
multiplies, compares what the window returned with the plain reference,
and prints one JSON line last on stdout: the end-to-end metrics with
``--trace 0``, the per-layer metrics read from a profiler trace of the
window with ``--trace 1``. Every number compared is printed beside its
limit, last on stderr and last in the JSON line (``checks``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also report the lower-precision control's "
                         "numbers on the window's requests")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bm = harness.load_benchmark()
    cell = harness.find_cell(bm, args.workload)
    try:
        harness.require_chips(int(cell["chips"]))
    except harness.ChipMissing as e:
        harness.log(f"refused: {e}")
        return 2
    harness.enable_compile_cache()
    out = harness.run_cell(bm, cell, args.seed, args.seconds,
                           bool(args.trace), T_START, control=args.control)
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
