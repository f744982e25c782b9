"""The harness finds every part of a cell by name (CPU, tiny sizes)."""

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import inputs  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_existing_files():
    bm = harness.load_benchmark()
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    for c in bm["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])
        assert (BENCH / "families" / f"{cfg['family']}.py").is_file()
        assert (BENCH / "semirings" / f"{cfg['semiring']}.py").is_file()
    names = {c["name"] for c in bm["configs"]}
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bm["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"])


@pytest.mark.parametrize("config", ["hv15r_like", "road_ny_like"])
def test_shipped_config_inputs_follow_the_seed(config):
    cfg = harness.load_json("configs", config)
    traffic = {"structures": 1, "value_sets": 2}
    a = inputs.generate(cfg, traffic, 2**31 + 7)
    b = inputs.generate(cfg, traffic, 2**31 + 7)
    c = inputs.generate(cfg, traffic, 2**31 + 8)
    assert np.array_equal(a.structures[0].indices, b.structures[0].indices)
    for va, vb, vc in zip(a.values[0], b.values[0], c.values[0]):
        assert np.array_equal(va, vb) and not np.array_equal(va, vc)
    assert not np.array_equal(a.values[0][0], a.values[0][1])


def test_harness_refuses_a_cpu_backend():
    res = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         harness.load_benchmark()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not any(line.startswith("{") for line in res.stdout.splitlines())


def test_dropped_in_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric, each a file
    of its own, run with no edit to the harness."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    for d in ("families", "semirings"):
        (bench / d).symlink_to(BENCH / d)
    for f in ("host_ms.py", "plan_s.py"):
        (bench / "metrics" / f).write_text(
            (BENCH / "metrics" / f).read_text())
    cfg = dict(harness.load_json("configs", "hv15r_like"), name="tiny_band",
               n=512, band=64, degree=4, bs=32)
    (bench / "configs" / "tiny_band.json").write_text(json.dumps(cfg))
    traffic = dict(harness.load_json("traffic", "a2.values"),
                   name="a2.two_values", value_sets=2)
    (bench / "traffic" / "a2.two_values.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "requests_traced.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.requests))\n")
    (bench / "metrics" / "never_there.py").write_text(
        "def read(ctx):\n    return None\n")
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"source": "test", "devices": {
        "cpu": {"flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}))
    bm = {
        "configs": [{"name": "tiny_band", "file": "bench/configs/tiny_band.json",
                     "reduced": cfg["reduced"]}],
        "workloads": [{"name": "tiny.cell", "config": "tiny_band",
                       "traffic": "a2.two_values", "chips": 1}],
        "end_to_end": [{"name": "multiply_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": u} for n, u in (
            ("requests_traced", "1"), ("never_there", "1"),
            ("host_ms", "ms"), ("plan_s", "s"))],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    bm = harness.load_benchmark(tmp_path)
    cell = harness.find_cell(bm, "tiny.cell")
    out = harness.run_cell(bm, cell, 9, 0.3, False, time.perf_counter(),
                           root=tmp_path)
    assert out["correct"] and set(out["metrics"]) == {"multiply_s"}
    out = harness.run_cell(bm, cell, 9, 0.3, True, time.perf_counter(),
                           root=tmp_path, peaks_path=peaks)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["requests_traced"]["value"] == out["attempted"] >= 2
    assert "never_there" not in m
    assert m["host_ms"]["unit"] == "ms" and m["host_ms"]["value"] > 0
    assert m["plan_s"]["value"] > 0
