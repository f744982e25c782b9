"""Bench-trajectory merge semantics of ``benchmarks.run --json``.

A ``--only`` run used to rewrite the trajectory file with just the subset
of rows that ran, destroying every other bench's recorded history (the
exact file ``tools/bench_smoke.sh`` gates on). These tests pin the fixed
behavior: rows merge keyed ``(bench, name)`` and per-run failure counts
accumulate in ``failures_history``.
"""

import json
import types

import pytest

import benchmarks.run as bench_run
from benchmarks.common import Csv


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # main() turns on the persistent compile cache for the whole process;
    # the stub benches compile nothing, and later tests on this worker
    # must not write into the checkout's cache
    monkeypatch.setattr(bench_run, "enable_compile_cache", lambda: None)


def _stub(name, rows, fail=False):
    mod = types.ModuleType(name)

    def main(scale=1):
        if fail:
            raise RuntimeError("boom")
        csv = Csv(name)
        for k, v in rows:
            csv.add(k, v)
        return csv

    mod.main = main
    return mod


def test_only_runs_merge_rows_instead_of_truncating(tmp_path, monkeypatch,
                                                    capsys):
    path = str(tmp_path / "traj.json")
    monkeypatch.setattr(bench_run, "MODULES",
                        [_stub("alpha", [("x", 1.0), ("z", 5.0)]),
                         _stub("beta", [("y", 2.0)])])
    assert bench_run.main(["--only", "alpha", "--json", path]) == 0
    assert bench_run.main(["--only", "beta", "--json", path]) == 0
    data = json.load(open(path))
    assert {r["bench"] for r in data["rows"]} == {"alpha", "beta"}

    # a re-run replaces its own rows by (bench, name) — no duplicates —
    # and every row it did not produce survives untouched
    monkeypatch.setattr(bench_run, "MODULES", [_stub("alpha", [("x", 7.0)])])
    assert bench_run.main(["--only", "alpha", "--json", path]) == 0
    data = json.load(open(path))
    xs = [r for r in data["rows"]
          if r["bench"] == "alpha" and r["name"] == "x"]
    assert len(xs) == 1 and float(xs[0]["value"]) == 7.0
    assert any(r["bench"] == "beta" for r in data["rows"])
    assert any(r["bench"] == "alpha" and r["name"] == "z"
               for r in data["rows"])


def test_failures_history_survives_clean_partial_runs(tmp_path, monkeypatch,
                                                      capsys):
    path = str(tmp_path / "traj.json")
    monkeypatch.setattr(bench_run, "MODULES", [_stub("bad", [], fail=True)])
    assert bench_run.main(["--json", path]) == 1
    monkeypatch.setattr(bench_run, "MODULES", [_stub("good", [("v", 1.0)])])
    assert bench_run.main(["--only", "good", "--json", path]) == 0
    data = json.load(open(path))
    assert data["failures"] == 0                 # the current run was clean
    assert [h["failures"] for h in data["failures_history"]] == [1, 0]
    assert data["failures_history"][1]["only"] == "good"


def test_corrupt_trajectory_file_is_replaced(tmp_path, monkeypatch, capsys):
    path = tmp_path / "traj.json"
    path.write_text("{not json")
    monkeypatch.setattr(bench_run, "MODULES", [_stub("alpha", [("x", 1.0)])])
    assert bench_run.main(["--json", str(path)]) == 0
    data = json.load(open(path))
    assert {r["bench"] for r in data["rows"]} == {"alpha"}

    # the corrupt file is never silently discarded: its bytes survive at
    # <path>.corrupt and the operator is told on stderr
    corrupt = tmp_path / "traj.json.corrupt"
    assert corrupt.read_text() == "{not json"
    err = capsys.readouterr().err
    assert "warning" in err and "traj.json.corrupt" in err


def test_corrupt_preservation_is_idempotent(tmp_path, monkeypatch, capsys):
    """A second corruption overwrites the parked copy rather than crashing
    on an existing ``.corrupt`` file."""
    path = tmp_path / "traj.json"
    monkeypatch.setattr(bench_run, "MODULES", [_stub("alpha", [("x", 1.0)])])
    for payload in ("{not json", "[still not json"):
        path.write_text(payload)
        assert bench_run.main(["--json", str(path)]) == 0
        assert (tmp_path / "traj.json.corrupt").read_text() == payload
