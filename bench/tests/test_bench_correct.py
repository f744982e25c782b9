"""The comparison that decides ``correct``: the references, the
lower-precision control, and a run of the harness (chip check skipped) at a
tiny size on the CPU, sound and with the timed path broken underneath."""

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import inputs  # noqa: E402
from sparse_ref import Mat  # noqa: E402

# the shipped configurations at a size the interpreter runs in seconds
TINY = {"hv15r_like": dict(n=512, band=64, degree=4, bs=32),
        "road_ny_like": dict(side=24, bs=32)}


def tiny(config, traffic="a2.values", chips=1):
    """A cell of ``config`` under ``traffic``, and the configuration cut to
    a tiny size."""
    cell = {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": chips}
    cfg = dict(harness.load_json("configs", config), **TINY[config])
    return harness.load_benchmark(), cell, cfg


def operand(config, seed=5):
    bm, cell, cfg = tiny(config)
    traffic = harness.load_json("traffic", cell["traffic"])
    data = inputs.generate(cfg, traffic, seed)
    st = data.structures[0]
    return cfg, Mat(st.indptr, st.indices, data.values[0][0], st.shape)


def dense_product(a, semiring):
    d = np.full(a.shape, np.inf if semiring == "min_plus" else 0.0)
    cols = np.repeat(np.arange(a.shape[1]), np.diff(a.indptr))
    d[a.indices, cols] = a.data
    if semiring == "min_plus":
        c = np.min(d[:, :, None] + d[None, :, :], axis=1)
        present = np.isfinite(c)
    else:
        c = d @ d
        present = ((d != 0).astype(int) @ (d != 0).astype(int)) > 0
    rows, cc = np.nonzero(present.T)
    indptr = np.zeros(a.shape[1] + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    return Mat(np.cumsum(indptr), cc, c.T[rows, cc].astype(np.float32),
               a.shape)


def passes(cfg, numbers):
    return all(v <= cfg["checks"][k] for k, v in numbers.items())


@pytest.mark.parametrize("config", sorted(TINY))
def test_reference_accepts_a_float32_answer(config):
    cfg, a = operand(config)
    ref = inputs.load_module("semirings", cfg["semiring"])
    assert passes(cfg, ref.compare(a, a, dense_product(a, cfg["semiring"])))


@pytest.mark.parametrize("config", sorted(TINY))
def test_control_fails_the_comparison(config):
    """The reference one precision down (bfloat16) in the program's place
    must come out not correct."""
    cfg, a = operand(config)
    ref = inputs.load_module("semirings", cfg["semiring"])
    numbers = ref.compare(a, a, ref.control(a, a))
    assert not passes(cfg, numbers), numbers


def run(config, seconds=0.3, seed=2**31 + 11):
    bm, cell, cfg = tiny(config)
    return harness.run_cell(bm, cell, seed, seconds, False,
                            time.perf_counter(), cfg=cfg)


@pytest.mark.parametrize("config", sorted(TINY))
def test_sound_run_is_correct(config):
    out = run(config)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"multiply_s", "multiply_p95_s",
                                   "peak_hbm_gib", "setup_s"}


def _stale_repack(monkeypatch):
    # a repack that leaves the device payloads as they were
    import repro.core.spgemm_1d_device as ring
    monkeypatch.setattr(ring, "repack_ring_payloads",
                        lambda plan, a=None, b=None: (None, None))


def _altered_answer(monkeypatch):
    # one value of each answer altered where the answer is decoded
    import repro.core.spgemm_1d_device as ring
    orig = ring.decode_ring_output

    def decode(plan, out):
        c = orig(plan, out)
        mid = len(c.data) // 2
        c.data[mid] = c.data[mid] * np.float32(1.001) + np.float32(0.01)
        return c
    monkeypatch.setattr(ring, "decode_ring_output", decode)


def _half_left_out(monkeypatch):
    # half of the output tiles never reach the answer
    import repro.core.spgemm_1d_device as ring
    orig = ring.decode_tiles

    def decode(out, c_rows, c_cols, c_counts, *args, **kw):
        return orig(out, c_rows, c_cols, np.asarray(c_counts) // 2,
                    *args, **kw)
    monkeypatch.setattr(ring, "decode_tiles", decode)


@pytest.mark.parametrize("config", sorted(TINY))
@pytest.mark.parametrize("fault", [_stale_repack, _altered_answer,
                                   _half_left_out])
def test_broken_timed_path_is_not_correct(config, fault, monkeypatch):
    fault(monkeypatch)
    out = run(config)
    assert not out["correct"], out["checks"]


RING4 = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax, numpy as np
    import harness
    if {broken}:
        # the ring's exchange left out: every device keeps its own tiles
        jax.lax.ppermute = lambda x, axis_name, perm: x
    bm = harness.load_benchmark()
    cell = {{"name": "ring4", "config": {config!r},
             "traffic": "a2.values.ring4", "chips": 4}}
    cfg = dict(harness.load_json("configs", {config!r}), **{tiny!r})
    out = harness.run_cell(bm, cell, 2**31 + 3, 0.3, False,
                           time.perf_counter(), cfg=cfg)
    print(json.dumps({{"correct": out["correct"],
                       "checks": out["checks"]}}))
""")


# big enough that every one of the four ring parts fetches tiles
TINY_RING4 = {"hv15r_like": dict(n=1024, band=256, degree=4, bs=32),
              "road_ny_like": dict(side=40, bs=32)}


@pytest.mark.parametrize("config", sorted(TINY_RING4))
@pytest.mark.parametrize("broken", [False, True])
def test_ring4_exchange_left_out_is_not_correct(config, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RING4.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                        broken=broken, config=config,
                        tiny=TINY_RING4[config])
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out
