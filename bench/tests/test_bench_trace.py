"""The reduction from a profiler trace to per-layer metrics (CPU).

The fixture is a trace of two served min-plus multiplies of the
``road_ny_like`` configuration, recorded on one TPU v5e by the harness and
flattened by ``tracereduce.load_xplane``: 40 op events on the device, the
harness's spans and the runtime's events on the host."""

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import inputs  # noqa: E402
import tracereduce as tr  # noqa: E402
import work  # noqa: E402
from tracereduce import Event, Trace, Window  # noqa: E402

FIXTURE = BENCH / "tests" / "fixtures" / "road_minplus_two_requests.trace.json"


@pytest.fixture(scope="module")
def window():
    return Window.of(Trace.from_json(FIXTURE.read_text()),
                     harness.REQUEST_SPAN, harness.WINDOW_SPAN)


def ctx(w, ops=2 * 10**7, nbytes=5 * 10**7):
    return harness.Context(window=w, plan_stats={},
                           ops=ops, nbytes=nbytes,
                           peak=work.peaks("TPU v5 lite"), chips=1)


def read(name, c):
    return inputs.load_module("metrics", name).read(c)


def test_recorded_trace_reduces_to_known_numbers(window):
    assert len(window.requests) == 2
    assert window.window_s == pytest.approx(16.692812451)
    assert window.device_busy_s() == [pytest.approx(0.292933814)]
    # the kernel's three launches per multiply, named by their own op name
    # (an operand named after the kernel must not count)
    assert window.per_device_sum_s(tr.KERNEL_MATCH) == [
        pytest.approx(0.285362017)]
    assert window.per_device_sum_s(tr.COLLECTIVE_MATCH) == [0.0]
    assert window.host_only_s() == [pytest.approx(8.763276084),
                                    pytest.approx(7.636499063)]
    assert window.top_ops()[0] == ["bsr_spgemm_pallas",
                                   pytest.approx(0.285362017)]
    gaps = window.idle_gaps()
    assert len(gaps) == 3 and all(n.startswith("bench.request / ")
                                  for n, _ in gaps)
    assert sum(s for _, s in gaps) + 0.292933814 == pytest.approx(
        window.window_s, abs=1e-6)


def test_readers_on_the_recorded_trace(window):
    c = ctx(window)
    assert read("kernel_ms", c) == pytest.approx(285.362017 / 2)
    assert read("device_idle_pct", c) == pytest.approx(
        100 * (1 - 0.292933814 / 16.692812451))
    assert read("host_ms", c) == pytest.approx(
        1e3 * (8.763276084 + 7.636499063) / 2)
    roof = read("bsr_spgemm_roofline", c)
    assert roof["bound"] == "memory"
    assert roof["value"] == pytest.approx(
        100 * (5e7 / 819e9) / (0.285362017 / 2))
    assert read("collective_ms", c) is None     # one chip: no collective
    assert read("kernel_ms", ctx(None)) is None


def test_json_round_trip(window):
    t = window.trace
    assert Trace.from_json(t.to_json()) == t


def test_two_devices_overlaps_and_collectives():
    us = 1e3
    dev0 = [Event("%bsr_spgemm_pallas.1 = f32[2] custom-call()", 10 * us,
                  30 * us),
            Event("%collective-permute-start.2 = f32[2] "
                  "collective-permute-start(%bsr_spgemm_pallas.1)",
                  25 * us, 40 * us),
            Event("%fusion.3 = f32[2] fusion(%bsr_spgemm_pallas.1)",
                  60 * us, 70 * us)]
    dev1 = [Event("%bsr_spgemm_pallas.1 = f32[2] custom-call()", 15 * us,
                  55 * us)]
    host = [Event("bench.window", 0, 100 * us),
            Event("bench.request", 0, 50 * us),
            Event("bench.request", 50 * us, 100 * us),
            Event("TransferFromDevice", 72 * us, 90 * us)]
    w = Window.of(Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                        host), "bench.request", "bench.window")
    assert w.device_busy_s() == [pytest.approx(40e-6), pytest.approx(40e-6)]
    assert w.per_device_sum_s(tr.KERNEL_MATCH) == [pytest.approx(20e-6),
                                                   pytest.approx(40e-6)]
    assert w.per_device_sum_s(tr.COLLECTIVE_MATCH) == [
        pytest.approx(15e-6), 0.0]
    # no device busy in [0, 10) and [55, 60), [70, 100)
    assert w.host_only_s() == [pytest.approx(10e-6), pytest.approx(35e-6)]
    c = ctx(w)
    assert read("kernel_ms", c) == pytest.approx(40e-3 / 2)
    assert read("collective_ms", c) == pytest.approx(15e-3 / 2)
    assert read("device_idle_pct", c) == pytest.approx(60.0)
    gaps = w.idle_gaps()
    assert gaps[0] == ["bench.request / TransferFromDevice (60%)",
                       pytest.approx(30e-6)]
    assert [s for _, s in gaps] == [pytest.approx(x) for x in
                                    (30e-6, 20e-6, 10e-6)]


def test_loads_an_xplane_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with harness.profiled(True, tmp_path):
        with harness.span(True, harness.WINDOW_SPAN):
            for _ in range(2):
                with harness.span(True, harness.REQUEST_SPAN):
                    f(x).block_until_ready()
                    time.sleep(0.01)
    t = tr.load_xplane(tr.find_xplane(tmp_path))
    w = Window.of(t, harness.REQUEST_SPAN, harness.WINDOW_SPAN)
    assert len(w.requests) == 2 and w.window_s >= 0.02
    assert t.devices == {}          # the CPU backend has no TPU plane
    assert all(s >= 0.01 for s in w.host_only_s())
