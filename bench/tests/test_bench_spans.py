"""The readers of the program's own spans (CPU, traces built by hand).

A served multiply's host path is split by the program's spans into
ingress, repack, device-to-host transfer and decode; what no span covers
is ``untraced_host_ms``. On a trace whose spans lie as the program lays
them, the phases, the host part of the dispatch and ``untraced_host_ms``
add up to ``host_ms``. On a trace of a program that records no span, as
the recorded fixture is, every reader reads nothing."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import inputs  # noqa: E402
import work  # noqa: E402
from tracereduce import Event, Trace, Window  # noqa: E402

FIXTURE = BENCH / "tests" / "fixtures" / "road_minplus_two_requests.trace.json"
READERS = ("ingress_ms", "repack_ms", "d2h_ms", "decode_ms",
           "untraced_host_ms")
US = 1e3   # ns


def ctx(w):
    return harness.Context(window=w, plan_stats={}, ops=2e7, nbytes=5e7,
                           peak=work.peaks("TPU v5 lite"), chips=1)


def read(name, w):
    return inputs.load_module("metrics", name).read(ctx(w))


def ev(name, lo, hi):
    return Event(name, lo * US, hi * US)


def two_requests(extra_host=()):
    """Two served multiplies, in us. The first is a values-only repack
    whose validation span straddles the window's start and whose blockize
    was retried (and recorded twice); its kernel starts inside the
    dispatch. The second is a cache hit with an untraced gap before its
    decode. A decode span after the last request lies in no request."""
    dev = [ev("%bsr_spgemm_pallas.1 = f32[2] custom-call()", 51, 70),
           ev("%bsr_spgemm_pallas.2 = f32[2] custom-call()", 114, 140),
           ev("%slice_bitcast_fusion = f32[2] fusion()", 140, 141)]
    host = [ev("bench.window", 20, 200),
            ev("bench.request", 20, 100),
            ev("spgemm.session.validate", 5, 25),
            ev("spgemm.session.fingerprint", 25, 30),
            ev("spgemm.repack.blockize", 30, 40),
            ev("spgemm.repack.blockize", 30, 40),
            ev("spgemm.repack.blockize", 40, 45),
            ev("spgemm.repack.h2d", 45, 50),
            ev("spgemm.execute.dispatch", 50, 52),
            ev("spgemm.execute.fetch", 52, 80),
            ev("TransferFromDevice", 72, 79),
            ev("spgemm.decode.prune", 80, 90),
            ev("spgemm.decode.assemble", 90, 95),
            ev("bench.request", 100, 190),
            ev("spgemm.service.coalesce", 100, 104),
            ev("spgemm.session.validate", 104, 110),
            ev("spgemm.session.fingerprint", 110, 112),
            ev("spgemm.execute.dispatch", 112, 113),
            ev("spgemm.execute.fetch", 113, 150),
            ev("spgemm.decode.prune", 160, 175),
            ev("spgemm.decode.assemble", 175, 185),
            ev("spgemm.decode.prune", 192, 198), *extra_host]
    return Window.of(Trace({"/device:TPU:0": dev}, host),
                     harness.REQUEST_SPAN, harness.WINDOW_SPAN)


@pytest.mark.parametrize("name, per_request_us", [
    # validate counts from the request's start only; coalesce, validate
    # and fingerprint in the second
    ("ingress_ms", (5 + 5, 4 + 6 + 2)),
    # the retried blockize counts once: [30, 45), then h2d [45, 50)
    ("repack_ms", (15 + 5, 0)),
    # the fetch less the kernel's time in it
    ("d2h_ms", (28 - 18, 37 - 27)),
    # the decode after the last request lies in no request
    ("decode_ms", (10 + 5, 15 + 10)),
    # [95, 100) in the first; [150, 160) and [185, 190) in the second
    ("untraced_host_ms", (5, 10 + 5)),
])
def test_reader_arithmetic(name, per_request_us):
    assert read(name, two_requests()) == pytest.approx(
        sum(per_request_us) / 2 / 1e3)


def test_phases_add_up_to_host_ms():
    w = two_requests()
    phases = sum(read(n, w) for n in READERS)
    dispatch_host_us = ((51 - 50) + (113 - 112)) / 2
    assert read("host_ms", w) == pytest.approx((61 + 63) / 2 / 1e3)
    assert phases + dispatch_host_us / 1e3 == pytest.approx(
        read("host_ms", w))
    # the kernel is still read beside the spans
    assert read("kernel_ms", w) == pytest.approx((19 + 26) / 2 / 1e3)


def test_a_missing_span_shows_as_untraced_time():
    w = two_requests()
    hidden = Window.of(
        Trace(w.trace.devices,
              [e for e in w.trace.host if e.name != "spgemm.decode.prune"]),
        harness.REQUEST_SPAN, harness.WINDOW_SPAN)
    assert read("untraced_host_ms", hidden) == pytest.approx(
        read("untraced_host_ms", w) + (10 + 15) / 2 / 1e3)
    assert read("decode_ms", hidden) == pytest.approx((5 + 10) / 2 / 1e3)


def test_idle_gaps_are_named_by_program_spans():
    gaps = two_requests().idle_gaps()
    assert gaps and all(n.startswith("bench.request / spgemm.")
                        for n, _ in gaps[:2])


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_without_program_spans(name):
    fixture = Window.of(Trace.from_json(FIXTURE.read_text()),
                        harness.REQUEST_SPAN, harness.WINDOW_SPAN)
    assert read(name, fixture) is None
    assert read(name, None) is None
