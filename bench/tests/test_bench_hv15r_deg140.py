"""The ``hv15r_deg140`` configuration and its cell's new reader (CPU).

HV15R's published 140.33 entries per column, plus-times over float32: the
inputs follow the seed, the structure at full size has the published
degree, a sound run at a tiny size is ``correct``, an answer one precision
down or one entry short is not, and ``decode_assemble_ms`` reads the
assembly span alone."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import inputs  # noqa: E402
import work  # noqa: E402
from sparse_ref import Mat, to_bf16  # noqa: E402
from tracereduce import Event, Trace, Window  # noqa: E402

CONFIG = "hv15r_deg140"
CELL = "hv15r.a2.values"
PUBLISHED_DEGREE = 140.33
# the configuration at a size the interpreter runs in seconds
TINY = dict(n=512, band=64, bs=32)
FIXTURE = BENCH / "tests" / "fixtures" / "road_minplus_two_requests.trace.json"
US = 1e3   # ns


def config(**over):
    return dict(harness.load_json("configs", CONFIG), **over)


def test_inputs_follow_the_seed():
    cfg = config(**TINY)
    traffic = {"structures": 1, "value_sets": 2}
    a = inputs.generate(cfg, traffic, 2**31 + 7)
    b = inputs.generate(cfg, traffic, 2**31 + 7)
    c = inputs.generate(cfg, traffic, 2**31 + 8)
    assert np.array_equal(a.structures[0].indices, b.structures[0].indices)
    assert np.array_equal(a.structures[0].indices, c.structures[0].indices)
    for va, vb, vc in zip(a.values[0], b.values[0], c.values[0]):
        assert va.dtype == np.float32
        assert np.array_equal(va, vb) and not np.array_equal(va, vc)
    assert not np.array_equal(a.values[0][0], a.values[0][1])


def test_full_size_structure_has_the_published_degree():
    """Structure only: the draws, after repeated positions merge, leave
    HV15R's entries per column within 1 %."""
    cfg = config()
    st = inputs.generate(cfg, {"structures": 1, "value_sets": 0}, 1) \
        .structures[0]
    assert st.shape == (cfg["n"], cfg["n"])
    degree = st.nnz / cfg["n"]
    assert abs(degree - PUBLISHED_DEGREE) <= 0.01 * PUBLISHED_DEGREE, degree
    # only the scale is cut: the degree and the band are as assumed
    assert cfg["reduced"] == ["n"]


def test_the_cell_is_in_the_benchmark():
    bm = harness.load_benchmark()
    cell = harness.find_cell(bm, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "a2.values", 1)
    assert harness.config_of(bm, cell) == config()
    traced = {m["name"] for m in harness.metrics_of(bm, cell, True)}
    assert {"kernel_ms", "bsr_spgemm_roofline", "decode_ms",
            "decode_assemble_ms"} <= traced


def test_sound_run_is_correct():
    bm = harness.load_benchmark()
    out = harness.run_cell(bm, harness.find_cell(bm, CELL), 2**31 + 11,
                           0.3, False, time.perf_counter(),
                           cfg=config(**TINY))
    assert out["correct"], out["checks"]
    assert out["checks"]["extra_entries"]["value"] == 0
    assert out["attempted"] >= 2 and out["failed"] == 0


def exact(a):
    """The float32-rounded float64 product: a sound answer."""
    m = sp.csc_matrix((a.data.astype(np.float64), a.indices, a.indptr),
                      shape=a.shape)
    c = (m @ m).tocsc()
    c.sort_indices()
    return Mat(c.indptr, c.indices, c.data.astype(np.float32), c.shape)


def rounded(a):
    c = exact(a)
    return c._replace(data=to_bf16(c.data).astype(np.float32))


def control(a):
    return inputs.load_module("semirings", "plus_times").control(a, a)


def one_short(a):
    c = exact(a)
    j = len(c.data) // 2
    col = np.searchsorted(c.indptr, j, side="right") - 1
    indptr = c.indptr.copy()
    indptr[col + 1:] -= 1
    return Mat(indptr, np.delete(c.indices, j), np.delete(c.data, j),
               c.shape)


@pytest.mark.parametrize("answer, sound", [
    (exact, True), (rounded, False), (control, False), (one_short, False)],
    ids=lambda x: getattr(x, "__name__", None))
def test_comparison_limits(answer, sound):
    """A float32 answer passes; one rounded through bfloat16 (its values,
    or its operands as one MXU pass would) or missing one entry fails."""
    cfg = config(**TINY)
    data = inputs.generate(cfg, {"structures": 1, "value_sets": 1}, 5)
    st = data.structures[0]
    a = Mat(st.indptr, st.indices, data.values[0][0], st.shape)
    ref = inputs.load_module("semirings", "plus_times")
    numbers = ref.compare(a, a, answer(a))
    passed = all(v <= cfg["checks"][k] for k, v in numbers.items())
    assert passed is sound, numbers


def ev(name, lo, hi):
    return Event(name, lo * US, hi * US)


def read(window):
    ctx = harness.Context(window=window, plan_stats={}, ops=2e7, nbytes=5e7,
                          peak=work.peaks("TPU v5 lite"), chips=1)
    return inputs.load_module("metrics", "decode_assemble_ms").read(ctx)


def window_of(host):
    dev = [ev("%bsr_spgemm_pallas.1 = f32[2] custom-call()", 12, 20)]
    return Window.of(Trace({"/device:TPU:0": dev}, host),
                     harness.REQUEST_SPAN, harness.WINDOW_SPAN)


def test_decode_assemble_ms_reads_the_assemble_span_alone():
    """Two requests: the prune spans are left out, the assembly spans are
    clipped to their requests, and one after the last request is not
    counted."""
    host = [ev("bench.window", 0, 100),
            ev("bench.request", 0, 50),
            ev("spgemm.execute.fetch", 10, 25),
            ev("spgemm.decode.prune", 25, 30),
            ev("spgemm.decode.assemble", 30, 42),
            ev("bench.request", 50, 100),
            ev("spgemm.decode.prune", 60, 75),
            ev("spgemm.decode.assemble", 75, 105),
            ev("spgemm.decode.assemble", 110, 120)]
    assert read(window_of(host)) == pytest.approx((12 + 25) / 2 / 1e3)


def test_decode_assemble_ms_reads_nothing_without_the_span():
    host = [ev("bench.window", 0, 100), ev("bench.request", 0, 100),
            ev("spgemm.decode.prune", 25, 30)]
    assert read(window_of(host)) is None
    fixture = Window.of(Trace.from_json(FIXTURE.read_text()),
                        harness.REQUEST_SPAN, harness.WINDOW_SPAN)
    assert read(fixture) is None
    assert read(None) is None
