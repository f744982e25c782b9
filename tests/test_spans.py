"""The served multiply's host path records named profiler spans.

A cold call and then a values-only call go through
``SpGEMMService.serve`` under the profiler (CPU, Pallas interpreter, a
tiny structure). The recorded ``.xplane.pb`` must hold every span of
``repro.runtime.spans.SPANS`` on the host plane, flat on the warm path,
with counters that equal the sizes they name; the answers are the same
with and without the profiler attached.
"""

import ast
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import MIN_PLUS, banded_clustered
from repro.runtime.spans import SPANS
from repro.serve import SpGEMMRequest, SpGEMMService

SRC = Path(__file__).resolve().parents[1] / "src"
BS = 16


def _matrix(n=96, seed=3, shift=0.0):
    a = banded_clustered(n, 8, 4.0, seed=seed)
    a.data[:] = 1 + (np.arange(a.nnz) % 7) + shift
    return a.astype(np.float32)


def _request(a):
    return SpGEMMRequest(tenant="t", a=a, b=a, semiring=MIN_PLUS, bs=BS)


def _record(log_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _host_spans(log_dir):
    """(name, start_ns, end_ns, counters) of every ``spgemm.`` event on
    the host plane, in start order."""
    (path,) = sorted(Path(log_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spgemm."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def _same(x, y):
    assert x.shape == y.shape
    for f in ("indptr", "indices", "data"):
        assert np.array_equal(getattr(x, f), getattr(y, f))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A cold call, then a values-only call, recorded; the answers of the
    same two calls on a service with no profiler attached."""
    cold, warm = _matrix(), _matrix(shift=0.5)
    log_dir = tmp_path_factory.mktemp("trace")
    svc = SpGEMMService()

    def both():
        (r0,) = svc.serve([_request(cold)])
        (r1,) = svc.serve([_request(warm)])
        return r0, r1

    traced = _record(log_dir, both)
    plain_svc = SpGEMMService()
    plain = [plain_svc.serve([_request(m)])[0] for m in (cold, warm)]
    return dict(svc=svc, traced=traced, plain=plain,
                spans=_host_spans(log_dir))


def test_every_span_of_the_path_is_on_the_host_plane(served):
    r0, r1 = served["traced"]
    assert r0.ok and not r0.cache_hit
    assert r1.ok and r1.call_stats["repacked"]
    seen = {name for name, *_ in served["spans"]}
    assert seen == set(SPANS)


def test_no_span_encloses_another_on_the_warm_path(served):
    coalesce = [s for s in served["spans"]
                if s[0] == "spgemm.service.coalesce"]
    assert len(coalesce) == 2
    warm = [s for s in served["spans"] if s[1] >= coalesce[1][1]]
    assert [s[0] for s in warm] == [
        "spgemm.service.coalesce", "spgemm.session.validate",
        "spgemm.session.fingerprint", "spgemm.repack.blockize",
        "spgemm.repack.h2d", "spgemm.execute.dispatch",
        "spgemm.execute.fetch", "spgemm.decode.prune",
        "spgemm.decode.assemble"]
    for (_, _, end, _), (_, start, _, _) in zip(warm, warm[1:]):
        assert end <= start


def test_counters_equal_the_sizes_they_name(served):
    r0, r1 = served["traced"]
    (entry,) = served["svc"].session._cache.values()
    plan = entry.plan
    by_name = {}
    for name, _, _, counters in served["spans"]:
        by_name.setdefault(name, []).append(counters)
    m = _matrix()
    fp_bytes = 2 * (16 + m.indptr.nbytes + m.indices.nbytes + m.data.nbytes)
    assert by_name["spgemm.service.coalesce"] == [
        {"requests": 1, "hashed_bytes": fp_bytes}] * 2
    assert by_name["spgemm.session.fingerprint"] == [
        {"hashed_bytes": fp_bytes}] * 2
    assert by_name["spgemm.session.validate"] == [{"nnz": 2 * m.nnz}] * 2
    # both sides of A·A changed values: both payload stacks were swapped
    (h2d,) = by_name["spgemm.repack.h2d"]
    assert h2d["h2d_bytes"] == plan.a_tiles.nbytes + plan.b_tiles.nbytes
    assert h2d["h2d_bytes"] == entry.args[0].nbytes + entry.args[1].nbytes
    (blk,) = by_name["spgemm.repack.blockize"]
    assert blk["tiles"] == (plan.a_tiles.size + plan.b_tiles.size) \
        // (BS * BS)
    # the output stack without its garbage slot, one fetch per call
    out_bytes = plan.nparts * plan.nc_max * BS * BS * 4
    assert by_name["spgemm.execute.fetch"] == [
        {"d2h_bytes": out_bytes}] * 2
    assert by_name["spgemm.decode.assemble"] == [
        {"nnz": r0.value.nnz}, {"nnz": r1.value.nnz}]


def test_answers_are_the_same_without_the_profiler(served):
    for traced, plain in zip(served["traced"], served["plain"]):
        _same(traced.value, plain.value)


def test_every_span_in_the_program_is_listed():
    """Each ``span("...")`` call in ``src/`` names a span of ``SPANS``, and
    each of ``SPANS`` is recorded somewhere."""
    used = set()
    for f in SRC.rglob("*.py"):
        for node in ast.walk(ast.parse(f.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", None) == "span"
                    and node.args and isinstance(node.args[0], ast.Constant)):
                used.add(node.args[0].value)
    assert used == set(SPANS)

