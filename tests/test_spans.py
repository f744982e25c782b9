"""The served multiply's host path records named profiler spans.

A cold call and then a values-only call go through
``SpGEMMService.serve`` under the profiler (CPU, Pallas interpreter, a
tiny structure). The recorded ``.xplane.pb`` must hold every span of
``repro.runtime.spans.SPANS`` on the host plane, flat on the warm path,
with counters that equal the sizes they name; the answers are the same
with and without the profiler attached. The dispatch span carries the
semiring's MXU passes per tile product. The second call is the plan's
second use: it builds the structural decode map and decodes through it.
"""

import ast
from pathlib import Path

import numpy as np
import pytest
from _trace import host_spans as _host_spans
from _trace import record as _record

from repro.core import (BOOL_OR_AND, MIN_PLUS, PLUS_TIMES, SpGEMMSession,
                        banded_clustered)
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
        "spgemm.decode.structure", "spgemm.execute.fetch",
        "spgemm.decode.prune", "spgemm.decode.assemble"]
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
    # both sides of A·A changed values: only the values went to the
    # device, and both were scattered into fresh payload stacks there
    (h2d,) = by_name["spgemm.repack.h2d"]
    assert h2d["entries"] == 2 * m.nnz
    assert h2d["h2d_bytes"] == 2 * m.nnz * m.data.itemsize
    assert h2d["h2d_bytes"] < plan.a_tiles.nbytes + plan.b_tiles.nbytes
    (blk,) = by_name["spgemm.repack.blockize"]
    assert blk["tiles"] == (plan.a_tiles.size + plan.b_tiles.size) \
        // (BS * BS)
    # the output stack without its garbage slot, one fetch per call
    out_bytes = plan.nparts * plan.nc_max * BS * BS * 4
    assert by_name["spgemm.execute.fetch"] == [
        {"d2h_bytes": out_bytes}] * 2
    assert by_name["spgemm.decode.assemble"] == [
        {"nnz": r0.value.nnz}, {"nnz": r1.value.nnz}]
    # the cold call scans; the second builds the map and gathers through
    # it, and min-plus of finite weights keeps every structural entry
    assert by_name["spgemm.decode.prune"] == [
        {"mapped": 0}, {"mapped": 1, "pruned": 0}]
    assert by_name["spgemm.decode.structure"] == [{"nnz": r1.value.nnz}]
    assert len(plan.decode_map.indices) == r1.value.nnz


@pytest.mark.parametrize("algorithm,geom", [
    ("1d", dict(nparts=1)), ("2d", dict(grid=1)),
    ("3d", dict(grid=1, layers=1))])
def test_repack_engagement_counter(tmp_path, algorithm, geom):
    """``entries`` on ``spgemm.repack.h2d`` counts the values scattered on
    the device: the changed side's nnz on the 1D ring, with ``h2d_bytes``
    the value bytes; 0 on the SUMMA engines, which put whole stacks."""
    a, b = _matrix(seed=4), _matrix(seed=5)
    b2 = _matrix(seed=5, shift=0.5)
    s = SpGEMMSession()
    kw = dict(algorithm=algorithm, semiring=MIN_PLUS, bs=BS, **geom)
    s.matmul(a, b, **kw)
    _record(tmp_path, lambda: s.matmul(a, b2, **kw))
    assert s.last_call["repacked"] and s.last_call["algorithm"] == algorithm
    (h2d,) = [c for n, _, _, c in _host_spans(tmp_path)
              if n == "spgemm.repack.h2d"]
    (entry,) = s._cache.values()
    if algorithm == "1d":
        assert h2d == {"entries": b2.nnz,
                       "h2d_bytes": b2.nnz * b2.data.itemsize}
    else:
        assert h2d == {"entries": 0, "h2d_bytes": entry.args[1].nbytes}


@pytest.mark.parametrize("semiring, passes", [
    (PLUS_TIMES, 6), (BOOL_OR_AND, 1), (MIN_PLUS, 0)],
    ids=["plus_times", "bool_or_and", "min_plus"])
def test_dispatch_records_the_combines_mxu_passes(tmp_path, semiring,
                                                  passes):
    """``mxu_passes`` on ``spgemm.execute.dispatch``: the bfloat16 MXU
    passes of one float32 tile product, 6 for plus-times at HIGHEST, 1 for
    the boolean combine, 0 for min-plus, which has no dot."""
    a = _matrix()
    s = SpGEMMSession()
    _record(tmp_path, lambda: s.matmul(a, a, semiring=semiring, bs=BS))
    assert [c for n, _, _, c in _host_spans(tmp_path)
            if n == "spgemm.execute.dispatch"] == [{"mxu_passes": passes}]


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

