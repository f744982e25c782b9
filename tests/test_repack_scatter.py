"""The 1D ring's values-only repack scatters new values on the device.

For every semiring (with explicit stored 0.0, and stored +inf under
min-plus), ``nparts`` 1 and 4, chunked and unchunked, and a new A, a new
B or both: the stacks the session swaps in equal the host re-blockize
(``refill_ring_stacks``) bit for bit, and the served C equals a cold
re-plan on the new values. ``nparts=4`` runs in one 8-fake-device
subprocess (``_device_harness``); the cases are in ``_repack_cases``.
"""

import json
import textwrap

import numpy as np
import pytest
from _device_harness import run_subprocess
from _repack_cases import CHUNKS, SEMIRINGS, SIDES, all_cases, run_case

from repro.core import SpGEMMSession, erdos_renyi
from repro.core import spgemm_1d_device
from repro.core.spgemm_1d_device import (build_device_plan, slot_map,
                                         repack_ring_payloads)

CASES = [(sr, nparts, chunk, side) for nparts in (1, 4) for sr in SEMIRINGS
         for chunk in CHUNKS for side in SIDES]

FOUR_PARTS_SCRIPT = textwrap.dedent("""
    import json
    from _repack_cases import all_cases
    print("RESULT " + json.dumps(all_cases(4)))
""")


@pytest.fixture(scope="module")
def four_parts():
    """Every ``nparts=4`` case, run once on 8 fake devices."""
    out = run_subprocess(FOUR_PARTS_SCRIPT, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    (line,) = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("semiring,nparts,chunk,side", CASES)
def test_device_scatter_equals_host_repack(semiring, nparts, chunk, side,
                                           request):
    if nparts == 1:
        got = run_case(semiring, nparts, chunk, side)
    else:
        got = request.getfixturevalue("four_parts")[
            f"{semiring}-{nparts}-{chunk}-{side}"]
    assert got == dict(scattered=True, repacked=True, retraced=False,
                       stacks_equal=True, c_equal=True)


def test_slot_map_places_every_entry_and_pads_out_of_range():
    """The map lists, in ascending order, the flat positions ``from_csc``
    wrote the stored entries to, with the CSC data index of each; pads are
    distinct, ascending and out of range."""
    a = erdos_renyi(40, 40, 3.0, seed=5).astype(np.float32)
    a.data[:] = np.arange(1, a.nnz + 1)
    plan = build_device_plan(a, a, 1, bs=8)
    flat = plan.a_tiles.reshape(1, -1)
    assert plan.a_pos.dtype == plan.a_order.dtype == np.int32
    assert np.all(np.diff(plan.a_pos[0]) > 0)
    assert np.array_equal(flat[0, plan.a_pos[0]], a.data[plan.a_order[0]])

    parts = [spgemm_1d_device.BlockSparse(
        tiles=np.zeros((2, 8, 8), np.float32), tile_rows=np.zeros(2, np.int32),
        tile_cols=np.zeros(2, np.int32), shape=(8, 16), orig_shape=(8, 16),
        bs=8, entry_pos=np.asarray(p, np.int64)) for p in ([3, 1], [5])]
    pos, order = slot_map(parts, (2, 2, 8, 8))
    assert pos.tolist() == [[1, 3], [5, 129]]
    assert order.tolist() == [[1, 0], [0, 1]]


def test_plan_past_int32_keeps_the_host_refill(monkeypatch):
    """A plan whose stack int32 cannot address has no slot map; the
    session then refills on the host, and still serves the new values."""
    monkeypatch.setattr(spgemm_1d_device, "POS_LIMIT", 64)
    a = erdos_renyi(40, 40, 3.0, seed=6).astype(np.float32)
    s = SpGEMMSession()
    s.matmul(a, a, bs=8)
    (entry,) = s._cache.values()
    assert entry.plan.a_pos is None and entry.scatter is None
    a2 = a.astype(np.float32)
    a2.data[:] = a.data + 1.0
    c = s.matmul(a2, a2, bs=8)
    assert s.last_call["repacked"]
    host_a, host_b = repack_ring_payloads(entry.plan, a2, a2)
    assert np.array_equal(np.asarray(entry.args[0]), host_a)
    assert np.array_equal(np.asarray(entry.args[1]), host_b)
    ref = spgemm_1d_device.run_device_spgemm(build_device_plan(a2, a2, 1,
                                                               bs=8))
    assert np.array_equal(c.data, ref.data)
    assert np.array_equal(c.indices, ref.indices)
