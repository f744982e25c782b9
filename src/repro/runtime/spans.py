"""Named profiler spans on the served multiply's host path.

Every phase of a served multiply that the host does — coalescing in the
service, validation and fingerprints at session ingress, the values-only
repack, the dispatch and fetch of the compiled program, the decode — runs
inside a :func:`span`: a ``jax.profiler.TraceAnnotation`` whose keyword
counters become stats of the event. The spans are ``TraceMe`` events, so
they land on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
device's ops, on the profiler's common clock, and a trace of a served
multiply splits its host time by phase.

Tracing is on exactly when a profiler is attached; with none attached a
span costs about a microsecond and records nothing. The spans add no
synchronisation: they time what the host does, and the device trace times
the device. Counter values are O(1) to compute (sums of ``nbytes``,
lengths), never a pass over the entries.

The spans are flat leaves: none encloses another on the served path, and
nothing brackets a whole call, so each moment of host time is attributed
to at most one phase. A span inside a retried stage appears once per
attempt.
"""

from __future__ import annotations

import jax

__all__ = ["SPANS", "span"]

# every span the program records, with what it covers and its counters
# (tests/test_spans.py pins the names; the benchmark's per-layer metrics in
# bench/metrics/ read them):
#   spgemm.service.coalesce   : SpGEMMService.run_pending's grouping of the
#                               queued requests by execution key (four
#                               blake2b fingerprints per request);
#                               requests, hashed_bytes -> ingress_ms
#   spgemm.session.validate   : ingress validation of both operands;
#                               nnz (entries checked) -> ingress_ms
#   spgemm.session.fingerprint: the structure fingerprints of the cache key
#                               and the values fingerprints; hashed_bytes
#                               -> ingress_ms
#   spgemm.session.plan       : host planning of a cold key
#   spgemm.session.compile    : placing the plan and compiling its program
#                               (cold key); both set-up, read by no metric
#   spgemm.repack.blockize    : re-blockizing changed operands into fresh
#                               payload stacks; tiles (payload tile slots
#                               refilled) -> repack_ms
#   spgemm.repack.h2d         : device_put of the fresh stacks; h2d_bytes
#                               -> repack_ms
#   spgemm.execute.dispatch   : launching the compiled program
#                               (asynchronous); read by no metric: its host
#                               part is what untraced_host_ms leaves out
#   spgemm.execute.fetch      : np.asarray of the output: waits for the
#                               device, then copies to the host; d2h_bytes
#                               -> d2h_ms (less the device's busy time)
#   spgemm.decode.prune       : resetting unwritten tiles, the prune-mask
#                               scan, nonzero and the gathers -> decode_ms
#   spgemm.decode.assemble    : from_coo of the kept entries; nnz (the
#                               answer's) -> decode_ms
# untraced_host_ms reads the host time of a request that no span covers.
SPANS = ("spgemm.service.coalesce", "spgemm.session.validate",
         "spgemm.session.fingerprint", "spgemm.session.plan",
         "spgemm.session.compile", "spgemm.repack.blockize",
         "spgemm.repack.h2d", "spgemm.execute.dispatch",
         "spgemm.execute.fetch", "spgemm.decode.prune",
         "spgemm.decode.assemble")


def span(name: str, **counters) -> jax.profiler.TraceAnnotation:
    """A named span (one of :data:`SPANS`) with integer ``counters``, as a
    context manager."""
    return jax.profiler.TraceAnnotation(name, **counters)
