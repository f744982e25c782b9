"""Named profiler spans on the served multiply's host path.

Every phase of a served multiply that the host does — validation and
fingerprints at session ingress, coalescing in the service, the values-only
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
lengths) or come out of the pass the phase makes anyway; no counter adds
a pass over the entries.

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
#   spgemm.session.validate   : SpGEMMSession.prepare's validation of both
#                               operands; nnz (entries checked) -> ingress_ms
#   spgemm.session.fingerprint: SpGEMMSession.prepare's structure and values
#                               fingerprints of both operands (four blake2b,
#                               the only hashing pass of a served request);
#                               hashed_bytes -> ingress_ms
#   spgemm.service.coalesce   : SpGEMMService.run_pending's grouping of the
#                               prepared requests on their calls' group
#                               keys (no hashing); requests -> ingress_ms
#   spgemm.session.plan       : host planning of a cold key
#   spgemm.session.compile    : placing the plan and compiling its program
#                               (cold key); both set-up, read by no metric
#   spgemm.repack.blockize    : the host part of a values-only repack
#                               (repack_*_payloads): on the 1D ring,
#                               slicing the changed operands' values per
#                               part and casting them; on the SUMMA
#                               engines, re-blockizing them into fresh
#                               payload stacks; tiles (payload tile slots
#                               refilled) -> repack_ms
#   spgemm.repack.h2d         : device_put of the values (1D ring) and
#                               their scatter into fresh stacks, waited
#                               for, or device_put of the host's stacks
#                               (SUMMA); h2d_bytes (bytes put),
#                               entries (values scattered on the device, 0
#                               where the host refilled) -> repack_ms
#   spgemm.execute.dispatch   : launching the compiled program
#                               (asynchronous); mxu_passes (bfloat16 MXU
#                               passes per tile product, from the combine's
#                               dot precision: 6 plus-times, an assumed
#                               count, 1 boolean, 0 min-plus); read by no
#                               metric: its host part is what
#                               untraced_host_ms leaves out
#   spgemm.execute.fetch      : np.asarray of the output: waits for the
#                               device, then copies to the host; d2h_bytes
#                               -> d2h_ms (less the device's busy time)
#   spgemm.decode.structure   : building a 1D ring plan's structural
#                               decode map on its second use (symbolic
#                               A·B on the host, each entry's position in
#                               the output stack); nnz (C's structural
#                               entries); set-up, read by no metric
#   spgemm.decode.prune       : through the plan's map, the gather of C's
#                               structural entries from the stack and the
#                               identity test; without one, resetting
#                               unwritten tiles, the prune-mask scan,
#                               nonzero and the gathers; mapped (1 where
#                               the map served, 0 where the scan ran),
#                               pruned (map only: structural entries
#                               dropped as the identity) -> decode_ms
#   spgemm.decode.assemble    : through the map, nothing where every
#                               structural entry is kept, else the
#                               compaction of the kept entries; without
#                               one, from_coo's sort of the kept entries;
#                               nnz (the answer's) -> decode_ms,
#                               decode_assemble_ms
# untraced_host_ms reads the host time of a request that no span covers.
SPANS = ("spgemm.service.coalesce", "spgemm.session.validate",
         "spgemm.session.fingerprint", "spgemm.session.plan",
         "spgemm.session.compile", "spgemm.repack.blockize",
         "spgemm.repack.h2d", "spgemm.execute.dispatch",
         "spgemm.execute.fetch", "spgemm.decode.prune",
         "spgemm.decode.assemble", "spgemm.decode.structure")


def span(name: str, **counters) -> jax.profiler.TraceAnnotation:
    """A named span (one of :data:`SPANS`) with integer ``counters``, as a
    context manager."""
    return jax.profiler.TraceAnnotation(name, **counters)
